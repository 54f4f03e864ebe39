"""Blocks that more than one decoder file here is built from, as Fluid
layers: a bias-free projection, a SwiGLU feed-forward (a dense MLP, a
shared expert), a routed layer of gated experts of which this chip holds a
range, and the greedy head of a served step. ``models/lfm2.py`` (trained),
``models/nemotron_h.py``, ``models/laguna.py``, ``models/glm_moe_dsa.py``,
``models/solar_open2.py`` and ``models/kimi_vl.py`` (served) take them from here; each keeps what only it has (its mixers, its state, its programs). The two latent-attention files
(``glm_moe_dsa.py``, ``kimi_vl.py``) also share a head's rotary part turned in
interleaved pairs (:func:`turned`) and the make of a latent row
(:func:`latent_rows`).
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

__all__ = ["fc", "swiglu", "routed_gated_experts", "routed_in_calls",
           "greedy_head", "turned", "latent_rows"]

MOE_PROMPT_ROWS = 4096     # tokens of a prompt a call of the routed layer
# takes: its sorted buffers hold tokens x experts per token rows of the
# hidden width whatever lands here (1.6 GB each at 16,384 tokens x 8 x 6,144)


def fc(x, size, name, nfd=1):
    """``x @ <name>.w``, no bias, over the last ``x.ndim - nfd`` axes."""
    return layers.fc(x, size, num_flatten_dims=nfd,
                     param_attr=ParamAttr(name=name + ".w"), bias_attr=False)


def swiglu(h, width, out_width, name, nfd=1):
    """``W2 (silu(W1 h) * W3 h)``: ``<name>.w1`` / ``.w3`` (in, width),
    ``<name>.w2`` (width, out_width)."""
    gate = layers.swish(fc(h, width, name + ".w1", nfd))
    return fc(layers.elementwise_mul(gate, fc(h, width, name + ".w3", nfd)),
              out_width, name + ".w2", nfd)


def routed_gated_experts(flat, num_experts, top_k, held, width, name, scope,
                         live=None, **router):
    """A chip's share of a routed layer of SwiGLU experts over ``flat``
    (T, H): the router over all ``num_experts`` (``<name>.gate``; further
    arguments go to ``layers.moe_route_topk``) under the name scope
    ``<scope>.route``, the held experts' part (``parallel.moe.
    held_experts_ffn(gated=True)``, ``<name>.experts``) under
    ``<scope>.experts``. ``live`` (T, 1) marks the rows that carry a token.
    -> (the held experts' part (T, H), its counts (4,) int32)."""
    from ..parallel.moe import held_experts_ffn

    with fluid.name_scope(scope + ".route"):
        idx, wt = layers.moe_route_topk(flat, num_experts, top_k,
                                        name + ".gate", **router)
    with fluid.name_scope(scope + ".experts"):
        return held_experts_ffn(flat, idx, wt, held, width,
                                name + ".experts", live=live, gated=True)


def routed_in_calls(flat, live, counts, *args, **router):
    """:func:`routed_gated_experts` over ``flat`` (T, H), a long prompt's
    rows in calls of MOE_PROMPT_ROWS tokens (where that divides T; else one
    call): the router chooses a token at a time, so the cuts change no
    number. ``live`` (T, 1) or None. Each call's counts are appended to
    ``counts``. -> the held experts' part (T, H)."""
    t = flat.shape[0]
    cuts = (range(0, t, MOE_PROMPT_ROWS)
            if t and t > MOE_PROMPT_ROWS and t % MOE_PROMPT_ROWS == 0
            else [None])
    parts = []
    for at in cuts:
        rows, alive = flat, live
        if at is not None:
            rows = layers.slice(flat, [0], [at], [at + MOE_PROMPT_ROWS])
            alive = live if live is None else layers.slice(
                live, [0], [at], [at + MOE_PROMPT_ROWS])
        part, c = routed_gated_experts(rows, *args, live=alive, **router)
        parts.append(part)
        counts.append(c)
    return parts[0] if len(parts) == 1 else layers.concat(parts, axis=0)


def greedy_head(x, vocab, eps, norm_name, head_name):
    """Final RMS norm, float32 logits over the held rows of the vocabulary,
    the greedy token (B, 1) int64. -> (logits, token)."""
    x = layers.rms_norm(x, norm_name, epsilon=eps)
    logits = layers.dense_acc32(x, vocab, head_name)
    nxt = layers.cast(
        layers.unsqueeze(layers.argmax(logits, axis=-1), [1]), "int64")
    return logits, nxt


def turned(x, lead, count, width, cfg, first, pos=None):
    """(B, lead, count * width) -> the same, of every head of ``width`` the
    first (``first``) or the last ``cfg.rope_dim`` dimensions turned by the
    row's position (``cfg.theta``), interleaved pairs."""
    x = layers.reshape(x, [-1, lead, count, width])
    if first:
        x = layers.rotary_embedding(x, cfg.theta, pos=pos,
                                    rotary_dim=cfg.rope_dim, interleaved=True)
    else:
        keep = width - cfg.rope_dim
        x = layers.concat([
            layers.slice(x, [3], [0], [keep]),
            layers.rotary_embedding(layers.slice(x, [3], [keep], [width]),
                                    cfg.theta, pos=pos, interleaved=True)],
            axis=3)
    return layers.reshape(x, [-1, lead, count * width])


def latent_rows(u, lead, cfg, n, pos=None):
    """The latent rows of a latent-attention layer over u (B, lead, H) ->
    (B, lead, cfg.latent_width): ``[RMSNorm(ckv) | k_rope turned | zeros]``
    from ``u <n>.mla.kv_a`` (``cfg.kv_rank`` + ``cfg.rope_dim`` wide), what
    the layer's cache holds of a position."""
    held = cfg.kv_rank + cfg.rope_dim
    kv = fc(u, held, n + ".mla.kv_a", 2)
    return layers.concat([
        layers.rms_norm(layers.slice(kv, [2], [0], [cfg.kv_rank]),
                        n + ".mla.kv_norm", epsilon=cfg.eps),
        turned(layers.slice(kv, [2], [cfg.kv_rank], [held]),
               lead, 1, cfg.rope_dim, cfg, True, pos),
        layers.fill_constant_batch_size_like(
            kv, shape=[-1, lead, cfg.latent_width - held], dtype=kv.dtype,
            value=0.0)], axis=2)
