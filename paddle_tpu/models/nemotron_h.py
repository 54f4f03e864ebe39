"""Nemotron-H family decoder (Mamba-2 + grouped-query attention + latent
mixture of experts) as slot-decode programs for ``serving.DecodeEngine``.

Every block is pre-norm residual, ``x <- x + mixer(RMSNorm(x))``, and the
pattern string gives one mixer per block: ``M`` Mamba-2, ``*`` attention
(no position term: the state-space layers carry order), ``E`` a latent
mixture of experts of which this chip holds a contiguous range
(``parallel.moe.held_experts_ffn``) plus a shared expert. After the last
block a final RMSNorm and an untied head. No bias except the convolution's.

The model declares the state a sequence carries (:meth:`NemotronHConfig.
decode_model`): per attention block a K and a V of ``rows`` (one per
position, ``kv_heads * head_dim`` wide), per Mamba-2 block a convolution
window and a state-space state of ``fixed`` size, per expert block nothing.

Weights are bfloat16 (``A_log``, ``D``, ``dt_bias`` and the router's score
correction float32); products take bfloat16 operands and accumulate in
float32; the router, ``dt``, ``exp(dt A)`` and the state-space state are
float32; the residual stream and K/V are bfloat16.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from .decode_utils import (DecodeModel, StateEntry, require_rows_only,
                           update_cache)
from .decoder_blocks import fc as _fc
from .decoder_blocks import greedy_head

__all__ = ["NemotronHConfig", "build_prefill", "build_step", "param_shapes"]

DTYPE = "bfloat16"


class NemotronHConfig:
    """Sizes under the names of the family's ``config.json``. ``held`` is
    the contiguous range ``(first, count)`` of each layer's
    ``num_experts`` routed experts that live here; the router keeps its
    full width. ``vocab`` rows of the embedding and of the head are held."""

    def __init__(self, pattern, vocab, hidden, heads, kv_heads, head_dim,
                 mamba_heads, mamba_head_dim, n_groups, ssm_state,
                 conv_kernel, chunk_size, num_experts, held, top_k,
                 moe_latent, moe_ffn, shared_ffn, routed_scale, eps=1e-5):
        if set(pattern) - set("M*E"):
            raise ValueError("pattern %r has blocks other than M, * and E"
                             % (pattern,))
        self.pattern = str(pattern)
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim = int(head_dim)
        self.mamba_heads = int(mamba_heads)
        self.mamba_head_dim = int(mamba_head_dim)
        self.n_groups, self.ssm_state = int(n_groups), int(ssm_state)
        self.conv_kernel, self.chunk_size = int(conv_kernel), int(chunk_size)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= self.held[0] + self.held[1] \
                <= self.num_experts:
            raise ValueError("held experts %r outside [0, %d)"
                             % (self.held, self.num_experts))
        self.moe_latent, self.moe_ffn = int(moe_latent), int(moe_ffn)
        self.shared_ffn = int(shared_ffn)
        self.routed_scale, self.eps = float(routed_scale), float(eps)

    @classmethod
    def from_hf(cls, m, router_experts=None, first_expert=0):
        """From a dict with the keys of the published ``config.json``.
        ``n_routed_experts`` is the number of experts held here, from
        ``first_expert`` on, of the ``router_experts`` (default: the same
        number) that the router spans."""
        count = int(m["n_routed_experts"])
        return cls(
            pattern=m["hybrid_override_pattern"], vocab=m["vocab_size"],
            hidden=m["hidden_size"], heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            mamba_heads=m["mamba_num_heads"],
            mamba_head_dim=m["mamba_head_dim"], n_groups=m["n_groups"],
            ssm_state=m["ssm_state_size"], conv_kernel=m["conv_kernel"],
            chunk_size=m["chunk_size"],
            num_experts=int(router_experts or count),
            held=(int(first_expert), count), top_k=m["num_experts_per_tok"],
            moe_latent=m["moe_latent_size"],
            moe_ffn=m["moe_intermediate_size"],
            shared_ffn=m["moe_shared_expert_intermediate_size"],
            routed_scale=m["routed_scaling_factor"],
            eps=m.get("layer_norm_epsilon", 1e-5))

    # -- derived sizes ---------------------------------------------------
    @property
    def d_inner(self):
        return self.mamba_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state

    @property
    def kv_width(self):
        return self.kv_heads * self.head_dim

    @property
    def expert_layers(self):
        return self.pattern.count("E")

    def decode_model(self, cache_len, kv_dtype="fp32"):
        """Builders and state declaration for ``serving.DecodeEngine``.
        ``kv_dtype`` names a residency of K/V rows alone and is refused
        for anything but the default."""
        import jax.numpy as jnp

        state = []
        for i, kind in enumerate(self.pattern):
            if kind == "M":
                state.append(StateEntry(
                    "conv_%d" % i, (self.conv_kernel - 1, self.conv_dim),
                    jnp.bfloat16, "fixed"))
                state.append(StateEntry(
                    "ssm_%d" % i, (self.mamba_heads, self.mamba_head_dim,
                                   self.ssm_state), np.float32, "fixed"))
            elif kind == "*":
                for part in ("k", "v"):
                    state.append(StateEntry(
                        "%s_%d" % (part, i), (int(cache_len), self.kv_width),
                        jnp.bfloat16, "rows"))
        model = DecodeModel(self, state, build_prefill, build_step,
                            step_counters=self._step_counters)
        if kv_dtype != "fp32":
            require_rows_only(model, "kv_dtype=%r" % (kv_dtype,))
            raise ValueError("this model's K/V rows are bfloat16; kv_dtype "
                             "%r is not one of its residencies"
                             % (kv_dtype,))
        return model

    def _step_counters(self, aux, live):
        """The step's ``(expert layers, 3)`` counts -> lifetime counters:
        assignments that landed on held experts, all assignments of the
        live tokens, and the sums over layers of the largest count on one
        held expert and of the held experts that got any."""
        return {"moe_assignments_held": int(aux[:, 0].sum()),
                "moe_assignments_total":
                    int(live) * self.top_k * self.expert_layers,
                "moe_expert_load_max_sum": int(aux[:, 1].sum()),
                "moe_experts_touched_sum": int(aux[:, 2].sum())}


def _moe(h, cfg, n, live):
    """LatentMoE on (T, H) rows: routed path in the latent over the held
    experts, shared expert on ``h`` itself. -> (out (T, H), counts, the
    held experts' part (T, latent) before its up-projection)."""
    from ..parallel.moe import held_experts_ffn

    idx, wt = layers.moe_route_topk(h, cfg.num_experts, cfg.top_k,
                                    n + ".gate", scale=cfg.routed_scale)
    lat = _fc(h, cfg.moe_latent, n + ".down")
    held, counts = held_experts_ffn(lat, idx, wt, cfg.held, cfg.moe_ffn,
                                    n + ".experts", live=live)
    routed = _fc(held, cfg.hidden, n + ".up")
    shared = _fc(layers.relu_squared(_fc(h, cfg.shared_ffn,
                                         n + ".shared.fc1")),
                 cfg.hidden, n + ".shared.fc2")
    return layers.elementwise_add(routed, shared), counts, held


def _mamba_in(h, cfg, n, nfd):
    """[z | xBC | dt] = W_in h."""
    return layers.split(
        _fc(h, cfg.d_inner + cfg.conv_dim + cfg.mamba_heads, n + ".in",
            nfd=nfd),
        [cfg.d_inner, cfg.conv_dim, cfg.mamba_heads], dim=-1)


def _mamba_out(y, z, cfg, n, nfd):
    y = layers.rms_norm(y, n + ".norm", epsilon=cfg.eps,
                        groups=cfg.n_groups, gate=z)
    return _fc(y, cfg.hidden, n + ".out", nfd=nfd)


def _ssm_args(cfg):
    return dict(heads=cfg.mamba_heads, head_dim=cfg.mamba_head_dim,
                groups=cfg.n_groups, state_size=cfg.ssm_state)


def _head(x, cfg):
    """Final norm, float32 logits over the held rows, greedy token."""
    return greedy_head(x, cfg.vocab, cfg.eps, "nh.norm_f", "nh.head")


def _embed(ids, cfg):
    return layers.embedding(ids, size=[cfg.vocab, cfg.hidden], dtype=DTYPE,
                            param_attr=ParamAttr(name="nh.emb"))


def build_prefill(cfg, prompt_len, cache_len):
    """Slot-prefill program: one pass over a right-padded prompt bucket.
    Feeds ``nh_prefill_ids`` (B, prompt_len) int64 and ``nh_prefill_len``
    (B, 1). Fetches the greedy token after the last real position and the
    sequence's state in the declaration's order, each ``(B,) + shape``:
    K/V rows zero past ``len``; the convolution window and the state-space
    state AT THE LAST REAL TOKEN (padded positions get ``dt = 0`` and the
    window is cut at ``len``), not at the bucket's end. ``moe_routed``
    names, per expert layer, the held experts' part ``(B * prompt_len,
    latent)`` for whoever wants to fetch it (the engine does not)."""
    from .gpt import _row_coords

    if not 1 <= prompt_len <= cache_len:
        raise ValueError("need 1 <= prompt_len (%d) <= cache_len (%d)"
                         % (prompt_len, cache_len))
    ids = fluid.data("nh_prefill_ids", shape=[None, prompt_len],
                     dtype="int64")
    plen = fluid.data("nh_prefill_len", shape=[None, 1], dtype="int64")
    x = layers.reshape(_embed(ids, cfg), [-1, prompt_len, cfg.hidden])
    steps = layers.unsqueeze(layers.range(0, prompt_len, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, plen), DTYPE)   # (B, P)
    valid3 = layers.unsqueeze(valid, [2])
    state, counts, routed = [], [], []
    for i, kind in enumerate(cfg.pattern):
        n = "nh%d" % i
        h = layers.rms_norm(x, n + ".norm", epsilon=cfg.eps)
        if kind == "M":
            z, xbc, dt = _mamba_in(h, cfg, n + ".mixer", 2)
            xbc, window = layers.causal_conv1d(
                xbc, cfg.conv_kernel, n + ".mixer.conv", length=plen)
            y, hs = layers.mamba2_scan(xbc, dt, n + ".mixer", length=plen,
                                       chunk=cfg.chunk_size, **_ssm_args(cfg))
            y = _mamba_out(y, z, cfg, n + ".mixer", 2)
            state += [window, hs]
        elif kind == "*":
            q = _fc(h, cfg.heads * cfg.head_dim, n + ".attn.q", 2)
            k = _fc(h, cfg.kv_width, n + ".attn.k", 2)
            v = _fc(h, cfg.kv_width, n + ".attn.v", 2)
            y = _fc(layers.gqa_attention(q, k, v, cfg.heads, cfg.kv_heads),
                    cfg.hidden, n + ".attn.o", 2)
            k = layers.elementwise_mul(k, valid3)
            v = layers.elementwise_mul(v, valid3)
            if cache_len > prompt_len:
                pad = layers.fill_constant_batch_size_like(
                    ids, shape=[-1, cache_len - prompt_len, cfg.kv_width],
                    dtype=DTYPE, value=0.0)
                k = layers.concat([k, pad], axis=1)
                v = layers.concat([v, pad], axis=1)
            state += [k, v]
        else:
            flat = layers.reshape(h, [-1, cfg.hidden])
            y, c, r = _moe(flat, cfg, n + ".moe",
                           layers.reshape(valid, [-1, 1]))
            y = layers.reshape(y, [-1, prompt_len, cfg.hidden])
            counts.append(c)
            routed.append(r)
        x = layers.elementwise_add(x, y)
    one = layers.fill_constant([1], "int64", 1)
    x_last = layers.gather_nd(x, _row_coords(
        layers.elementwise_sub(plen, one)))                     # (B, H)
    logits, nxt = _head(x_last, cfg)
    return {"ids": ids, "len": plen, "next": nxt, "logits": logits,
            "state": state, "moe_counts": counts, "moe_routed": routed,
            "feed_names": ["nh_prefill_ids", "nh_prefill_len"],
            "fetch_vars": [nxt] + state}


def build_step(cfg, cache_len):
    """One decode step for all slots. Feeds ``nh_step_tok`` / ``nh_step_pos``
    (S, 1) int64 and the state buffers, one feed per declared entry
    (``cache_feed_names``), all donated: K and V get one row written at
    each slot's ``pos``, each window and state-space state is replaced.
    Fetches the greedy tokens, the updated state in the same order, and
    ``moe_counts`` (expert layers, 3) int32: per expert layer the live
    tokens' assignments that landed on held experts, the largest count on
    one held expert and the held experts that got any. A slot with ``pos == 0`` is dead: its row is
    computed and ignored, and it is routed to no expert."""
    tok = fluid.data("nh_step_tok", shape=[None, 1], dtype="int64")
    pos = fluid.data("nh_step_pos", shape=[None, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("nh_step_" + e.name, shape=[None] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    by_name = {e.name: f for e, f in zip(decl, feeds)}
    x = layers.reshape(_embed(tok, cfg), [-1, cfg.hidden])       # (S, H)
    live = layers.cast(layers.greater_than(
        pos, layers.fill_constant([1], "int64", 0)), DTYPE)      # (S, 1)
    state, counts = [], []
    for i, kind in enumerate(cfg.pattern):
        n = "nh%d" % i
        h = layers.rms_norm(x, n + ".norm", epsilon=cfg.eps)
        if kind == "M":
            z, xbc, dt = _mamba_in(h, cfg, n + ".mixer", 1)
            xbc, window = layers.causal_conv1d(
                layers.unsqueeze(xbc, [1]), cfg.conv_kernel,
                n + ".mixer.conv", state=by_name["conv_%d" % i])
            y, hs = layers.mamba2_step(
                layers.squeeze(xbc, [1]), dt, by_name["ssm_%d" % i],
                n + ".mixer", **_ssm_args(cfg))
            y = _mamba_out(y, z, cfg, n + ".mixer", 1)
            state += [window, hs]
        elif kind == "*":
            q = _fc(h, cfg.heads * cfg.head_dim, n + ".attn.q")
            k = update_cache(by_name["k_%d" % i], layers.unsqueeze(
                _fc(h, cfg.kv_width, n + ".attn.k"), [1]),
                pos=pos, per_row=True)
            v = update_cache(by_name["v_%d" % i], layers.unsqueeze(
                _fc(h, cfg.kv_width, n + ".attn.v"), [1]),
                pos=pos, per_row=True)
            a = layers.gqa_attention(layers.unsqueeze(q, [1]), k, v,
                                     cfg.heads, cfg.kv_heads, pos=pos)
            y = _fc(layers.squeeze(a, [1]), cfg.hidden, n + ".attn.o")
            state += [k, v]
        else:
            y, c, _ = _moe(h, cfg, n + ".moe", live)
            counts.append(c)
        x = layers.elementwise_add(x, y)
    logits, nxt = _head(x, cfg)
    aux = layers.stack(counts, axis=0) if counts else \
        layers.fill_constant([0, 3], "int32", 0)
    names = [f.name for f in feeds]
    return {"tok": tok, "pos": pos, "next": nxt, "logits": logits,
            "state": state, "moe_counts": aux,
            "feed_names": ["nh_step_tok", "nh_step_pos"] + names,
            "cache_feed_names": names,
            "fetch_vars": [nxt] + state + [aux]}


def param_shapes(cfg):
    """{name: (shape, dtype name)} of every parameter the programs read:
    what a checkpoint for this model holds."""
    h = cfg.hidden
    out = {"nh.emb": ((cfg.vocab, h), DTYPE),
           "nh.head.w": ((h, cfg.vocab), DTYPE),
           "nh.norm_f.w": ((h,), DTYPE)}
    for i, kind in enumerate(cfg.pattern):
        n = "nh%d" % i
        out[n + ".norm.w"] = ((h,), DTYPE)
        if kind == "M":
            m = n + ".mixer"
            out.update({
                m + ".in.w": ((h, cfg.d_inner + cfg.conv_dim
                               + cfg.mamba_heads), DTYPE),
                m + ".conv.w": ((cfg.conv_dim, cfg.conv_kernel), DTYPE),
                m + ".conv.b": ((cfg.conv_dim,), DTYPE),
                m + ".dt_bias": ((cfg.mamba_heads,), "float32"),
                m + ".A_log": ((cfg.mamba_heads,), "float32"),
                m + ".D": ((cfg.mamba_heads,), "float32"),
                m + ".norm.w": ((cfg.d_inner,), DTYPE),
                m + ".out.w": ((cfg.d_inner, h), DTYPE)})
        elif kind == "*":
            a = n + ".attn"
            out.update({
                a + ".q.w": ((h, cfg.heads * cfg.head_dim), DTYPE),
                a + ".k.w": ((h, cfg.kv_width), DTYPE),
                a + ".v.w": ((h, cfg.kv_width), DTYPE),
                a + ".o.w": ((cfg.heads * cfg.head_dim, h), DTYPE)})
        else:
            e = n + ".moe"
            out.update({
                e + ".gate.w": ((h, cfg.num_experts), DTYPE),
                e + ".gate.bias": ((cfg.num_experts,), "float32"),
                e + ".down.w": ((h, cfg.moe_latent), DTYPE),
                e + ".up.w": ((cfg.moe_latent, h), DTYPE),
                e + ".experts.w1": ((cfg.held[1], cfg.moe_latent,
                                     cfg.moe_ffn), DTYPE),
                e + ".experts.w2": ((cfg.held[1], cfg.moe_ffn,
                                     cfg.moe_latent), DTYPE),
                e + ".shared.fc1.w": ((h, cfg.shared_ffn), DTYPE),
                e + ".shared.fc2.w": ((cfg.shared_ffn, h), DTYPE)})
    return out
