"""Blocks that more than one decoder file here is built from, as Fluid
layers: a bias-free projection, a SwiGLU feed-forward (a dense MLP, a
shared expert), a routed layer of gated experts of which this chip holds a
range, and the greedy head of a served step. ``models/lfm2.py`` (trained),
``models/nemotron_h.py`` and ``models/laguna.py`` (served) take them from
here; each keeps what only it has (its mixers, its state, its programs).
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

__all__ = ["fc", "swiglu", "routed_gated_experts", "greedy_head"]


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


def greedy_head(x, vocab, eps, norm_name, head_name):
    """Final RMS norm, float32 logits over the held rows of the vocabulary,
    the greedy token (B, 1) int64. -> (logits, token)."""
    x = layers.rms_norm(x, norm_name, epsilon=eps)
    logits = layers.dense_acc32(x, vocab, head_name)
    nxt = layers.cast(
        layers.unsqueeze(layers.argmax(logits, axis=-1), [1]), "int64")
    return logits, nxt
