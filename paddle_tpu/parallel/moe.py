"""Mixture-of-Experts FFN with expert parallelism over an 'ep' mesh
axis.

Not in the reference (Fluid 1.5 predates MoE) — included because
expert parallelism is a first-class sharding dimension on TPU pods and
the multichip dryrun exercises dp/tp/sp/pp/ep. Design is the standard
TPU Switch-Transformer recipe (top-1 routing, capacity-bounded einsum
dispatch — Fedus et al. 2021, public GSPMD MoE pattern), built entirely
from this framework's layer ops so it rides the same Program → one-XLA-
module path:

- router: fc -> softmax -> top-1 (argmax + one_hot), straight-through
  scaling by the winning probability
- capacity C per expert; a token's slot comes from an exclusive cumsum
  over its expert's one-hot column; overflow tokens are DROPPED (their
  residual path carries them — the standard Switch behavior)
- dispatch/combine are batched matmuls over an explicit (S, E, C)
  dispatch tensor; expert FFN weights are rank-3 (E, H, F)/(E, F, H)
  batched matmuls that GSPMD shards over 'ep' (one expert group per
  mesh slice; XLA inserts the token all-to-all on ICI)
- aux load-balancing loss: E * sum(fraction_tokens_e * mean_prob_e)

``moe_ep_rules(name)`` gives the ShardingRule patterns for the expert
dim; on a mesh without 'ep' the same program runs replicated.

:func:`held_experts_ffn` is the other way to spread experts: one chip's
share of an expert-parallel layer as a program of its own. The router
(``layers.moe_route_topk``) keeps its published width and its experts per
token; the layer is told the contiguous range of experts it holds and
computes their part of the result, with no capacity and no dropped token.
"""
from jax.sharding import PartitionSpec as P

__all__ = ["switch_ffn", "moe_ep_rules", "held_experts_ffn"]


def switch_ffn(x, num_experts, d_ff, capacity_factor=1.25, act="gelu",
               name="moe"):
    """Switch-Transformer FFN over (B, T, H) input. Returns
    (y (B, T, H), aux_loss scalar)."""
    import math

    from ..fluid import layers
    from ..fluid.param_attr import ParamAttr

    if any(d is None or int(d) < 0 for d in x.shape):
        raise ValueError(
            "switch_ffn needs a fully static (B, T, H) input shape to "
            "compute expert capacity; got %r. Declare the batch dim "
            "explicitly (fluid.data(..., shape=[batch, T, H]) rather "
            "than the default None batch)." % (tuple(x.shape),))
    T, H = int(x.shape[1]), int(x.shape[2])
    E = int(num_experts)
    F = int(d_ff)

    xs = layers.reshape(x, [-1, H])                       # (S, H)
    gate_logits = layers.fc(
        xs, E, param_attr=ParamAttr(name=name + ".gate.w"),
        bias_attr=False)
    probs = layers.softmax(gate_logits)                   # (S, E)
    top_prob = layers.reduce_max(probs, dim=[-1])         # (S,)
    expert_idx = layers.argmax(probs, axis=-1)            # (S,)
    onehot = layers.one_hot(
        layers.unsqueeze(layers.cast(expert_idx, "int64"), [1]), E)

    # slot within the chosen expert, capacity-bounded
    position = layers.elementwise_mul(
        layers.cumsum(onehot, axis=0, exclusive=True), onehot)
    pos_tok = layers.reduce_sum(position, dim=[-1])       # (S,)
    # static capacity: tokens-per-expert x factor (S is static under jit)
    S_static = 1
    for d in x.shape[:-1]:
        S_static *= int(d)
    C = max(4, int(math.ceil(S_static / E * float(capacity_factor))))
    keep = layers.cast(
        layers.less_than(pos_tok,
                         layers.fill_constant([1], "float32", float(C))),
        "float32")                                        # (S,)
    pos_oh = layers.one_hot(
        layers.unsqueeze(layers.cast(pos_tok, "int64"), [1]), C)
    dispatch = layers.elementwise_mul(
        layers.elementwise_mul(
            layers.unsqueeze(onehot, [2]),                # (S, E, 1)
            layers.unsqueeze(pos_oh, [1])),               # (S, 1, C)
        layers.reshape(keep, [-1, 1, 1]))                 # (S, E, C)

    # dispatch: (E, C, S) @ (S, H) -> (E, C, H)
    expert_in = layers.matmul(
        layers.transpose(dispatch, [1, 2, 0]), xs)
    w1 = layers.create_parameter([E, H, F], "float32",
                                 name=name + ".w1")
    b1 = layers.create_parameter([E, 1, F], "float32",
                                 name=name + ".b1",
                                 is_bias=True)
    w2 = layers.create_parameter([E, F, H], "float32",
                                 name=name + ".w2")
    b2 = layers.create_parameter([E, 1, H], "float32",
                                 name=name + ".b2",
                                 is_bias=True)
    h1 = layers.elementwise_add(layers.matmul(expert_in, w1), b1)
    h1 = getattr(layers, act)(h1)
    out_e = layers.elementwise_add(layers.matmul(h1, w2), b2)  # (E,C,H)

    # combine: (S, E*C) @ (E*C, H), scaled by the winning gate prob
    combine = layers.elementwise_mul(
        dispatch, layers.reshape(top_prob, [-1, 1, 1]))
    y = layers.matmul(layers.reshape(combine, [-1, E * C]),
                      layers.reshape(out_e, [E * C, H]))
    y = layers.reshape(y, [-1, T, H])

    # Switch aux loss: E * sum_e mean(tokens routed to e) * mean(prob_e)
    frac = layers.reduce_mean(onehot, dim=[0])            # (E,)
    mprob = layers.reduce_mean(probs, dim=[0])            # (E,)
    aux = layers.scale(
        layers.reduce_sum(layers.elementwise_mul(frac, mprob)),
        scale=float(E))
    return y, aux


def held_experts_ffn(x, index, weight, held, d_ff, name, live=None,
                     gated=False):
    """The held experts' part of a routed layer (no bias): ``sum over the
    chosen experts e in [held[0], held[0] + held[1]) of weight_e * W2_e
    relu(W1_e x)^2``, or with ``gated`` of ``weight_e * W2_e (silu(W1_e x)
    * W3_e x)`` (SwiGLU experts).

    ``x`` (T, D); ``index``/``weight`` (T, k) from a router over ALL the
    layer's experts, the weights already normalised over all k chosen, so
    a token whose experts lie on other chips adds nothing from them and
    the shares of all chips sum to the whole layer. ``live`` (T, 1) masks
    rows that carry no token (a dead decode slot). The products are
    grouped by expert (``ops.hybrid_ops.grouped_dot``: the Pallas kernel
    ``gmm`` on the TPU, ``lax.ragged_dot`` elsewhere): their work grows
    with the assignments that land here. On one chip the layer runs
    without its exchange; nothing stands in for the absent chips. The
    gated layer trains: gradients reach ``x``, the matrices and, through
    ``weight``, the router.

    Parameters ``<name>.w1`` (held, D, d_ff), ``<name>.w2`` (held, d_ff,
    D), gated also ``<name>.w3`` (held, D, d_ff). Returns ``(out (T, D),
    counts)``: ``counts`` is int32 ``[assignments that landed on held
    experts, the largest count on one held expert, held experts that got
    any]``, gated also the sorted rows its loops covered."""
    from ..fluid.layer_helper import LayerHelper
    from ..fluid.param_attr import ParamAttr

    first, count = int(held[0]), int(held[1])
    d = int(x.shape[-1])
    helper = LayerHelper("held_experts_ffn")

    def matrix(part, shape):
        return [helper.create_parameter(ParamAttr(name=name + part), shape,
                                        x.dtype)]

    inputs = {"X": [x], "Index": [index], "Weight": [weight],
              "W1": matrix(".w1", [count, d, int(d_ff)]),
              "W2": matrix(".w2", [count, int(d_ff), d])}
    if gated:
        inputs["W3"] = matrix(".w3", [count, d, int(d_ff)])
    if live is not None:
        inputs["Live"] = [live]
    out = helper.create_variable_for_type_inference(x.dtype)
    out.shape = tuple(x.shape)
    counts = helper.create_variable_for_type_inference("int32")
    counts.shape = (4 if gated else 3,)
    helper.append_op(type="held_experts_ffn", inputs=inputs,
                     outputs={"Out": [out], "Counts": [counts]},
                     attrs={"first_expert": first})
    return out, counts


def moe_ep_rules(name="moe"):
    """Shard the expert dim of the FFN weights over 'ep'."""
    import re

    esc = re.escape(name)
    return [
        (esc + r"\.w1$", P("ep", None, None)),
        (esc + r"\.b1$", P("ep", None, None)),
        (esc + r"\.w2$", P("ep", None, None)),
        (esc + r"\.b2$", P("ep", None, None)),
    ]
