"""Layers of hybrid state-space / attention / sparse-expert decoders: RMS
norm (plain, grouped, gated), squared ReLU, a causal depthwise convolution
with carried window, the Mamba-2 recurrence (chunked scan and one step),
a rotary position term, grouped-query attention, sigmoid top-k routing and
a product that keeps its float32 accumulator. The lowerings are ``paddle_tpu/ops/hybrid_ops.py``;
the held-experts layer built on the router is
``paddle_tpu.parallel.moe.held_experts_ffn``.
"""
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["rms_norm", "relu_squared", "dense_acc32", "causal_conv1d",
           "mamba2_scan", "mamba2_step", "rotary_embedding", "gqa_attention",
           "moe_route_topk"]


def _out(helper, dtype, shape):
    v = helper.create_variable_for_type_inference(dtype)
    v.shape = tuple(shape)
    return v


def _param(helper, name, shape, dtype, trainable=True, learning_rate=1.0):
    return helper.create_parameter(
        ParamAttr(name=name, trainable=trainable,
                  learning_rate=learning_rate), list(shape), dtype)


def rms_norm(x, name, epsilon=1e-5, groups=1, gate=None):
    """``w * x / sqrt(mean(x^2) + eps)`` over the last axis (or each of
    ``groups`` equal parts of it), computed in float32; ``gate`` multiplies
    the input by ``silu(gate)`` first. The weight is ``<name>.w``."""
    helper = LayerHelper("rms_norm")
    w = _param(helper, name + ".w", [x.shape[-1]], x.dtype)
    inputs = {"X": [x], "Scale": [w]}
    if gate is not None:
        inputs["Gate"] = [gate]
    out = _out(helper, x.dtype, x.shape)
    helper.append_op(type="rms_norm", inputs=inputs, outputs={"Y": [out]},
                     attrs={"epsilon": float(epsilon), "groups": int(groups)})
    return out


def relu_squared(x):
    helper = LayerHelper("relu_squared")
    out = _out(helper, x.dtype, x.shape)
    helper.append_op(type="relu_squared", inputs={"X": [x]},
                     outputs={"Out": [out]})
    return out


def dense_acc32(x, size, name):
    """``x @ <name>.w`` with the float32 accumulator kept (no rounding to
    the operands' dtype): router logits and the output head."""
    helper = LayerHelper("dense_acc32")
    w = _param(helper, name + ".w", [x.shape[-1], size], x.dtype)
    out = _out(helper, "float32", tuple(x.shape[:-1]) + (size,))
    helper.append_op(type="dense_acc32", inputs={"X": [x], "W": [w]},
                     outputs={"Out": [out]})
    return out


def causal_conv1d(x, kernel, name, state=None, length=None, bias=True,
                  activation="silu"):
    """Depthwise causal convolution over (B, T, C) with the window carried:
    ``state`` (B, kernel-1, C) holds the columns before ``x``; returns
    ``(out, state_out)`` where ``state_out`` precedes the next call (with
    ``length`` (B, 1): the columns ending at each row's last real
    position). Weights ``<name>.w`` (C, kernel), ``<name>.b`` (C)."""
    helper = LayerHelper("causal_conv1d")
    c = x.shape[-1]
    inputs = {"X": [x], "Weight": [_param(helper, name + ".w", [c, kernel],
                                          x.dtype)]}
    if bias:
        inputs["Bias"] = [_param(helper, name + ".b", [c], x.dtype)]
    if state is not None:
        inputs["State"] = [state]
    if length is not None:
        inputs["Len"] = [length]
    out = _out(helper, x.dtype, x.shape)
    sdt = state.dtype if state is not None else x.dtype
    state_out = _out(helper, sdt, (x.shape[0], kernel - 1, c))
    helper.append_op(type="causal_conv1d", inputs=inputs,
                     outputs={"Out": [out], "StateOut": [state_out]},
                     attrs={"activation": activation or ""})
    return out, state_out


def _ssm(op, xbc, dt, name, heads, head_dim, groups, state_size, extra,
         y_shape, state_shape, attrs):
    helper = LayerHelper(op)
    inputs = {"XBC": [xbc], "Dt": [dt],
              "DtBias": [_param(helper, name + ".dt_bias", [heads],
                                "float32")],
              "ALog": [_param(helper, name + ".A_log", [heads], "float32")],
              "D": [_param(helper, name + ".D", [heads], "float32")]}
    inputs.update(extra)
    y = _out(helper, xbc.dtype, y_shape)
    state_out = _out(helper, "float32", state_shape)
    attrs = dict(attrs, heads=int(heads), head_dim=int(head_dim),
                 groups=int(groups), state=int(state_size))
    helper.append_op(type=op, inputs=inputs,
                     outputs={"Y": [y], "StateOut": [state_out]}, attrs=attrs)
    return y, state_out


def mamba2_scan(xbc, dt, name, heads, head_dim, groups, state_size,
                length=None, chunk=128):
    """Mamba-2 over a whole right-padded sequence from a zero state
    (chunked). ``xbc`` (B, T, heads*head_dim + 2*groups*state) after the
    convolution, ``dt`` (B, T, heads) raw; positions >= ``length`` leave
    the state alone. -> ``(y (B, T, heads*head_dim), state (B, heads,
    head_dim, state) float32)``. Parameters ``<name>.dt_bias/A_log/D``."""
    b, t = xbc.shape[0], xbc.shape[1]
    extra = {"Len": [length]} if length is not None else {}
    return _ssm("mamba2_scan", xbc, dt, name, heads, head_dim, groups,
                state_size, extra, (b, t, heads * head_dim),
                (b, heads, head_dim, state_size), {"chunk": int(chunk)})


def mamba2_step(xbc, dt, state, name, heads, head_dim, groups, state_size):
    """One position of the same recurrence for every row: ``xbc`` (S,
    conv_dim), ``dt`` (S, heads), ``state`` (S, heads, head_dim, state)
    float32 -> ``(y (S, heads*head_dim), state_out)``."""
    return _ssm("mamba2_step", xbc, dt, name, heads, head_dim, groups,
                state_size, {"State": [state]},
                (xbc.shape[0], heads * head_dim), state.shape, {})


def rotary_embedding(x, theta):
    """Rotary position term over ``x`` (B, T, heads, head_dim): position t
    along axis 1 turns pair ``(x[i], x[i + head_dim/2])`` of every head by
    ``t * theta^(-2i/head_dim)``. No parameter."""
    helper = LayerHelper("rotary_embedding")
    out = _out(helper, x.dtype, x.shape)
    helper.append_op(type="rotary_embedding", inputs={"X": [x]},
                     outputs={"Out": [out]}, attrs={"theta": float(theta)})
    return out


def gqa_attention(q, k, v, heads, kv_heads, pos=None):
    """Softmax attention of ``heads`` query heads over ``kv_heads``
    key/value heads, no position term of its own. Causal over (B, T, .)
    inputs, or with ``pos`` (B, 1) over a slot cache of which row b sees
    positions <= pos[b]. A causal call of 1,024 positions or more runs as
    the Pallas flash kernels on an unsharded TPU program
    (``ops.hybrid_ops.FLASH_MIN_SEQ``), so no (T, T) scores are held for
    the backward pass."""
    helper = LayerHelper("gqa_attention")
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if pos is not None:
        inputs["Pos"] = [pos]
    attrs = {"heads": int(heads), "kv_heads": int(kv_heads)}
    out = _out(helper, q.dtype, q.shape)
    helper.append_op(type="gqa_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def moe_route_topk(x, num_experts, k, name, scale=1.0, norm_eps=None,
                   bias_update_rate=0.0, apply_gradient=True):
    """Sigmoid top-k router over ``num_experts`` in float32: ``x`` (T, H)
    -> ``(index (T, k) int32, weight (T, k) float32)``, the weights
    normalised over the k chosen (``norm_eps``, default 1e-20, added to
    their sum) and multiplied by ``scale``. Parameters ``<name>.w`` (H,
    experts) and ``<name>.bias`` (experts, float32, the score correction
    added for the choice only: a buffer no optimizer trains). With
    ``bias_update_rate`` > 0 every call also moves that buffer by the
    auxiliary-loss-free balancing rule, from the call's own counts: up by
    the rate for an expert under its even share of the assignments, down
    for one over it; the call itself chooses with the buffer as it was.
    ``apply_gradient`` false is for one chip's share of an expert-parallel
    layer, where the gradient through ``weight`` is a partial sum (only the
    held experts' terms): it is still computed down to ``<name>.w``, whose
    learning rate is 0 (the optimizer's state holds it, nothing moves), and
    goes no further into ``x``."""
    helper = LayerHelper("moe_route_topk")
    t = x.shape[0]
    inputs = {"X": [x],
              "Gate": [_param(helper, name + ".w",
                              [x.shape[-1], num_experts], x.dtype,
                              learning_rate=float(bool(apply_gradient)))],
              "Bias": [_param(helper, name + ".bias", [num_experts],
                              "float32", trainable=False)]}
    idx = _out(helper, "int32", (t, k))
    wt = _out(helper, "float32", (t, k))
    attrs = {"k": int(k), "scale": float(scale)}
    if norm_eps is not None:
        attrs["norm_eps"] = float(norm_eps)
    outputs = {"Index": [idx], "Weight": [wt]}
    if not apply_gradient:
        attrs["detach_input"] = True
    if bias_update_rate:
        attrs["bias_update_rate"] = float(bias_update_rate)
        outputs["BiasOut"] = inputs["Bias"]
    helper.append_op(type="moe_route_topk", inputs=inputs, outputs=outputs,
                     attrs=attrs)
    return idx, wt
