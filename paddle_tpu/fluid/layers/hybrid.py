"""Layers of hybrid state-space / attention / sparse-expert decoders: RMS
norm (plain, grouped, gated), squared ReLU, a causal depthwise convolution
with carried window, the Mamba-2 recurrence (chunked scan and one step),
the gated delta rule with a decay a channel (the same two forms), a rotary position term (whole or part of a head, half-split or interleaved
pairs, plain or YaRN, from the row index or from a slot's position),
grouped-query attention (causal, windowed, over a slot cache or ring), a
learned selection of keys and latent attention over it (a prompt's expanded
path, a step's absorbed path over gathered cache rows), top-k routing
(sigmoid or softmax scores) and a product that keeps its float32
accumulator. The lowerings are
``paddle_tpu/ops/hybrid_ops.py``; the held-experts layer built on the router is
``paddle_tpu.parallel.moe.held_experts_ffn``.
"""
from ..layer_helper import LayerHelper
from ..param_attr import ParamAttr

__all__ = ["rms_norm", "relu_squared", "dense_acc32", "causal_conv1d",
           "mamba2_scan", "mamba2_step", "kda_scan", "kda_step",
           "rotary_embedding", "gqa_attention",
           "kv_ring_gather", "dsa_select", "mla_attention", "moe_route_topk"]


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


def _kda(op, q, k, v, g, beta, name, heads, head_dim, extra, state_shape,
         attrs):
    helper = LayerHelper(op)
    inputs = {"Q": [q], "K": [k], "V": [v], "G": [g], "Beta": [beta],
              "ALog": [_param(helper, name + ".A_log", [heads], "float32")],
              "DtBias": [_param(helper, name + ".dt_bias",
                                [heads * head_dim], "float32")]}
    inputs.update(extra)
    out = _out(helper, q.dtype, q.shape)
    state_out = _out(helper, "float32", state_shape)
    attrs = dict(attrs, heads=int(heads), head_dim=int(head_dim))
    helper.append_op(type=op, inputs=inputs,
                     outputs={"O": [out], "StateOut": [state_out]},
                     attrs=attrs)
    return out, state_out


def kda_scan(q, k, v, g, beta, name, heads, head_dim, length=None,
             state=None, chunk=None, beta_scale=1.0):
    """The gated delta rule with a decay a channel (Kimi Delta Attention)
    over a whole right-padded sequence, chunked: ``S_t = (I - beta_t k_t
    k_t^T) diag(exp(g_t)) S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T
    q_t``. ``q``, ``k``, ``v`` (B, T, heads * head_dim) after their
    convolutions (the op L2-norms q and k a head and scales q), ``g`` (B,
    T, heads * head_dim) and ``beta`` (B, T, heads) raw: ``g_t =
    -exp(A_log) softplus(g + dt_bias)``, ``beta_t = beta_scale
    sigmoid(beta)``. ``state`` (B, heads, head_dim, head_dim) float32 is
    the state before the first position (zeros without it); positions >=
    ``length`` (B, 1) leave it alone. -> ``(o (B, T, heads * head_dim),
    state (B, heads, head_dim, head_dim) float32)``. Parameters
    ``<name>.A_log`` (heads) and ``<name>.dt_bias`` (heads * head_dim),
    float32. On the TPU, in an unsharded program, with ``head_dim`` a
    multiple of 128, T whole chunks of 64 positions and ``chunk`` left
    alone (a run of 4,096, a bucket of 8,192 or 16,384), the lowering is
    one Pallas kernel that keeps a head's chunk and its float32 state in
    fast memory (``ops/pallas_kda.py``); every other call (a CPU, a mesh, a
    narrower head, a ragged T, another ``chunk``) takes the same float32
    arithmetic through XLA, which is also the kernel's gradient. The op
    chooses from what it sees; nothing here selects."""
    extra = {}
    if length is not None:
        extra["Len"] = [length]
    if state is not None:
        extra["State"] = [state]
    attrs = {"beta_scale": float(beta_scale)}
    if chunk:
        attrs["chunk"] = int(chunk)
    return _kda("kda_scan", q, k, v, g, beta, name, heads, head_dim, extra,
                (q.shape[0], heads, head_dim, head_dim), attrs)


def kda_step(q, k, v, g, beta, state, name, heads, head_dim, beta_scale=1.0):
    """One position of the same recurrence for every row: ``q``, ``k``,
    ``v``, ``g`` (S, heads * head_dim), ``beta`` (S, heads), ``state`` (S,
    heads, head_dim, head_dim) float32 -> ``(o (S, heads * head_dim),
    state_out)``."""
    return _kda("kda_step", q, k, v, g, beta, name, heads, head_dim,
                {"State": [state]}, state.shape,
                {"beta_scale": float(beta_scale)})


def rotary_embedding(x, theta, pos=None, rotary_dim=None, yarn=None,
                     interleaved=False):
    """Rotary position term over ``x`` (B, T, heads, head_dim): the row at
    t along axis 1 stands at position t, or with ``pos`` (B, 1) at
    ``pos[b] + t`` (a decode step's one row at its slot's position). Pair
    ``(x[i], x[i + rotary_dim/2])`` of every head is turned by ``position
    * theta^(-2i/rotary_dim)``, with ``interleaved`` pair ``(x[2i], x[2i +
    1])``, which comes out de-interleaved (at i and i + rotary_dim/2);
    dimensions past ``rotary_dim`` (default: the whole head) pass
    unturned. ``yarn`` = ``(factor, original
    positions, beta_fast, beta_slow, attention_factor)`` blends the rates
    and multiplies cos and sin by the last
    (``ops.hybrid_ops.rotary_inv_freq``). No parameter."""
    helper = LayerHelper("rotary_embedding")
    out = _out(helper, x.dtype, x.shape)
    inputs = {"X": [x]}
    if pos is not None:
        inputs["Pos"] = [pos]
    attrs = {"theta": float(theta)}
    if rotary_dim is not None:
        attrs["rotary_dim"] = int(rotary_dim)
    if yarn is not None:
        attrs["yarn"] = [float(v) for v in yarn]
    if interleaved:
        attrs["interleaved"] = True
    helper.append_op(type="rotary_embedding", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def gqa_attention(q, k, v, heads, kv_heads, pos=None, window=None,
                  offset=None):
    """Softmax attention of ``heads`` query heads over ``kv_heads``
    key/value heads, no position term of its own. Causal over (B, T, .)
    inputs, with ``window`` each query sees the last ``window`` positions
    only (a block of ``window`` queries against its own keys and the block
    before: neither (T, T) scores nor a full causal call's cost; on an
    unsharded TPU program, with a head size and a window that are
    multiples of 128, one Pallas kernel whose scores stay on the chip,
    everywhere else the same blocks through XLA: the op chooses,
    ``ops.hybrid_ops._gqa_attention``); or with ``pos`` (B, 1) over a slot
    cache of which row b sees columns <= pos[b], be it a sequence's rows or
    a window layer's ring; or with ``offset`` (1, 1) a chunk of queries that
    stand ``offset`` rows into the keys (the rows of the sequence so far):
    query i sees the columns <= offset + i, and a chunk of 1,024 positions
    or more takes the flash forward kernel with that offset.
    A plain causal call of 1,024 positions or more runs as the Pallas flash
    kernels on an unsharded TPU program (``ops.hybrid_ops.FLASH_MIN_SEQ``),
    so no (T, T) scores are held for the backward pass."""
    helper = LayerHelper("gqa_attention")
    inputs = {"Q": [q], "K": [k], "V": [v]}
    if pos is not None:
        inputs["Pos"] = [pos]
    if offset is not None:
        inputs["Offset"] = [offset]
    attrs = {"heads": int(heads), "kv_heads": int(kv_heads)}
    if window:
        attrs["window"] = int(window)
    out = _out(helper, q.dtype, q.shape)
    helper.append_op(type="gqa_attention", inputs=inputs,
                     outputs={"Out": [out]}, attrs=attrs)
    return out


def kv_ring_gather(x, length, window):
    """A window layer's ring as a prefill hands it over: ``x`` (B, T, C)
    one row a position, ``length`` (B, 1) -> (B, window, C), column j the
    row of the last real position p with ``p mod window == j`` (zeros
    where the sequence has none yet)."""
    helper = LayerHelper("kv_ring_gather")
    out = _out(helper, x.dtype, (x.shape[0], int(window), x.shape[-1]))
    helper.append_op(type="kv_ring_gather",
                     inputs={"X": [x], "Len": [length]},
                     outputs={"Out": [out]}, attrs={"window": int(window)})
    return out


def dsa_select(q, k, w, heads, topk, pos=None):
    """The keys a sparse-attention layer's indexer keeps for each query:
    ``q`` (B, Tq, heads * d) and ``k`` (B, Tk, d) the indexer's queries and
    keys, turned by their positions, ``w`` (B, Tq, heads) float32 the
    heads' weights; key s scores ``sum_j w[t, j] relu(q[t, j] . k[s])`` and
    the ``topk`` visible keys of largest score are kept (all, while there
    are no more), exactly. Over a prompt (causal) -> (B, T, T) int8, 1
    where query t keeps key s; with ``pos`` (B, 1) over the slots' indexer
    rows, row b seeing columns <= pos[b] -> (B, min(topk, Tk)) int32 kept
    columns, -1 for none. What :func:`mla_attention` takes as
    ``selected``. No parameter."""
    helper = LayerHelper("dsa_select")
    inputs = {"Q": [q], "K": [k], "W": [w]}
    if pos is None:
        out = _out(helper, "int8", (q.shape[0], q.shape[1], k.shape[1]))
    else:
        inputs["Pos"] = [pos]
        out = _out(helper, "int32", (q.shape[0], min(int(topk), k.shape[1])))
    helper.append_op(type="dsa_select", inputs=inputs,
                     outputs={"Selected": [out]},
                     attrs={"heads": int(heads), "topk": int(topk)})
    return out


def mla_attention(q, latent, selected, name, heads, rank, nope_dim, rope_dim,
                  v_dim, pos=None, offset=None):
    """Multi-head latent attention over the keys ``selected``
    (:func:`dsa_select`), or with ``selected`` None over every earlier
    position: ``q`` (B, Tq, heads * (nope_dim + rope_dim)) per head
    ``[q_nope | q_rope]``, ``latent`` (B, Tk, >= rank + rope_dim) a
    position's ``[ckv | k_rope]`` of latent rank ``rank``, zeros after it up
    to the cache's width -> (B, Tq, heads * v_dim). A prompt takes
    the expanded path (keys and values of every head made from the
    latents); with ``offset`` (1, 1) and no selection the queries are a
    chunk that stands ``offset`` rows into ``latent``, the sequence's rows
    so far; with ``pos`` (B, 1) a step takes the absorbed path over the
    slots' cache ``latent``: its kept rows gathered, or without a selection
    its rows ``<= pos`` where they lie. Parameters ``<name>.uk.w`` (rank,
    heads * nope_dim) and ``<name>.uv.w`` (rank, heads * v_dim)."""
    helper = LayerHelper("mla_attention")
    inputs = {"Q": [q], "Latent": [latent],
              "Wuk": [_param(helper, name + ".uk.w",
                             [rank, heads * nope_dim], q.dtype)],
              "Wuv": [_param(helper, name + ".uv.w",
                             [rank, heads * v_dim], q.dtype)]}
    if selected is not None:
        inputs["Selected"] = [selected]
    if pos is not None:
        inputs["Pos"] = [pos]
    if offset is not None:
        inputs["Offset"] = [offset]
    out = _out(helper, q.dtype, (q.shape[0], q.shape[1], heads * v_dim))
    helper.append_op(type="mla_attention", inputs=inputs,
                     outputs={"Out": [out]},
                     attrs={"heads": int(heads), "nope_dim": int(nope_dim),
                            "rope_dim": int(rope_dim), "v_dim": int(v_dim)})
    return out


def moe_route_topk(x, num_experts, k, name, scale=1.0, norm_eps=None,
                   bias_update_rate=0.0, apply_gradient=True,
                   score_func="sigmoid", bias=True):
    """Top-k router over ``num_experts`` in float32, scores a sigmoid of
    the logits or (``score_func="softmax"``) their softmax: ``x`` (T, H)
    -> ``(index (T, k) int32, weight (T, k) float32)``, the weights
    normalised over the k chosen (``norm_eps``, default 1e-20, added to
    their sum) and multiplied by ``scale``. Parameters ``<name>.w`` (H,
    experts) and, unless ``bias`` is false, ``<name>.bias`` (experts,
    float32, the score correction
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
                              learning_rate=float(bool(apply_gradient)))]}
    if bias:
        inputs["Bias"] = [_param(helper, name + ".bias", [num_experts],
                                 "float32", trainable=False)]
    elif bias_update_rate:
        raise ValueError("bias_update_rate moves the score correction; "
                         "this router has none (bias=False)")
    idx = _out(helper, "int32", (t, k))
    wt = _out(helper, "float32", (t, k))
    attrs = {"k": int(k), "scale": float(scale)}
    if score_func != "sigmoid":
        attrs["score_func"] = str(score_func)
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
