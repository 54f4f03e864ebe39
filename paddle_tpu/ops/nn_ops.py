"""NN op lowerings: activations, softmax, conv, pool, norms, dropout,
embedding, interpolation.

Replaces activation_op.*, softmax_op, conv_op/conv_cudnn_op, pool_op,
batch_norm_op, layer_norm_op, group_norm_op, instance_norm_op, dropout_op,
lookup_table_op, interpolate_op (ref: paddle/fluid/operators/...). Convs and
matmuls lower to lax.conv_general_dilated / dot_general so XLA tiles them on
the MXU; norms/activations are elementwise chains XLA fuses around them.
"""
import math

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single


# ---------------------------------------------------------------------------
# activations (ref: paddle/fluid/operators/activation_op.cc)
# ---------------------------------------------------------------------------
def _act(fn):
    def lower(ctx, ins, attrs):
        return single(fn(ins["X"][0], attrs))

    return lower


register_op("relu")(_act(lambda x, a: jax.nn.relu(x)))
register_op("sigmoid")(_act(lambda x, a: jax.nn.sigmoid(x)))
register_op("tanh")(_act(lambda x, a: jnp.tanh(x)))
register_op("exp")(_act(lambda x, a: jnp.exp(x)))
register_op("log")(_act(lambda x, a: jnp.log(x)))
register_op("sqrt")(_act(lambda x, a: jnp.sqrt(x)))
register_op("rsqrt")(_act(lambda x, a: lax.rsqrt(x)))
register_op("square")(_act(lambda x, a: x * x))
register_op("reciprocal")(_act(lambda x, a: 1.0 / x))
register_op("floor")(_act(lambda x, a: jnp.floor(x)))
register_op("ceil")(_act(lambda x, a: jnp.ceil(x)))
register_op("round")(_act(lambda x, a: jnp.round(x)))
register_op("sin")(_act(lambda x, a: jnp.sin(x)))
register_op("cos")(_act(lambda x, a: jnp.cos(x)))
register_op("tan")(_act(lambda x, a: jnp.tan(x)))
register_op("asin")(_act(lambda x, a: jnp.arcsin(x)))
register_op("acos")(_act(lambda x, a: jnp.arccos(x)))
register_op("atan")(_act(lambda x, a: jnp.arctan(x)))
register_op("sinh")(_act(lambda x, a: jnp.sinh(x)))
register_op("cosh")(_act(lambda x, a: jnp.cosh(x)))
register_op("erf")(_act(lambda x, a: jax.scipy.special.erf(x)))
register_op("gelu")(
    _act(lambda x, a: jax.nn.gelu(x, approximate=a.get("approximate", False)))
)
register_op("logsigmoid")(_act(lambda x, a: jax.nn.log_sigmoid(x)))
register_op("softplus")(_act(lambda x, a: jax.nn.softplus(x)))
register_op("softsign")(_act(lambda x, a: jax.nn.soft_sign(x)))
register_op("softshrink")(
    _act(
        lambda x, a: jnp.where(
            x > a.get("lambda", 0.5),
            x - a.get("lambda", 0.5),
            jnp.where(x < -a.get("lambda", 0.5), x + a.get("lambda", 0.5), 0.0),
        )
    )
)
register_op("hard_shrink")(
    _act(
        lambda x, a: jnp.where(
            jnp.abs(x) > a.get("threshold", 0.5), x, 0.0
        )
    )
)
register_op("tanh_shrink")(_act(lambda x, a: x - jnp.tanh(x)))
register_op("hard_sigmoid")(
    _act(
        lambda x, a: jnp.clip(
            a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0
        )
    )
)
register_op("hard_swish")(
    _act(
        lambda x, a: x
        * jnp.clip(x + a.get("offset", 3.0), 0.0, a.get("threshold", 6.0))
        / a.get("scale", 6.0)
    )
)
register_op("relu6")(
    _act(lambda x, a: jnp.clip(x, 0.0, a.get("threshold", 6.0)))
)
register_op("brelu")(
    _act(lambda x, a: jnp.clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)))
)
register_op("leaky_relu")(
    _act(lambda x, a: jnp.where(x >= 0, x, a.get("alpha", 0.02) * x))
)
register_op("elu")(
    _act(
        lambda x, a: jnp.where(
            x >= 0, x, a.get("alpha", 1.0) * (jnp.exp(jnp.minimum(x, 0.0)) - 1)
        )
    )
)
register_op("selu")(
    _act(
        lambda x, a: a.get("scale", 1.0507009873554805)
        * jnp.where(
            x >= 0,
            x,
            a.get("alpha", 1.6732632423543772)
            * (jnp.exp(jnp.minimum(x, 0.0)) - 1),
        )
    )
)
register_op("swish")(
    _act(lambda x, a: x * jax.nn.sigmoid(a.get("beta", 1.0) * x))
)
register_op("stanh")(
    _act(
        lambda x, a: a.get("scale_b", 1.7159)
        * jnp.tanh(a.get("scale_a", 0.67) * x)
    )
)
register_op("soft_relu")(
    _act(
        lambda x, a: jnp.log(
            1 + jnp.exp(jnp.clip(x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))
        )
    )
)
register_op("thresholded_relu")(
    _act(lambda x, a: jnp.where(x > a.get("threshold", 1.0), x, 0.0))
)
register_op("maxout")(
    _act(
        lambda x, a: jnp.max(
            x.reshape(
                (x.shape[0], a["groups"], x.shape[1] // a["groups"])
                + x.shape[2:]
            ),
            axis=1,
        )
    )
)


@register_op("prelu")
def _prelu(ctx, ins, attrs):
    x, alpha = ins["X"][0], ins["Alpha"][0]
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    elif mode == "all":
        alpha = alpha.reshape(())
    return single(jnp.where(x >= 0, x, alpha * x))


@register_op("softmax")
def _softmax(ctx, ins, attrs):
    return single(jax.nn.softmax(ins["X"][0], axis=attrs.get("axis", -1)))


@register_op("log_softmax")
def _log_softmax(ctx, ins, attrs):
    return single(jax.nn.log_softmax(ins["X"][0], axis=attrs.get("axis", -1)))


# ---------------------------------------------------------------------------
# dropout (ref: paddle/fluid/operators/dropout_op.cc)
# ---------------------------------------------------------------------------
def _dropout_keep_mask(ctx, p, shape):
    """Bernoulli keep-mask for dropout; returns ``(mask, keep_prob)``.

    The mask rides XLA's native RngBitGenerator (rbg): threefry mask
    generation cost about a third of a BERT-base train step on the v5e.
    The rbg key derives from the deterministic per-(op, draw) step key,
    so masks are reproducible and identical between the forward pass
    and its vjp replay.

    One uint32 generator word serves two elements: each takes a 16-bit
    field of it and is kept iff the field is below
    ``round(keep_prob * 65536)``, so the realised keep rate is
    ``round(keep_prob * 65536) / 65536``, within 2**-17 of ``keep_prob``.
    The words lie twice along the first axis of even size, shifted by
    0 in the first half and by 16 in the second: a concatenation, a
    shift and a compare that fuse into one pass from the half-size word
    array to the mask.  A shape with no even axis draws ``ceil(n / 2)``
    words flat and cuts the mask to ``n``."""
    kd = jax.random.key_data(ctx.next_rng()).astype(jnp.uint32).reshape(-1)
    if kd.size < 4:
        kd = jnp.concatenate([kd, kd])
    key = jax.random.wrap_key_data(kd[:4], impl="rbg")
    keep_prob = 1.0 - p
    shape = tuple(shape)
    n = math.prod(shape)
    axis = next((i for i, s in enumerate(shape) if s % 2 == 0), None)
    flat = axis is None
    if flat:
        axis, word_shape = 0, (-(-n // 2),)
    else:
        word_shape = shape[:axis] + (shape[axis] // 2,) + shape[axis + 1:]
    words = jax.random.bits(key, word_shape, jnp.uint32)
    twice = jnp.concatenate([words, words], axis=axis)
    row = lax.broadcasted_iota(jnp.int32, twice.shape, axis)
    shift = jnp.where(row < word_shape[axis], jnp.uint32(0), jnp.uint32(16))
    fields = (twice >> shift) & jnp.uint32(0xFFFF)
    keep = fields < jnp.uint32(round(keep_prob * 65536))
    if flat:
        keep = keep[:n].reshape(shape)
    return keep, keep_prob


@register_op("dropout")
def _dropout(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs.get("dropout_prob", 0.5)
    is_test = attrs.get("is_test", False) or ctx.is_test
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if is_test:
        if impl == "downgrade_in_infer":
            out = x * (1.0 - p)
        else:
            out = x
        return {"Out": [out], "Mask": [jnp.ones_like(x)]}
    keep, keep_prob = _dropout_keep_mask(ctx, p, x.shape)
    if impl == "upscale_in_train":
        out = jnp.where(keep, x / max(keep_prob, 1e-8), 0.0)
    else:
        out = jnp.where(keep, x, 0.0)
    return {"Out": [out.astype(x.dtype)], "Mask": [keep.astype(x.dtype)]}


# ---------------------------------------------------------------------------
# embedding (ref: paddle/fluid/operators/lookup_table_op.cc)
# ---------------------------------------------------------------------------
@register_op("lookup_table_v2")
def _lookup_table(ctx, ins, attrs):
    w, ids = ins["W"][0], ins["Ids"][0]
    padding_idx = attrs.get("padding_idx", -1)
    squeeze_last = False
    if ids.ndim >= 2 and ids.shape[-1] == 1 and attrs.get("_squeeze", True):
        ids = ids[..., 0]
    out = jnp.take(w, ids.astype(jnp.int32), axis=0)
    if padding_idx is not None and padding_idx >= 0:
        mask = (ids != padding_idx)[..., None]
        out = out * mask.astype(out.dtype)
    return single(out)


# ---------------------------------------------------------------------------
# conv / pool (ref: conv_op.cc, pool_op.cc — cuDNN path replaced by
# lax.conv_general_dilated which XLA maps onto the MXU)
# ---------------------------------------------------------------------------
def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(v)
    return (v,) * n


@register_op("conv2d")
def _conv2d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    pad_alg = attrs.get("padding_algorithm", "EXPLICIT")
    if pad_alg == "SAME":
        padding = "SAME"
    elif pad_alg == "VALID":
        padding = "VALID"
    else:
        if len(pads) == 4:
            padding = [(pads[0], pads[1]), (pads[2], pads[3])]
        else:
            padding = [(pads[0], pads[0]), (pads[1], pads[1])]
    # no preferred_element_type here: the TPU MXU accumulates bf16 convs
    # in f32 internally already, and jax's conv transpose (grad) rule
    # does not thread the widened output dtype — the f32 cotangent then
    # meets the bf16 lhs and conv_general_dilated rejects the mix (the
    # bf16 ResNet AMP path failed exactly there)
    out = lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=padding,
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    return {"Output": [out]}


@register_op("depthwise_conv2d")
def _depthwise_conv2d(ctx, ins, attrs):
    return _conv2d(ctx, ins, attrs)


@register_op("conv3d")
def _conv3d(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    groups = attrs.get("groups", 1) or 1
    padding = [(p, p) for p in pads]
    out = lax.conv_general_dilated(
        x, w, strides, padding, rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
    )
    return {"Output": [out]}


@register_op("conv2d_transpose")
def _conv2d_transpose(ctx, ins, attrs):
    x, w = ins["Input"][0], ins["Filter"][0]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1) or 1
    if (attrs.get("groups", 1) or 1) != 1:
        raise NotImplementedError(
            "conv2d_transpose groups>1: lax.conv_transpose has no grouped "
            "mode — split channels and concat results, or use groups=1"
        )
    # gradient of conv2d == transposed conv (ref conv2d_transpose_op.cc).
    # Paddle filter layout is (C_in, C_out, kh, kw); with
    # transpose_kernel=True the spec names the FORWARD-conv roles, so the
    # C_in axis sits in the 'O' slot (verified vs torch conv_transpose2d).
    # output_padding (from the layer's output_size) extends the bottom/right
    # edge by shrinking the high-side implicit crop, like the reference.
    opad = _pair(attrs.get("output_padding", [0, 0]))
    out = lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=[(p, p - o) for p, o in zip(pads, opad)],
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    )
    return {"Output": [out]}


@register_op("conv3d_transpose")
def _conv3d_transpose(ctx, ins, attrs):
    """3-D transposed conv (ref conv3d_transpose_op.cc) — the gradient of
    conv3d, via lax.conv_transpose over NCDHW."""
    x, w = ins["Input"][0], ins["Filter"][0]
    if (attrs.get("groups", 1) or 1) != 1:
        raise NotImplementedError(
            "conv3d_transpose groups>1: lax.conv_transpose has no grouped "
            "mode — split channels and concat results, or use groups=1"
        )
    strides = _pair(attrs.get("strides", [1, 1, 1]), 3)
    pads = _pair(attrs.get("paddings", [0, 0, 0]), 3)
    dilations = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    opad = _pair(attrs.get("output_padding", [0, 0, 0]), 3)
    out = lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=[(p, p - o) for p, o in zip(pads, opad)],
        rhs_dilation=dilations,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        transpose_kernel=True,
    )
    return {"Output": [out]}


def _pool(x, ksize, strides, pads, ptype, ceil_mode, exclusive, global_pool,
          adaptive=False):
    if global_pool:
        ksize = x.shape[2:]
        strides = ksize
        pads = (0,) * len(ksize)
    if adaptive:
        # adaptive: output size = ksize; use reduce_window with computed strides
        out_hw = ksize
        in_hw = x.shape[2:]
        strides = tuple(i // o for i, o in zip(in_hw, out_hw))
        ksize = tuple(i - (o - 1) * s for i, o, s in zip(in_hw, out_hw, strides))
        pads = (0,) * len(out_hw)
    window = (1, 1) + tuple(ksize)
    strides_full = (1, 1) + tuple(strides)
    pad_full = ((0, 0), (0, 0)) + tuple((p, p) for p in pads)
    if ceil_mode:
        # add extra right/bottom padding so ceil division is covered
        extra = []
        for i, (k, s, p) in enumerate(zip(ksize, strides, pads)):
            dim = x.shape[2 + i]
            out_ceil = -(-(dim + 2 * p - k) // s) + 1
            needed = (out_ceil - 1) * s + k - dim - 2 * p
            extra.append((p, p + max(0, needed)))
        pad_full = ((0, 0), (0, 0)) + tuple(extra)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        out = lax.reduce_window(
            x, init, lax.max, window, strides_full, pad_full
        )
    else:
        summed = lax.reduce_window(
            x, 0.0, lax.add, window, strides_full, pad_full
        )
        if exclusive:
            ones = jnp.ones_like(x)
            counts = lax.reduce_window(
                ones, 0.0, lax.add, window, strides_full, pad_full
            )
            out = summed / counts
        else:
            out = summed / float(np.prod(ksize))
    return out


@register_op("pool2d")
def _pool2d(ctx, ins, attrs):
    x = ins["X"][0]
    out = _pool(
        x,
        _pair(attrs.get("ksize", [2, 2])),
        _pair(attrs.get("strides", [1, 1])),
        _pair(attrs.get("paddings", [0, 0])),
        attrs.get("pooling_type", "max"),
        attrs.get("ceil_mode", False),
        attrs.get("exclusive", True),
        attrs.get("global_pooling", False),
        attrs.get("adaptive", False),
    )
    return single(out)


@register_op("pool3d")
def _pool3d(ctx, ins, attrs):
    x = ins["X"][0]
    out = _pool(
        x,
        _pair(attrs.get("ksize", [2, 2, 2]), 3),
        _pair(attrs.get("strides", [1, 1, 1]), 3),
        _pair(attrs.get("paddings", [0, 0, 0]), 3),
        attrs.get("pooling_type", "max"),
        attrs.get("ceil_mode", False),
        attrs.get("exclusive", True),
        attrs.get("global_pooling", False),
        attrs.get("adaptive", False),
    )
    return single(out)


# ---------------------------------------------------------------------------
# normalization (ref: batch_norm_op.cc, layer_norm_op.cc, group_norm_op.cc,
# instance_norm_op.cc). batch_norm keeps running stats as persistable state
# updated functionally in the one jitted step.
# ---------------------------------------------------------------------------
@register_op("batch_norm")
def _batch_norm(ctx, ins, attrs):
    x = ins["X"][0]
    scale, bias = ins["Scale"][0], ins["Bias"][0]
    mean, var = ins["Mean"][0], ins["Variance"][0]
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False) or ctx.is_test
    use_global = attrs.get("use_global_stats", False) or is_test
    layout = attrs.get("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    axes = tuple(i for i in range(x.ndim) if i != ch_axis)
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]

    if use_global:
        use_mean, use_var = mean, var
        new_mean, new_var = mean, var
        saved_mean = jnp.zeros_like(mean)
        saved_var = jnp.ones_like(var)
    else:
        xf = x.astype(jnp.float32)
        bm = jnp.mean(xf, axis=axes)
        bv = jnp.var(xf, axis=axes)
        use_mean, use_var = bm, bv
        new_mean = momentum * mean + (1 - momentum) * bm
        new_var = momentum * var + (1 - momentum) * bv
        saved_mean = bm
        saved_var = 1.0 / jnp.sqrt(bv + eps)
    inv = lax.rsqrt(use_var.astype(jnp.float32) + eps)
    out = (x.astype(jnp.float32) - use_mean.reshape(bshape)) * (
        inv * scale.astype(jnp.float32)
    ).reshape(bshape) + bias.astype(jnp.float32).reshape(bshape)
    out = out.astype(x.dtype)
    return {
        "Y": [out],
        "MeanOut": [new_mean.astype(mean.dtype)],
        "VarianceOut": [new_var.astype(var.dtype)],
        "SavedMean": [saved_mean],
        "SavedVariance": [saved_var],
    }


@register_op("layer_norm")
def _layer_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    norm_shape = x.shape[begin:]
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(norm_shape).astype(jnp.float32)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(norm_shape).astype(jnp.float32)
    return {
        "Y": [out.astype(x.dtype)],
        "Mean": [jnp.squeeze(mean)],
        "Variance": [jnp.squeeze(var)],
    }


@register_op("group_norm")
def _group_norm(ctx, ins, attrs):
    x = ins["X"][0]
    g = attrs["groups"]
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    out = ((xg - mean) * lax.rsqrt(var + eps)).reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(bshape)
    return {"Y": [out], "Mean": [jnp.squeeze(mean)], "Variance": [jnp.squeeze(var)]}


@register_op("instance_norm")
def _instance_norm(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 1e-5)
    axes = tuple(range(2, x.ndim))
    mean = jnp.mean(x, axis=axes, keepdims=True)
    var = jnp.var(x, axis=axes, keepdims=True)
    out = (x - mean) * lax.rsqrt(var + eps)
    bshape = (1, x.shape[1]) + (1,) * (x.ndim - 2)
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(bshape)
    return {
        "Y": [out],
        "SavedMean": [jnp.squeeze(mean)],
        "SavedVariance": [jnp.squeeze(var)],
    }


@register_op("data_norm")
def _data_norm(ctx, ins, attrs):
    x = ins["X"][0]
    size = ins["BatchSize"][0]
    bsum = ins["BatchSum"][0]
    bsquare = ins["BatchSquareSum"][0]
    mean = bsum / size
    scale = lax.rsqrt(bsquare / size - mean * mean + 1e-4)
    out = (x - mean) * scale
    return {"Y": [out], "Means": [mean], "Scales": [scale]}


@register_op("norm")
def _norm(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": [x / norm], "Norm": [norm]}


def _wn_norm(v, dim):
    """||v|| over all axes except `dim` (dim=-1 → over everything)."""
    if dim is None or dim < 0:
        return jnp.sqrt(jnp.sum(v * v)).reshape((1,))
    axes = tuple(a for a in range(v.ndim) if a != dim)
    return jnp.sqrt(jnp.sum(v * v, axis=axes))


@register_op("norm_except_dim")
def _norm_except_dim(ctx, ins, attrs):
    """g0 = ||v|| keeping axis `dim` (ref layer_helper_base.py
    __norm_except_dim); used by startup to seed weight-norm g so the
    initial effective weight equals the initialised v."""
    return single(_wn_norm(ins["V"][0], attrs.get("dim", -1)))


@register_op("weight_norm_reparam")
def _weight_norm_reparam(ctx, ins, attrs):
    """w = g * v / ||v|| (ref layer_helper_base.py:88 create_parameter
    weight-norm path). Differentiable in g and v via the jax vjp."""
    v = ins["V"][0]
    g = ins["G"][0]
    dim = attrs.get("dim", -1)
    norm = _wn_norm(v, dim)
    if dim is None or dim < 0:
        return single(v * (g[0] / norm[0]))
    bshape = [1] * v.ndim
    bshape[dim] = v.shape[dim]
    return single(v * (g / norm).reshape(bshape))


@register_op("lrn")
def _lrn(ctx, ins, attrs):
    x = ins["X"][0]
    n = attrs.get("n", 5)
    k = attrs.get("k", 2.0)
    alpha = attrs.get("alpha", 1e-4)
    beta = attrs.get("beta", 0.75)
    sq = x * x
    # sum over channel window: pad channels and reduce
    half = n // 2
    sq_pad = jnp.pad(sq, ((0, 0), (half, half), (0, 0), (0, 0)))
    win = sum(
        sq_pad[:, i : i + x.shape[1]] for i in range(n)
    )
    mid = jnp.power(k + alpha * win, beta)
    return {"Out": [x / mid], "MidOut": [mid]}


@register_op("spectral_norm")
def _spectral_norm(ctx, ins, attrs):
    w = ins["Weight"][0]
    u = ins["U"][0]
    v = ins["V"][0]
    dim = attrs.get("dim", 0)
    power_iters = attrs.get("power_iters", 1)
    eps = attrs.get("eps", 1e-12)
    w2 = jnp.moveaxis(w, dim, 0).reshape((w.shape[dim], -1))
    for _ in range(power_iters):
        v = w2.T @ u
        v = v / jnp.maximum(jnp.linalg.norm(v), eps)
        u = w2 @ v
        u = u / jnp.maximum(jnp.linalg.norm(u), eps)
    sigma = u @ (w2 @ v)
    return single(w / sigma)


# ---------------------------------------------------------------------------
# interpolation / image (ref: interpolate_op.cc)
# ---------------------------------------------------------------------------
def _interp(ctx, ins, attrs, method):
    x = ins["X"][0]
    out_h = attrs.get("out_h", -1)
    out_w = attrs.get("out_w", -1)
    scale = attrs.get("scale", 0.0)
    if ins.get("OutSize"):
        sz = ins["OutSize"][0]
        out_h, out_w = int(sz[0]), int(sz[1])
    elif scale and scale > 0:
        out_h = int(x.shape[2] * scale)
        out_w = int(x.shape[3] * scale)
    out = jax.image.resize(
        x, (x.shape[0], x.shape[1], out_h, out_w), method=method
    )
    return single(out.astype(x.dtype))


@register_op("bilinear_interp")
def _bilinear_interp(ctx, ins, attrs):
    return _interp(ctx, ins, attrs, "bilinear")


@register_op("nearest_interp")
def _nearest_interp(ctx, ins, attrs):
    return _interp(ctx, ins, attrs, "nearest")


@register_op("trilinear_interp")
def _trilinear_interp(ctx, ins, attrs):
    x = ins["X"][0]
    out_d = attrs.get("out_d", -1)
    out_h = attrs.get("out_h", -1)
    out_w = attrs.get("out_w", -1)
    out = jax.image.resize(
        x, (x.shape[0], x.shape[1], out_d, out_h, out_w), method="trilinear"
    )
    return single(out)


@register_op("grid_sampler")
def _grid_sampler(ctx, ins, attrs):
    x, grid = ins["X"][0], ins["Grid"][0]
    n, c, h, w = x.shape
    gx = (grid[..., 0] + 1) * (w - 1) / 2
    gy = (grid[..., 1] + 1) * (h - 1) / 2
    x0 = jnp.floor(gx).astype(jnp.int32)
    y0 = jnp.floor(gy).astype(jnp.int32)
    x1, y1 = x0 + 1, y0 + 1
    wx = gx - x0
    wy = gy - y0

    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1)
        yi = jnp.clip(yi, 0, h - 1)
        bidx = jnp.arange(n)[:, None, None]
        return x[bidx, :, yi, xi]  # (n, oh, ow, c)

    v00 = sample(x0, y0)
    v01 = sample(x1, y0)
    v10 = sample(x0, y1)
    v11 = sample(x1, y1)
    wx_ = wx[..., None]
    wy_ = wy[..., None]
    out = (
        v00 * (1 - wx_) * (1 - wy_)
        + v01 * wx_ * (1 - wy_)
        + v10 * (1 - wx_) * wy_
        + v11 * wx_ * wy_
    )
    return {"Output": [jnp.moveaxis(out, -1, 1)]}


@register_op("affine_grid")
def _affine_grid(ctx, ins, attrs):
    theta = ins["Theta"][0]
    if ins.get("OutputShape"):
        oshape = [int(v) for v in np.asarray(ins["OutputShape"][0])]
    else:
        oshape = attrs["output_shape"]
    n, _, h, w = oshape
    ys = jnp.linspace(-1, 1, h)
    xs = jnp.linspace(-1, 1, w)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    ones = jnp.ones_like(gx)
    base = jnp.stack([gx, gy, ones], axis=-1)  # (h, w, 3)
    out = jnp.einsum("hwk,njk->nhwj", base, theta)
    return {"Output": [out]}


@register_op("pixel_shuffle")
def _pixel_shuffle(ctx, ins, attrs):
    x = ins["X"][0]
    r = attrs["upscale_factor"]
    n, c, h, w = x.shape
    x = x.reshape(n, c // (r * r), r, r, h, w)
    x = jnp.transpose(x, (0, 1, 4, 2, 5, 3))
    return single(x.reshape(n, c // (r * r), h * r, w * r))


@register_op("temporal_shift")
def _temporal_shift(ctx, ins, attrs):
    x = ins["X"][0]
    seg = attrs["seg_num"]
    ratio = attrs.get("shift_ratio", 0.25)
    nt, c, h, w = x.shape
    x = x.reshape(nt // seg, seg, c, h, w)
    c1 = int(c * ratio)
    fwd = jnp.pad(x[:, 1:, :c1], ((0, 0), (0, 1), (0, 0), (0, 0), (0, 0)))
    back = jnp.pad(x[:, :-1, c1 : 2 * c1], ((0, 0), (1, 0), (0, 0), (0, 0), (0, 0)))
    rest = x[:, :, 2 * c1 :]
    out = jnp.concatenate([fwd, back, rest], axis=2)
    return single(out.reshape(nt, c, h, w))


@register_op("add_position_encoding")
def _add_position_encoding(ctx, ins, attrs):
    x = ins["X"][0]
    alpha = attrs.get("alpha", 1.0)
    beta = attrs.get("beta", 1.0)
    b, t, d = x.shape
    pos = jnp.arange(t)[:, None]
    i = jnp.arange(d // 2)[None, :]
    angle = pos / jnp.power(10000.0, 2 * i / d)
    pe = jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)
    return single(alpha * x + beta * pe[None, :, :])
