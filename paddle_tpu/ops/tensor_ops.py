"""Tensor manipulation op lowerings.

Replaces cast_op, concat_op, reshape_op, transpose_op, slice_op, split_op,
gather/scatter ops, fill_constant, assign, one_hot, expand, stack, etc.
(ref: paddle/fluid/operators/{cast,concat,reshape,transpose,slice,gather,
scatter,fill_constant,assign,one_hot,expand,stack}_op.*).
"""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from ..fluid import core
from .registry import register_op, single


@register_op("cast")
def _cast(ctx, ins, attrs):
    x = ins["X"][0]
    dtype = core.np_dtype(core.convert_dtype(attrs["out_dtype"]))
    return single(x.astype(dtype))


@register_op("concat")
def _concat(ctx, ins, attrs):
    axis = ins["AxisTensor"][0] if ins.get("AxisTensor") else attrs.get("axis", 0)
    return single(jnp.concatenate(ins["X"], axis=int(axis)))


@register_op("split")
def _split(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    num = attrs.get("num", 0)
    sections = attrs.get("sections", [])
    if sections:
        idx = np.cumsum(sections[:-1])
        outs = jnp.split(x, idx, axis=axis)
    else:
        outs = jnp.split(x, num, axis=axis)
    return {"Out": list(outs)}


@register_op("reshape2")
def _reshape(ctx, ins, attrs):
    x = ins["X"][0]
    if ins.get("ShapeTensor"):
        shape = [int(s) for s in ins["ShapeTensor"]]
    else:
        shape = list(attrs["shape"])
    # paddle: 0 means copy dim from input
    shape = [x.shape[i] if s == 0 else s for i, s in enumerate(shape)]
    return {
        "Out": [x.reshape(shape)],
        "XShape": [jnp.zeros((0,) + x.shape, x.dtype)],
    }


@register_op("transpose2")
def _transpose(ctx, ins, attrs):
    x = ins["X"][0]
    return {
        "Out": [jnp.transpose(x, attrs["axis"])],
        "XShape": [jnp.zeros((0,) + x.shape, x.dtype)],
    }


@register_op("squeeze2")
def _squeeze(ctx, ins, attrs):
    x = ins["X"][0]
    axes = attrs.get("axes", [])
    if axes:
        axes = tuple(a % x.ndim for a in axes if x.shape[a % x.ndim] == 1)
        out = jnp.squeeze(x, axis=axes) if axes else x
    else:
        out = jnp.squeeze(x)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("unsqueeze2")
def _unsqueeze(ctx, ins, attrs):
    x = ins["X"][0]
    out = x
    for a in sorted(attrs["axes"]):
        out = jnp.expand_dims(out, a)
    return {"Out": [out], "XShape": [jnp.zeros((0,) + x.shape, x.dtype)]}


@register_op("flatten2")
def _flatten(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 1)
    lead = 1
    for s in x.shape[:axis]:
        lead *= s
    return {
        "Out": [x.reshape((lead, -1))],
        "XShape": [jnp.zeros((0,) + x.shape, x.dtype)],
    }


@register_op("slice")
def _slice(ctx, ins, attrs):
    x = ins["Input"][0]
    axes = attrs["axes"]
    starts = attrs["starts"]
    ends = attrs["ends"]
    idx = [slice(None)] * x.ndim
    for ax, st, en in zip(axes, starts, ends):
        dim = x.shape[ax]
        st = max(st + dim, 0) if st < 0 else min(st, dim)
        en = max(en + dim, 0) if en < 0 else min(en, dim)
        idx[ax] = slice(st, en)
    return single(x[tuple(idx)])


@register_op("strided_slice")
def _strided_slice(ctx, ins, attrs):
    x = ins["Input"][0]
    idx = [slice(None)] * x.ndim
    for ax, st, en, sd in zip(
        attrs["axes"], attrs["starts"], attrs["ends"], attrs["strides"]
    ):
        idx[ax] = slice(st, en, sd)
    return single(x[tuple(idx)])


@register_op("fill_constant")
def _fill_constant(ctx, ins, attrs):
    shape = attrs.get("shape", [])
    if ins.get("ShapeTensor"):
        shape = [int(v) for v in ins["ShapeTensor"]]
    dtype = core.np_dtype(core.convert_dtype(attrs["dtype"]))
    value = attrs.get("value", 0.0)
    if ins.get("ValueTensor"):
        value = ins["ValueTensor"][0]
    return single(jnp.full(tuple(int(s) for s in shape), value, dtype=dtype))


@register_op("fill_constant_batch_size_like")
def _fill_constant_bsl(ctx, ins, attrs):
    ref = ins["Input"][0]
    shape = list(attrs["shape"])
    in_idx = attrs.get("input_dim_idx", 0)
    out_idx = attrs.get("output_dim_idx", 0)
    shape[out_idx] = ref.shape[in_idx]
    dtype = core.np_dtype(core.convert_dtype(attrs["dtype"]))
    return single(jnp.full(tuple(shape), attrs.get("value", 0.0), dtype=dtype))


@register_op("fill_zeros_like")
def _fill_zeros_like(ctx, ins, attrs):
    return single(jnp.zeros_like(ins["X"][0]))


@register_op("assign")
def _assign(ctx, ins, attrs):
    return single(ins["X"][0])


@register_op("assign_value")
def _assign_value(ctx, ins, attrs):
    dtype = core.np_dtype(core.convert_dtype(attrs["dtype"]))
    values = np.array(attrs["values"], dtype=dtype).reshape(attrs["shape"])
    return single(jnp.asarray(values))


@register_op("shape")
def _shape(ctx, ins, attrs):
    x = ins["Input"][0]
    return single(jnp.array(x.shape, dtype=jnp.int32))


@register_op("size")
def _size(ctx, ins, attrs):
    x = ins["Input"][0]
    return single(jnp.array(x.size, dtype=jnp.int64))


@register_op("gather")
def _gather(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    if idx.ndim == 2 and idx.shape[1] == 1:
        idx = idx[:, 0]
    return single(jnp.take(x, idx, axis=0))


@register_op("gather_nd")
def _gather_nd(ctx, ins, attrs):
    x, idx = ins["X"][0], ins["Index"][0]
    # idx shape (..., k): index into first k dims of x
    k = idx.shape[-1]
    out = x[tuple(jnp.moveaxis(idx, -1, 0))]
    return single(out)


@register_op("scatter")
def _scatter(ctx, ins, attrs):
    x, idx, upd = ins["X"][0], ins["Ids"][0], ins["Updates"][0]
    if idx.ndim == 2 and idx.shape[1] == 1:
        idx = idx[:, 0]
    if attrs.get("overwrite", True):
        return single(x.at[idx].set(upd))
    return single(x.at[idx].set(0).at[idx].add(upd))


@register_op("scatter_nd_add")
def _scatter_nd_add(ctx, ins, attrs):
    x, idx, upd = ins["X"][0], ins["Index"][0], ins["Updates"][0]
    return single(x.at[tuple(jnp.moveaxis(idx, -1, 0))].add(upd))


@register_op("one_hot")
def _one_hot(ctx, ins, attrs):
    x = ins["X"][0]
    depth = attrs["depth"]
    # v1 convention collapses a trailing (n, 1) ids dim; the v2 API
    # (fluid.input.one_hot) appends depth to the shape as-is
    if x.ndim >= 2 and x.shape[-1] == 1 and attrs.get("_squeeze", True):
        x = x[..., 0]
    out = jax.nn.one_hot(x, depth, dtype=jnp.float32)
    return single(out)


@register_op("expand")
def _expand(ctx, ins, attrs):
    x = ins["X"][0]
    times = attrs["expand_times"]
    return single(jnp.tile(x, times))


@register_op("expand_as")
def _expand_as(ctx, ins, attrs):
    x, tgt = ins["X"][0], ins["target_tensor"][0]
    times = [t // s for t, s in zip(tgt.shape, x.shape)]
    return single(jnp.tile(x, times))


@register_op("stack")
def _stack(ctx, ins, attrs):
    return {"Y": [jnp.stack(ins["X"], axis=attrs.get("axis", 0))]}


@register_op("unstack")
def _unstack(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", 0)
    num = x.shape[axis]
    outs = [jnp.squeeze(a, axis) for a in jnp.split(x, num, axis=axis)]
    return {"Y": outs}


@register_op("tile")
def _tile(ctx, ins, attrs):
    return single(jnp.tile(ins["X"][0], attrs["repeat_times"]))


@register_op("pad")
def _pad(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]
    pads = [(p[2 * i], p[2 * i + 1]) for i in range(x.ndim)]
    return single(jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0)))


@register_op("pad2d")
def _pad2d(ctx, ins, attrs):
    x = ins["X"][0]
    p = attrs["paddings"]  # [top, bottom, left, right]
    mode = attrs.get("mode", "constant")
    fmt = attrs.get("data_format", "NCHW")
    if fmt == "NCHW":
        pads = [(0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])]
    else:
        pads = [(0, 0), (p[0], p[1]), (p[2], p[3]), (0, 0)]
    if mode == "constant":
        return single(
            jnp.pad(x, pads, constant_values=attrs.get("pad_value", 0.0))
        )
    jmode = {"reflect": "reflect", "edge": "edge"}[mode]
    return single(jnp.pad(x, pads, mode=jmode))


@register_op("pad_constant_like")
def _pad_constant_like(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    pads = [(0, xs - ys) for xs, ys in zip(x.shape, y.shape)]
    return single(jnp.pad(y, pads, constant_values=attrs.get("pad_value", 0.0)))


@register_op("arg_max")
def _arg_max(ctx, ins, attrs):
    return single(
        jnp.argmax(ins["X"][0], axis=attrs.get("axis", -1)).astype(jnp.int64)
    )


@register_op("arg_min")
def _arg_min(ctx, ins, attrs):
    return single(
        jnp.argmin(ins["X"][0], axis=attrs.get("axis", -1)).astype(jnp.int64)
    )


@register_op("argsort")
def _argsort(ctx, ins, attrs):
    x = ins["X"][0]
    axis = attrs.get("axis", -1)
    desc = attrs.get("descending", False)
    idx = jnp.argsort(-x if desc else x, axis=axis)
    out = jnp.take_along_axis(x, idx, axis=axis)
    return {"Out": [out], "Indices": [idx.astype(jnp.int64)]}


@register_op("top_k")
def _top_k(ctx, ins, attrs):
    x = ins["X"][0]
    k = int(ins["K"][0]) if ins.get("K") else attrs["k"]
    vals, idx = lax.top_k(x, k)
    return {"Out": [vals], "Indices": [idx.astype(jnp.int64)]}


@register_op("where_index")
def _where_index(ctx, ins, attrs):
    # nonzero has data-dependent shape; provide host-side only (documented)
    x = np.asarray(ins["Condition"][0])
    return single(jnp.asarray(np.stack(np.nonzero(x), axis=1).astype(np.int64)))


@register_op("where")
def _where(ctx, ins, attrs):
    return single(
        jnp.where(ins["Condition"][0], ins["X"][0], ins["Y"][0])
    )


@register_op("multiplex")
def _multiplex(ctx, ins, attrs):
    xs = jnp.stack(ins["X"], axis=0)  # (n, batch, d)
    idx = ins["Ids"][0].reshape(-1).astype(jnp.int32)
    batch = jnp.arange(idx.shape[0])
    return single(xs[idx, batch])


@register_op("range")
def _range(ctx, ins, attrs):
    start = ins["Start"][0] if ins.get("Start") else attrs["start"]
    end = ins["End"][0] if ins.get("End") else attrs["end"]
    step = ins["Step"][0] if ins.get("Step") else attrs["step"]
    return single(jnp.arange(float(start), float(end), float(step)).astype(
        core.np_dtype(core.convert_dtype(attrs.get("dtype", "float32")))
    ))


@register_op("linspace")
def _linspace(ctx, ins, attrs):
    start = float(ins["Start"][0]) if ins.get("Start") else attrs["start"]
    stop = float(ins["Stop"][0]) if ins.get("Stop") else attrs["stop"]
    num = int(ins["Num"][0]) if ins.get("Num") else attrs["num"]
    return single(jnp.linspace(start, stop, num))


@register_op("increment")
def _increment(ctx, ins, attrs):
    return single(ins["X"][0] + attrs.get("step", 1.0))


@register_op("eye")
def _eye(ctx, ins, attrs):
    dtype = core.np_dtype(core.convert_dtype(attrs.get("dtype", "float32")))
    return single(
        jnp.eye(attrs["num_rows"], attrs.get("num_columns") or attrs["num_rows"], dtype=dtype)
    )


@register_op("diag")
def _diag(ctx, ins, attrs):
    return single(jnp.diag(ins["Diagonal"][0]))


@register_op("reverse")
def _reverse(ctx, ins, attrs):
    x = ins["X"][0]
    return single(jnp.flip(x, axis=tuple(attrs["axis"])))


@register_op("roll")
def _roll(ctx, ins, attrs):
    return single(
        jnp.roll(ins["X"][0], attrs["shifts"], axis=tuple(attrs.get("axis", ())) or None)
    )


@register_op("flip")
def _flip(ctx, ins, attrs):
    return single(jnp.flip(ins["X"][0], axis=tuple(attrs["axis"])))


@register_op("crop")
def _crop(ctx, ins, attrs):
    x = ins["X"][0]
    offsets = attrs.get("offsets")
    shape = attrs.get("shape")
    if ins.get("Y") is not None and ins.get("Y"):
        shape = ins["Y"][0].shape
    idx = tuple(
        slice(o, o + s) for o, s in zip(offsets, shape)
    )
    return single(x[idx])


@register_op("label_smooth")
def _label_smooth(ctx, ins, attrs):
    x = ins["X"][0]
    eps = attrs.get("epsilon", 0.1)
    if ins.get("PriorDist"):
        prior = ins["PriorDist"][0]
        out = (1 - eps) * x + eps * prior
    else:
        out = (1 - eps) * x + eps / x.shape[-1]
    return single(out)


@register_op("share_data")
def _share_data(ctx, ins, attrs):
    return single(ins["X"][0])


@register_op("print")
def _print(ctx, ins, attrs):
    x = ins["In"][0]
    import jax as _jax

    _jax.debug.print(attrs.get("message", "") + "{x}", x=x)
    return single(x)


@register_op("decode_cache_write")
def _decode_cache_write(ctx, ins, attrs):
    """TPU-native incremental-decode KV-cache write: Out = Cache with
    the (B, 1, H) step Value written at time index Pos along axis 1.

    Contract: the decode position is UNIFORM across the batch (row 0's
    value is used) — true for the KV-cache decoders here, where every
    row advances one token per scan step. Lowers to
    lax.dynamic_update_slice, an O(B·H) write, replacing the one-hot
    masked rewrite (mul+mul+add over the whole (B, T, H) cache) that
    re-reads and re-writes the entire cache every step — the decode
    equivalent of the reference's in-place beam-search cache kernels
    (ref: paddle/fluid/operators/math/beam_search.cc writes rows in
    place rather than rebuilding the tensor). With ``per_row`` every
    batch row writes its (K, H) block of Value at its own Pos."""
    cache, val, pos = ins["Cache"][0], ins["Value"][0], ins["Pos"][0]
    if attrs.get("per_row"):
        # continuous-batching slot semantics: every row is its OWN
        # sequence at its own position (freed slots restart at 0 while
        # neighbours keep decoding), so the write index varies per row.
        # ONE scatter of B*K rows of width H over (row, position) index
        # pairs: the TPU compiler emits that as a single in-place
        # operation, where a vmap of dynamic_update_slice (a scatter of
        # B windows) is expanded into a loop of B iterations. Rows that
        # would land past the cache's end are dropped (callers keep
        # pos + K <= T).
        b, k, h = val.shape
        rows = jnp.broadcast_to(
            jnp.arange(b, dtype=jnp.int32)[:, None], (b, k))
        cols = (pos.reshape(-1, 1).astype(jnp.int32)
                + jnp.arange(k, dtype=jnp.int32)[None, :])
        dnums = lax.ScatterDimensionNumbers(
            update_window_dims=(1,), inserted_window_dims=(0, 1),
            scatter_dims_to_operand_dims=(0, 1))
        return single(lax.scatter(
            cache, jnp.stack([rows, cols], -1).reshape(b * k, 2),
            val.astype(cache.dtype).reshape(b * k, h), dnums,
            indices_are_sorted=True, unique_indices=True,
            mode=lax.GatherScatterMode.FILL_OR_DROP))
    start = pos.reshape(-1)[0].astype(jnp.int32)
    zero = jnp.int32(0)
    return single(lax.dynamic_update_slice(
        cache, val.astype(cache.dtype), (zero, start, zero)))
