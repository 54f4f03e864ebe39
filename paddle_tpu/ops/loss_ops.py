"""Loss op lowerings (ref: paddle/fluid/operators/cross_entropy_op.cc,
softmax_with_cross_entropy_op.cc, squared_l2_distance, bce ops, hinge,
huber, margin_rank, etc.)."""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single


def _squeeze_label(label):
    if label.ndim >= 2 and label.shape[-1] == 1:
        return label[..., 0]
    return label


@register_op("cross_entropy")
def _cross_entropy(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    ignore = attrs.get("ignore_index", -100)
    eps = 1e-12
    if soft:
        out = -jnp.sum(label * jnp.log(jnp.maximum(x, eps)), axis=-1, keepdims=True)
    else:
        lab = _squeeze_label(label).astype(jnp.int32)
        picked = jnp.take_along_axis(
            x, lab[..., None].clip(0, x.shape[-1] - 1), axis=-1
        )[..., 0]
        out = -jnp.log(jnp.maximum(picked, eps))
        out = jnp.where(lab == ignore, 0.0, out)
        out = out[..., None]
    return {"Y": [out]}


@register_op("cross_entropy2")
def _cross_entropy2(ctx, ins, attrs):
    r = _cross_entropy(ctx, ins, attrs)
    y = r["Y"][0]
    return {"Y": [y], "XShape": [jnp.zeros((0,))], "MatchX": [y]}


@register_op("softmax_with_cross_entropy")
def _softmax_with_ce(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Label"][0]
    soft = attrs.get("soft_label", False)
    ignore = attrs.get("ignore_index", -100)
    axis = attrs.get("axis", -1)
    logp = jax.nn.log_softmax(logits, axis=axis)
    softmax = jnp.exp(logp)
    if soft:
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        lab = _squeeze_label(label).astype(jnp.int32)
        picked = jnp.take_along_axis(
            logp, lab[..., None].clip(0, logits.shape[axis] - 1), axis=axis
        )[..., 0]
        loss = -picked
        loss = jnp.where(lab == ignore, 0.0, loss)
        loss = loss[..., None]
    return {"Softmax": [softmax], "Loss": [loss]}


def head_chunk_rows(n_rows, vocab):
    """Rows of one chunk of the fused vocabulary head, from shapes alone:
    float32 logits of about a quarter of a GB (2,048 rows at a vocabulary
    of 30,522), a multiple of 256, and no more than the rows there are."""
    r = max(256, (1 << 26) // max(int(vocab), 1) // 256 * 256)
    return min(r, -(-int(n_rows) // 8) * 8)


def _head_rows(x, lab, vocab, ignore, dp):
    """What both loops of the fused head start from: x and lab flattened
    to rows; the chunk size r; the labelled rows first, in their order (a
    sort of the row indices alone), padded to whole chunks; each row's
    place in that list; the trips of the loop, the same on every shard of
    `dp`."""
    x, lab = x.reshape(-1, x.shape[-1]), lab.reshape(-1).astype(jnp.int32)
    n = lab.shape[0]
    r = head_chunk_rows(n, vocab)
    keep = lab != ignore
    count = jnp.sum(keep, dtype=jnp.int32)
    iota = lax.iota(jnp.int32, n)
    order = jnp.sort(jnp.where(keep, iota, iota + n))
    rows = jnp.pad(jnp.where(order >= n, order - n, order),
                   (0, -(-n // r) * r - n))
    pos = jnp.maximum(jnp.cumsum(keep, dtype=jnp.int32) - 1, 0)
    trips = (count + (r - 1)) // r
    trips = lax.pmax(trips, dp) if dp else trips
    return x, lab, r, keep, count, rows, pos, trips


def _head_chunk(x, w, lab, rows, c, r, tp):
    """Chunk `c` of the compacted rows: their hidden states, their float32
    logits over the vocabulary this device holds, and their labels as
    columns of it (out of range where another device holds the column)."""
    idx = lax.dynamic_slice(rows, (c * r,), (r,))
    xr = jnp.take(x, idx, axis=0)
    logits = lax.dot_general(xr, w, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    v = w.shape[0]
    col = jnp.take(lab, idx)
    if tp:
        col = (jnp.clip(col, 0, v * lax.axis_size(tp) - 1)
               - lax.axis_index(tp) * v)
    else:
        col = jnp.clip(col, 0, v - 1)
    return idx, xr, logits, col


def _head_fwd_local(x, w, lab, ignore, dp, tp):
    """Losses of the rows one device holds, a chunk of labelled rows at a
    time; `tp` names the axis the vocabulary is split over, `dp` the axis
    whose shards must make the same number of trips."""
    shape = lab.shape
    x, lab, r, keep, count, rows, pos, trips = _head_rows(
        x, lab, w.shape[0], ignore, dp)

    def body(state):
        c, loss_c, lse_c = state
        _, _, logits, col = _head_chunk(x, w, lab, rows, c, r, tp)
        m = jnp.max(logits, axis=-1)
        m = lax.pmax(m, tp) if tp else m
        s = jnp.sum(jnp.exp(logits - m[:, None]), axis=-1)
        lse = m + jnp.log(lax.psum(s, tp) if tp else s)
        held = (col >= 0) & (col < w.shape[0])
        picked = jnp.where(held, jnp.take_along_axis(
            logits, jnp.clip(col, 0, w.shape[0] - 1)[:, None], axis=-1
        )[:, 0], 0.0)
        picked = lax.psum(picked, tp) if tp else picked
        at = (c * r,)
        return (c + 1, lax.dynamic_update_slice(loss_c, lse - picked, at),
                lax.dynamic_update_slice(lse_c, lse, at))

    zeros = jnp.zeros(rows.shape, jnp.float32)
    _, loss_c, lse_c = lax.while_loop(
        lambda state: state[0] < trips, body, (jnp.int32(0), zeros, zeros))
    loss = jnp.where(keep, jnp.take(loss_c, pos), 0.0)
    n_rows = lax.psum(count, dp) if dp else count
    return loss.reshape(shape + (1,)), lse_c, n_rows, trips


def _head_bwd_local(x, w, lab, lse_c, g, ignore, dp, tp):
    """The same loop for the gradients: the chunk's logits again,
    `softmax - onehot` scaled by each row's cotangent, one product into
    the rows' dX and one into a float32 dW."""
    x_shape = x.shape
    x, lab, r, keep, count, rows, pos, trips = _head_rows(
        x, lab, w.shape[0], ignore, dp)
    g = g.reshape(-1).astype(jnp.float32)
    cols = lax.iota(jnp.int32, w.shape[0])

    def body(state):
        c, dx_c, dw = state
        idx, xr, logits, col = _head_chunk(x, w, lab, rows, c, r, tp)
        lse = lax.dynamic_slice(lse_c, (c * r,), (r,))
        live = c * r + lax.iota(jnp.int32, r) < count
        scale = jnp.where(live, jnp.take(g, idx), 0.0)
        d = ((jnp.exp(logits - lse[:, None])
              - (cols[None, :] == col[:, None])) * scale[:, None]
             ).astype(x.dtype)
        dxr = lax.dot_general(d, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
        dxr = lax.psum(dxr, tp) if tp else dxr
        dw = dw + lax.dot_general(d, xr, (((0,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        return (c + 1, lax.dynamic_update_slice(
            dx_c, dxr.astype(x.dtype), (c * r, 0)), dw)

    _, dx_c, dw = lax.while_loop(
        lambda state: state[0] < trips, body,
        (jnp.int32(0), jnp.zeros((rows.shape[0], x.shape[1]), x.dtype),
         jnp.zeros(w.shape, jnp.float32)))
    dx = jnp.where(keep[:, None], jnp.take(dx_c, pos, axis=0), 0)
    dw = lax.psum(dw, dp) if dp else dw
    return dx.reshape(x_shape), dw.astype(w.dtype)


def _head_sharded(local, spmd, args, outs):
    """`local` over the rows and the vocabulary each device holds: one
    list of labelled rows per batch shard, so no hidden state crosses
    devices. `args` / `outs` name what each argument and result is split
    along: "b" the batch, "v" the vocabulary, "-" nothing."""
    if spmd is None:
        return functools.partial(local, dp=None, tp=None)
    from jax.sharding import PartitionSpec as P

    mesh, dp, tp = spmd
    spec = {"b": P(dp), "v": P(tp), "-": P()}
    return jax.shard_map(
        functools.partial(local, dp=dp, tp=tp), mesh=mesh,
        in_specs=tuple(spec[k] for k in args),
        out_specs=tuple(spec[k] for k in outs), check_vma=False)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def linear_softmax_ce(x, w, lab, ignore, spmd):
    """Softmax cross-entropy of `x @ w.T` against `lab`, computed for the
    rows whose label is not `ignore` only, a chunk of rows at a time: no
    array of rows x vocabulary is ever built. x [B, T, H], w [V, H],
    lab [B, T]; returns (loss [B, T, 1] float32, zero at ignored rows; the
    count of labelled rows; the trips of the loop). `spmd` is None or
    (mesh, batch axis or None, vocabulary axis or None)."""
    return _linear_softmax_ce_fwd(x, w, lab, ignore, spmd)[0]


def _linear_softmax_ce_fwd(x, w, lab, ignore, spmd):
    loss, lse_c, n_rows, trips = _head_sharded(
        functools.partial(_head_fwd_local, ignore=ignore), spmd,
        "bvb", "bb--")(x, w, lab)
    return (loss, n_rows, trips), (x, w, lab, lse_c)


def _linear_softmax_ce_bwd(ignore, spmd, res, cts):
    dx, dw = _head_sharded(
        functools.partial(_head_bwd_local, ignore=ignore), spmd,
        "bvbbb", "bv")(*res, cts[0])
    return dx, dw, None


linear_softmax_ce.defvjp(_linear_softmax_ce_fwd, _linear_softmax_ce_bwd)


def _head_spmd(ctx, x, w):
    """How the rows and the vocabulary are split when the lowering is
    partitioned over a mesh: (mesh, 'dp' axis, 'tp' axis), an axis None
    where it does not divide; None on one device, and inside a shard_map
    (the arrays are one shard's already)."""
    mesh = ctx.mesh
    if mesh is None or jax.sharding.get_abstract_mesh().manual_axes:
        return None

    def axis(name, dim):
        a = ctx.mesh_axes.get(name)
        if a in mesh.shape and mesh.shape[a] > 1 and dim % mesh.shape[a] == 0:
            return a
        return None

    dp, tp = axis("dp", x.shape[0]), axis("tp", w.shape[0])
    return (mesh, dp, tp) if dp or tp else None


@register_op("linear_softmax_with_cross_entropy")
def _linear_softmax_with_ce(ctx, ins, attrs):
    """The vocabulary head and its loss in one op: Loss = softmax cross-
    entropy of X @ W^T against Label, over the rows whose label is not
    `ignore_index` only (ops above). Rows / Chunks count, on the device,
    the labelled rows of the step and the trips the loop made."""
    x, w, label = ins["X"][0], ins["W"][0], ins["Label"][0]
    lab = label[..., 0] if label.ndim == x.ndim else label
    lead = x.shape[:-1]
    x3 = x.reshape(lead[0], -1, x.shape[-1])
    loss, n_rows, trips = linear_softmax_ce(
        x3, w, lab.reshape(x3.shape[:2]),
        int(attrs.get("ignore_index", -100)), _head_spmd(ctx, x3, w))
    return {"Loss": [loss.reshape(lead + (1,))], "Rows": [n_rows],
            "Chunks": [trips]}


@register_op("sigmoid_cross_entropy_with_logits")
def _sigmoid_ce(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.maximum(x, 0.0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        cnt = jnp.sum((label != ignore).astype(loss.dtype))
        loss = loss / jnp.maximum(cnt, 1.0)
    return single(loss)


@register_op("square_error_cost")
def _square_error_cost(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = x - y
    return single(d * d)


@register_op("squared_l2_distance")
def _squared_l2_distance(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    d = x - y
    return {
        "Out": [jnp.sum(d * d, axis=-1, keepdims=True)],
        "sub_result": [d],
    }


@register_op("smooth_l1_loss")
def _smooth_l1(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    sigma = attrs.get("sigma", 1.0)
    inw = ins["InsideWeight"][0] if ins.get("InsideWeight") else 1.0
    outw = ins["OutsideWeight"][0] if ins.get("OutsideWeight") else 1.0
    s2 = sigma * sigma
    d = (x - y) * inw
    ad = jnp.abs(d)
    val = jnp.where(ad < 1.0 / s2, 0.5 * d * d * s2, ad - 0.5 / s2)
    out = jnp.sum(val * outw, axis=tuple(range(1, x.ndim)))[:, None]
    return {"Out": [out], "Diff": [d]}


@register_op("huber_loss")
def _huber_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    out = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": [out], "Residual": [r]}


@register_op("hinge_loss")
def _hinge_loss(ctx, ins, attrs):
    logits, label = ins["Logits"][0], ins["Labels"][0]
    return {"Loss": [jnp.maximum(0.0, 1.0 - (2 * label - 1) * logits)]}


@register_op("margin_rank_loss")
def _margin_rank_loss(ctx, ins, attrs):
    label, left, right = ins["Label"][0], ins["X1"][0], ins["X2"][0]
    margin = attrs.get("margin", 0.0)
    out = jnp.maximum(0.0, -label * (left - right) + margin)
    return {"Out": [out], "Activated": [(out > 0).astype(out.dtype)]}


@register_op("rank_loss")
def _rank_loss(ctx, ins, attrs):
    label, left, right = ins["Label"][0], ins["Left"][0], ins["Right"][0]
    d = left - right
    return single(jnp.log1p(jnp.exp(d)) - label * d)


@register_op("bpr_loss")
def _bpr_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    lab = _squeeze_label(label).astype(jnp.int32)
    pos = jnp.take_along_axis(x, lab[:, None], axis=-1)
    diff = x - pos
    loss = jnp.mean(
        jnp.log1p(jnp.exp(diff)), axis=-1, keepdims=True
    )
    return {"Y": [loss]}


@register_op("log_loss")
def _log_loss(ctx, ins, attrs):
    pred, label = ins["Predicted"][0], ins["Labels"][0]
    eps = attrs.get("epsilon", 1e-4)
    return {
        "Loss": [
            -label * jnp.log(pred + eps)
            - (1 - label) * jnp.log(1 - pred + eps)
        ]
    }


@register_op("kldiv_loss")
def _kldiv_loss(ctx, ins, attrs):
    x, target = ins["X"][0], ins["Target"][0]
    red = attrs.get("reduction", "mean")
    loss = target * (jnp.log(jnp.maximum(target, 1e-12)) - x)
    loss = jnp.where(target > 0, loss, 0.0)
    if red == "mean":
        return {"Loss": [jnp.mean(loss)]}
    if red == "sum":
        return {"Loss": [jnp.sum(loss)]}
    if red == "batchmean":
        return {"Loss": [jnp.sum(loss) / x.shape[0]]}
    return {"Loss": [loss]}


@register_op("dice_loss")
def _dice_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    eps = attrs.get("epsilon", 1e-5)
    label_oh = jax.nn.one_hot(_squeeze_label(label).astype(jnp.int32), x.shape[-1])
    reduce_axes = tuple(range(1, x.ndim))
    inter = jnp.sum(x * label_oh, axis=reduce_axes)
    union = jnp.sum(x, axis=reduce_axes) + jnp.sum(label_oh, axis=reduce_axes)
    return single(jnp.mean(1 - (2 * inter + eps) / (union + eps)))


@register_op("center_loss")
def _center_loss(ctx, ins, attrs):
    x, label, centers = ins["X"][0], ins["Label"][0], ins["Centers"][0]
    alpha = ins["CenterUpdateRate"][0] if ins.get("CenterUpdateRate") else 0.5
    lab = _squeeze_label(label).astype(jnp.int32)
    picked = centers[lab]
    diff = x - picked
    loss = 0.5 * jnp.sum(diff * diff, axis=-1, keepdims=True)
    if attrs.get("need_update", True):
        counts = jnp.zeros((centers.shape[0],)).at[lab].add(1.0)
        upd = jnp.zeros_like(centers).at[lab].add(diff)
        new_centers = centers + alpha * upd / (counts[:, None] + 1.0)
    else:
        new_centers = centers
    return {
        "Loss": [loss],
        "SampleCenterDiff": [diff],
        "CentersOut": [new_centers],
    }


@register_op("npair_loss_helper")
def _npair_dummy(ctx, ins, attrs):  # composed in python layer
    raise NotImplementedError


@register_op("mse_loss")
def _mse_loss(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    return single(jnp.mean((x - y) ** 2))


@register_op("sampled_softmax_with_cross_entropy")
def _sampled_softmax_ce(ctx, ins, attrs):
    """Sampled softmax (ref: sample_logits_op.cc). TPU-native: uniform
    candidate sampling with log-q correction, static sample count."""
    logits, label = ins["Logits"][0], ins["Label"][0]
    num_samples = attrs.get("num_samples", 64)
    n_classes = logits.shape[-1]
    lab = label.astype(jnp.int32)  # (batch, num_true)
    samples = jax.random.randint(
        ctx.next_rng(), (num_samples,), 0, n_classes
    )
    # gather true + sampled logits
    true_logits = jnp.take_along_axis(logits, lab, axis=-1)
    sampled_logits = logits[:, samples]
    # remove accidental hits softly: subtract large where sample == label
    hits = (samples[None, None, :] == lab[:, :, None]).any(axis=1)
    sampled_logits = jnp.where(hits, -1e20, sampled_logits)
    all_logits = jnp.concatenate([true_logits, sampled_logits], axis=-1)
    logq = jnp.log(1.0 / n_classes)
    all_logits = all_logits - logq
    tgt = jnp.zeros(all_logits.shape[0], dtype=jnp.int32)
    logp = jax.nn.log_softmax(all_logits, axis=-1)
    loss = -jnp.take_along_axis(logp, tgt[:, None], axis=-1)
    return {"Loss": [loss]}


@register_op("teacher_student_sigmoid_loss")
def _ts_sigmoid_loss(ctx, ins, attrs):
    x, label = ins["X"][0], ins["Label"][0]
    soft_max_up = attrs.get("soft_max_up_bound", 15.0)
    z = jnp.clip(x, -soft_max_up, soft_max_up)
    loss = jnp.log1p(jnp.exp(-jnp.abs(z))) + jnp.maximum(z, 0.0) - z * label
    return {"Y": [loss]}
