"""Misc op lowerings: CTC, NCE, hierarchical sigmoid, row_conv, unfold,
shard_index, hash, cvm, fsp (ref: paddle/fluid/operators/{warpctc_op,nce_op,
hierarchical_sigmoid_op,row_conv_op,unfold_op,shard_index_op,hash_op,cvm_op,
fsp_op}.*)."""
import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single


@register_op("py_func")
def _py_func(ctx, ins, attrs):
    """Custom python op (ref operators/py_func_op.cc) via
    jax.pure_callback: the host function runs outside the XLA module with
    numpy arrays; backward_func (when given) becomes the custom VJP, also
    a callback."""
    from ..fluid.layers.nn import _PY_FUNC_REGISTRY

    func, backward_func, skip = _PY_FUNC_REGISTRY[attrs["func_id"]]
    xs = list(ins["X"])
    out_dtypes = [np.dtype(d) for d in attrs["out_dtypes"]]
    batch = xs[0].shape[0] if xs and xs[0].ndim else 1
    out_shapes = []
    for s in attrs["out_shapes"]:
        out_shapes.append(tuple(batch if d == -1 else d for d in s))
    structs = tuple(
        jax.ShapeDtypeStruct(s, d) for s, d in zip(out_shapes, out_dtypes)
    )

    def host_fwd(*arrays):
        res = func(*arrays)
        if res is None:  # debugging/printing use (ref allows it)
            res = arrays[: len(structs)]
        if not isinstance(res, (tuple, list)):
            res = (res,)
        return tuple(
            np.asarray(r, dtype=d).reshape(s)
            for r, s, d in zip(res, out_shapes, out_dtypes)
        )

    if backward_func is None:
        outs = jax.pure_callback(host_fwd, structs, *xs)
        return {"Out": list(outs)}

    x_names = attrs["x_names"]
    out_names = attrs["out_names"]

    @jax.custom_vjp
    def fwd(*xs_):
        return jax.pure_callback(host_fwd, structs, *xs_)

    def fwd_fwd(*xs_):
        outs = jax.pure_callback(host_fwd, structs, *xs_)
        return outs, (xs_, outs)

    def fwd_bwd(res, gouts):
        xs_, outs = res

        def host_bwd(*arrays):
            n_in = len(xs_)
            n_out = len(outs)
            call_args = []
            it = iter(arrays)
            arr_x = [next(it) for _ in range(n_in)]
            arr_out = [next(it) for _ in range(n_out)]
            arr_g = [next(it) for _ in range(n_out)]
            # ref py_func backward signature: x..., out..., dout...
            # with skip_vars_in_backward_input removed
            for name, a in zip(x_names, arr_x):
                if name not in skip:
                    call_args.append(a)
            for name, a in zip(out_names, arr_out):
                if name not in skip:
                    call_args.append(a)
            call_args.extend(arr_g)
            res_ = backward_func(*call_args)
            if not isinstance(res_, (tuple, list)):
                res_ = (res_,)
            return tuple(
                np.zeros(x.shape, x.dtype) if r is None
                else np.asarray(r, x.dtype).reshape(x.shape)
                for r, x in zip(res_, xs_)
            )

        gx_structs = tuple(
            jax.ShapeDtypeStruct(x.shape, x.dtype) for x in xs_
        )
        gxs = jax.pure_callback(
            host_bwd, gx_structs, *(list(xs_) + list(outs) + list(gouts))
        )
        return tuple(gxs)

    fwd.defvjp(fwd_fwd, fwd_bwd)
    outs = fwd(*xs)
    return {"Out": list(outs)}


@register_op("similarity_focus")
def _similarity_focus(ctx, ins, attrs):
    """Similarity focus mask (ref operators/similarity_focus_op.h): for
    each selected channel slice T (B', C') greedily pick min(B', C')
    maxima with distinct rows AND columns, OR the picks over indexes,
    broadcast over the focus axis."""
    x = ins["X"][0]              # (N, d1, d2, d3)
    axis = attrs["axis"]
    indexes = attrs["indexes"]
    n = x.shape[0]
    # move the focus axis next to batch: (N, A, B, C)
    perm = [0, axis] + [d for d in range(1, 4) if d != axis]
    xt = jnp.transpose(x, perm)
    b_, c_ = xt.shape[2], xt.shape[3]
    k = min(b_, c_)

    def mask_of(t):
        """(B', C') -> greedy distinct-row/col argmax mask."""
        def body(carry, _):
            cur, mask = carry
            idx = jnp.argmax(cur)
            ri, ci = idx // c_, idx % c_
            mask = mask.at[ri, ci].set(1.0)
            cur = jnp.where(jnp.arange(b_)[:, None] == ri, -jnp.inf, cur)
            cur = jnp.where(jnp.arange(c_)[None, :] == ci, -jnp.inf, cur)
            return (cur, mask), None

        (_, mask), _ = lax.scan(
            body, (t.astype(jnp.float32), jnp.zeros((b_, c_))), None,
            length=k,
        )
        return mask

    total = jnp.zeros((n, b_, c_))
    for ind in indexes:
        total = jnp.maximum(total, jax.vmap(mask_of)(xt[:, int(ind)]))
    out = jnp.broadcast_to(total[:, None], xt.shape).astype(x.dtype)
    inv = [0] * 4
    for i, d in enumerate(perm):
        inv[d] = i
    return single(jnp.transpose(out, inv))


@register_op("merge_selected_rows")
def _merge_selected_rows(ctx, ins, attrs):
    """SelectedRows duplicate-row merge (ref operators/
    merge_selected_rows_op): gradients here are DENSE jax arrays (no
    SelectedRows type — XLA scatters duplicate embedding rows at the
    vjp), so rows are already merged; identity."""
    return {"Out": [ins["X"][0]]}


@register_op("get_tensor_from_selected_rows")
def _get_tensor_from_selected_rows(ctx, ins, attrs):
    """SelectedRows -> dense (ref operators/
    get_tensor_from_selected_rows_op): dense already; identity."""
    return {"Out": [ins["X"][0]]}


@register_op("deformable_psroi_pooling")
def _deformable_psroi_pooling(ctx, ins, attrs):
    """Deformable (PS-)ROI pooling (ref operators/deformable_psroi_pooling
    _op.h): each bin samples at its roi-local position shifted by a
    learned normalized offset, averaged over sample_per_part^2 bilinear
    taps; position_sensitive selects the psroi channel."""
    x = ins["Input"][0]          # (N, C, H, W)
    rois = ins["ROIs"][0]        # (R, 4)
    trans = ins["Trans"][0] if ins.get("Trans") else None
    bidx = (
        ins["RoisBatchIdx"][0].astype(jnp.int32)
        if ins.get("RoisBatchIdx")
        else jnp.zeros((rois.shape[0],), jnp.int32)
    )
    no_trans = attrs.get("no_trans", False)
    scale = attrs.get("spatial_scale", 1.0)
    out_c = attrs.get("output_dim")
    group = attrs.get("group_size", [1, 1])
    ph = attrs.get("pooled_height", 1)
    pw = attrs.get("pooled_width", 1)
    part = attrs.get("part_size", [ph, pw])
    spp = max(attrs.get("sample_per_part", 1), 1)
    trans_std = attrs.get("trans_std", 0.1)
    pos_sensitive = attrs.get("position_sensitive", True)
    n, c_in, h, w = x.shape
    gh, gw = (group if isinstance(group, (list, tuple)) else [group] * 2)
    part_h, part_w = (
        part if isinstance(part, (list, tuple)) else [part] * 2
    )

    def pool_one(roi, bi, tr):
        x1 = roi[0] * scale - 0.5
        y1 = roi[1] * scale - 0.5
        x2 = roi[2] * scale + 0.5
        y2 = roi[3] * scale + 0.5
        rh = jnp.maximum(y2 - y1, 0.1)
        rw = jnp.maximum(x2 - x1, 0.1)
        bin_h = rh / ph
        bin_w = rw / pw
        img = x[bi]
        ii = jnp.arange(ph)[:, None]
        jj = jnp.arange(pw)[None, :]
        if no_trans or tr is None:
            dy = jnp.zeros((ph, pw))
            dx = jnp.zeros((ph, pw))
        else:
            pi = jnp.clip((ii * part_h) // ph, 0, part_h - 1)
            pj = jnp.clip((jj * part_w) // pw, 0, part_w - 1)
            dy = tr[0, pi, pj] * trans_std * rh
            dx = tr[1, pi, pj] * trans_std * rw

        def sample(sy, sx):
            py = y1 + ii * bin_h + (sy + 0.5) * bin_h / spp + dy
            px = x1 + jj * bin_w + (sx + 0.5) * bin_w / spp + dx
            # out-of-image taps are SKIPPED (excluded from the count),
            # matching the reference kernel — clamping-in would bias the
            # average toward zero at the border
            ok = (py > -1) & (py < h) & (px > -1) & (px < w)
            py = jnp.clip(py, 0.0, h - 1.0)
            px = jnp.clip(px, 0.0, w - 1.0)
            y0 = jnp.floor(py).astype(jnp.int32)
            x0 = jnp.floor(px).astype(jnp.int32)
            wy = py - y0
            wx = px - x0

            def at(yy, xx):
                return img[:, jnp.clip(yy, 0, h - 1),
                           jnp.clip(xx, 0, w - 1)]

            val = (
                at(y0, x0) * (1 - wy) * (1 - wx)
                + at(y0, x0 + 1) * (1 - wy) * wx
                + at(y0 + 1, x0) * wy * (1 - wx)
                + at(y0 + 1, x0 + 1) * wy * wx
            )                                    # (C, ph, pw)
            okf = ok.astype(img.dtype)
            return val * okf, okf

        acc = jnp.zeros((c_in, ph, pw), x.dtype)
        cnt = jnp.zeros((ph, pw), x.dtype)
        for sy in range(spp):
            for sx in range(spp):
                v, okf = sample(sy, sx)
                acc = acc + v
                cnt = cnt + okf
        acc = acc / jnp.maximum(cnt, 1.0)
        if pos_sensitive:
            gi = jnp.clip((ii * gh) // ph, 0, gh - 1)
            gj = jnp.clip((jj * gw) // pw, 0, gw - 1)
            chan = (
                jnp.arange(out_c)[:, None, None] * gh * gw
                + gi[None] * gw + gj[None]
            )
            return acc[chan, ii[None], jj[None]]
        return acc[:out_c]

    if trans is None:
        out = jax.vmap(lambda r_, b_: pool_one(r_, b_, None))(rois, bidx)
    else:
        out = jax.vmap(pool_one)(rois, bidx, trans)
    return {"Output": [out]}


@register_op("tree_conv")
def _tree_conv(ctx, ins, attrs):
    """Tree-based convolution (ref operators/tree_conv_op.h + math/
    tree2col.cc, TBCNN continuous binary tree). TPU redesign: the
    reference's per-node BFS patch walk becomes max_depth reachability
    matmuls (reach_{d+1} = reach_d @ A) with per-(node, depth) eta
    coefficients — all MXU work, no host tree traversal.

    NodesVector (B, N, F); EdgeSet (B, E, 2) int32 (parent, child) pairs,
    1-indexed, zero rows = padding; Filter (F, 3, output_size,
    num_filters) with dim1 ordered (eta_l, eta_r, eta_t) like tree2col's
    patch layout. Out (B, N, output_size, num_filters)."""
    nodes = ins["NodesVector"][0]       # (B, N, F)
    edges = ins["EdgeSet"][0].astype(jnp.int32)  # (B, E, 2)
    w = ins["Filter"][0]                # (F, 3, S, M)
    max_depth = int(attrs.get("max_depth", 2))
    b, n, f = nodes.shape
    e = edges.shape[1]
    fs, _, s_out, m_out = w.shape

    def per_graph(feat, edge):
        parent = edge[:, 0]
        child = edge[:, 1]
        valid = (parent > 0) & (child > 0)
        p0 = jnp.where(valid, parent - 1, n)     # dump row
        c0 = jnp.where(valid, child - 1, n)
        # adjacency with a dump row/col for padded edges
        adj = jnp.zeros((n + 1, n + 1), nodes.dtype).at[p0, c0].set(
            jnp.where(valid, 1.0, 0.0)
        )[:n, :n]
        # index of each child among its parent's children = 1 + number of
        # EARLIER edge rows with the same parent (tree2col uses the
        # child-list order, which is edge-row order)
        same_parent_before = (
            (parent[None, :] == parent[:, None])
            & valid[None, :] & valid[:, None]
            & (jnp.arange(e)[None, :] < jnp.arange(e)[:, None])
        )
        index_e = 1.0 + jnp.sum(same_parent_before, axis=1)
        pclen_e = jnp.sum(
            (parent[None, :] == parent[:, None]) & valid[None, :]
            & valid[:, None],
            axis=1,
        ).astype(nodes.dtype)
        # scatter per-child (index, pclen) to node ids
        idx_n = jnp.ones((n + 1,), nodes.dtype).at[c0].set(
            jnp.where(valid, index_e, 1.0))[:n]
        pcl_n = jnp.ones((n + 1,), nodes.dtype).at[c0].set(
            jnp.where(valid, pclen_e, 1.0))[:n]

        out = jnp.zeros((n, f * 3), nodes.dtype)
        reach = jnp.eye(n, dtype=nodes.dtype)
        for d in range(max_depth):
            eta_t = (max_depth - d) / max_depth
            if d == 0:
                # the root enters its own patch as TreeNode(index=1,
                # pclen=1) regardless of its position under its parent
                lfac = jnp.full((n,), 0.5, nodes.dtype)
            else:
                lfac = jnp.where(
                    pcl_n == 1.0, 0.5,
                    (idx_n - 1.0) / jnp.maximum(pcl_n - 1.0, 1.0),
                )
            eta_l = (1.0 - eta_t) * lfac
            eta_r = (1.0 - eta_t) * (1.0 - eta_l)
            coefs = jnp.stack(
                [eta_l, eta_r, jnp.full((n,), eta_t, nodes.dtype)], axis=1
            )                                     # (N, 3)
            weighted = feat[:, :, None] * coefs[:, None, :]  # (N, F, 3)
            out = out + reach @ weighted.reshape(n, f * 3)
            reach = reach @ adj
        return out

    patches = jax.vmap(per_graph)(nodes, edges)   # (B, N, F*3)
    wk = w.reshape(fs * 3, s_out * m_out)
    out = (patches.reshape(b, n, fs * 3) @ wk).reshape(b, n, s_out, m_out)
    return single(out)


@register_op("isinf_any")
def _isinf_any(ctx, ins, attrs):
    return single(jnp.any(jnp.isinf(ins["X"][0])))


@register_op("isnan_any")
def _isnan_any(ctx, ins, attrs):
    return single(jnp.any(jnp.isnan(ins["X"][0])))


@register_op("shard_index")
def _shard_index(ctx, ins, attrs):
    x = ins["X"][0]
    index_num = attrs["index_num"]
    nshards = attrs["nshards"]
    shard_id = attrs["shard_id"]
    ignore_value = attrs.get("ignore_value", -1)
    shard_size = (index_num + nshards - 1) // nshards
    in_shard = (x // shard_size) == shard_id
    return single(jnp.where(in_shard, x % shard_size, ignore_value))


@register_op("hash")
def _hash(ctx, ins, attrs):
    x = ins["X"][0].astype(jnp.uint32)
    mod_by = attrs["mod_by"]
    num_hash = attrs.get("num_hash", 1)
    outs = []
    for i in range(num_hash):
        h = (x * jnp.uint32(2654435761) + jnp.uint32(0x9E3779B9 * (i + 1)))
        h = h ^ (h >> 16)
        outs.append((h % jnp.uint32(mod_by)).astype(jnp.int64))
    out = jnp.stack(outs, axis=-2) if num_hash > 1 else outs[0]
    return single(out)


@register_op("row_conv")
def _row_conv(ctx, ins, attrs):
    """Lookahead row convolution over (B, T, D) with future context window."""
    x, w = ins["X"][0], ins["Filter"][0]  # w: (ctx+1, D)
    k = w.shape[0]
    out = jnp.zeros_like(x)
    for i in range(k):
        shifted = jnp.pad(x[:, i:, :], ((0, 0), (0, i), (0, 0)))
        out = out + shifted * w[i][None, None, :]
    return single(out)


@register_op("unfold")
def _unfold(ctx, ins, attrs):
    x = ins["X"][0]
    ks = attrs["kernel_sizes"]
    st = attrs["strides"]
    pd = attrs["paddings"]
    dl = attrs["dilations"]
    n, c, h, w = x.shape
    patches = lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(ks),
        window_strides=tuple(st),
        padding=[(pd[0], pd[0]), (pd[1], pd[1])] if len(pd) == 2 else [(pd[0], pd[1]), (pd[2], pd[3])],
        rhs_dilation=tuple(dl),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    np_, cp, hp, wp = patches.shape
    return {"Y": [patches.reshape(np_, cp, hp * wp)]}


@register_op("im2sequence")
def _im2sequence(ctx, ins, attrs):
    x = ins["X"][0]
    ks = attrs["kernels"]
    st = attrs["strides"]
    patches = lax.conv_general_dilated_patches(
        x,
        filter_shape=tuple(ks),
        window_strides=tuple(st),
        padding="VALID",
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )
    n, cp, hp, wp = patches.shape
    return single(
        jnp.moveaxis(patches.reshape(n, cp, hp * wp), 1, 2).reshape(-1, cp)
    )


@register_op("cvm")
def _cvm(ctx, ins, attrs):
    x = ins["X"][0]
    if attrs.get("use_cvm", True):
        return {"Y": [x]}
    return {"Y": [x[:, 2:]]}


@register_op("fsp")
def _fsp(ctx, ins, attrs):
    x, y = ins["X"][0], ins["Y"][0]
    n, cx = x.shape[0], x.shape[1]
    cy = y.shape[1]
    hw = x.shape[2] * x.shape[3]
    xf = x.reshape(n, cx, hw)
    yf = y.reshape(n, cy, hw)
    return single(jnp.einsum("nch,ndh->ncd", xf, yf) / hw)


@register_op("nce")
def _nce(ctx, ins, attrs):
    """Noise-contrastive estimation with uniform negative sampling."""
    x = ins["Input"][0]          # (B, D)
    label = ins["Label"][0]      # (B, num_true)
    w = ins["Weight"][0]         # (C, D)
    b = ins["Bias"][0] if ins.get("Bias") else None  # (C, 1)
    num_neg = attrs.get("num_neg_samples", 10)
    n_classes = attrs["num_total_classes"]
    lab = label.astype(jnp.int32)
    if lab.ndim == 1:
        lab = lab[:, None]
    neg = jax.random.randint(ctx.next_rng(), (num_neg,), 0, n_classes)

    def score(ids):  # ids (..,) -> logits
        s = jnp.einsum("bd,...d->b...", x, w[ids])
        if b is not None:
            s = s + b[ids, 0]
        return s

    true_logit = jnp.sum(x * w[lab[:, 0]], axis=-1)
    if b is not None:
        true_logit = true_logit + b[lab[:, 0], 0]
    neg_logit = x @ w[neg].T
    if b is not None:
        neg_logit = neg_logit + b[neg, 0][None, :]
    logq = jnp.log(num_neg / n_classes)
    pos_loss = jax.nn.softplus(-(true_logit - logq))
    neg_loss = jnp.sum(jax.nn.softplus(neg_logit - logq), axis=-1)
    return {"Cost": [(pos_loss + neg_loss)[:, None]]}


@register_op("hierarchical_sigmoid")
def _hsigmoid(ctx, ins, attrs):
    """Default complete-binary-tree hierarchical sigmoid."""
    x = ins["X"][0]          # (B, D)
    label = ins["Label"][0]  # (B, 1)
    w = ins["W"][0]          # (C-1, D)
    b = ins["Bias"][0] if ins.get("Bias") else None
    num_classes = attrs["num_classes"]
    depth = max(1, int(np.ceil(np.log2(max(num_classes, 2)))))
    lab = label.astype(jnp.int32)
    if lab.ndim == 2:
        lab = lab[:, 0]
    # complete binary tree: internal node ids along the path to leaf `lab`
    loss = jnp.zeros(x.shape[0], x.dtype)
    node = jnp.ones_like(lab)  # root = 1 (1-indexed heap order)
    code = lab + num_classes   # leaf position in heap
    # walk from leaf up: bits of (lab + C) below the msb give directions
    for d in range(depth, 0, -1):
        parent = code >> d
        bit = (code >> (d - 1)) & 1
        nid = jnp.clip(parent - 1, 0, w.shape[0] - 1)
        valid = parent >= 1
        logit = jnp.sum(x * w[nid], axis=-1)
        if b is not None:
            logit = logit + b[nid, 0]
        # bit==1 → go right (target 1), else 0
        step_loss = jax.nn.softplus(jnp.where(bit == 1, -logit, logit))
        loss = loss + jnp.where(valid, step_loss, 0.0)
    return {"Out": [loss[:, None]]}


@register_op("warpctc")
def _warpctc(ctx, ins, attrs):
    """CTC loss, dense log-domain forward algorithm via lax.scan
    (TPU-native replacement for the warp-ctc CUDA kernel).

    Logits: (B, T, C) padded; Label: (B, L) padded with `blank`;
    LogitsLength/LabelLength: (B,) int. Output: (B, 1) loss.
    """
    logits = ins["Logits"][0]
    label = ins["Label"][0].astype(jnp.int32)
    blank = attrs.get("blank", 0)
    B = logits.shape[0] if logits.ndim == 3 else 1
    if logits.ndim == 2:
        logits = logits[None]
        label = label[None] if label.ndim == 1 else label
    T = logits.shape[1]
    L = label.shape[1]
    logits_len = (
        ins["LogitsLength"][0].astype(jnp.int32).reshape(-1)
        if ins.get("LogitsLength")
        else jnp.full((B,), T, jnp.int32)
    )
    label_len = (
        ins["LabelLength"][0].astype(jnp.int32).reshape(-1)
        if ins.get("LabelLength")
        else jnp.sum((label != blank).astype(jnp.int32), axis=1)
    )
    logp = jax.nn.log_softmax(logits, axis=-1)
    NEG = -1e30

    # extended label: blank, l1, blank, l2, ..., blank  (length S = 2L+1)
    S = 2 * L + 1
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(label)
    pos = jnp.arange(S)[None, :]
    valid_ext = pos < (2 * label_len[:, None] + 1)
    # allowed skip: ext[s] != ext[s-2]
    ext_m2 = jnp.pad(ext, ((0, 0), (2, 0)), constant_values=-1)[:, :S]
    can_skip = (ext != ext_m2) & (pos >= 2)

    alpha0 = jnp.full((B, S), NEG)
    alpha0 = alpha0.at[:, 0].set(logp[:, 0, blank])
    first_lab = jnp.take_along_axis(
        logp[:, 0, :], ext[:, 1:2].clip(0), axis=-1
    )[:, 0]
    alpha0 = alpha0.at[:, 1].set(jnp.where(label_len > 0, first_lab, NEG))

    def step(alpha, t):
        a_prev = alpha
        a_m1 = jnp.pad(alpha, ((0, 0), (1, 0)), constant_values=NEG)[:, :S]
        a_m2 = jnp.pad(alpha, ((0, 0), (2, 0)), constant_values=NEG)[:, :S]
        a_m2 = jnp.where(can_skip, a_m2, NEG)
        merged = jnp.logaddexp(jnp.logaddexp(a_prev, a_m1), a_m2)
        emit = jnp.take_along_axis(logp[:, t, :], ext.clip(0), axis=-1)
        new_alpha = merged + emit
        new_alpha = jnp.where(valid_ext, new_alpha, NEG)
        # freeze past logits_len
        new_alpha = jnp.where((t < logits_len)[:, None], new_alpha, alpha)
        return new_alpha, None

    alpha, _ = lax.scan(step, alpha0, jnp.arange(1, T))
    end1 = 2 * label_len - 1
    end2 = 2 * label_len
    a1 = jnp.take_along_axis(alpha, end1.clip(0)[:, None], axis=1)[:, 0]
    a1 = jnp.where(label_len > 0, a1, NEG)
    a2 = jnp.take_along_axis(alpha, end2[:, None], axis=1)[:, 0]
    loss = -jnp.logaddexp(a1, a2)
    return {"Loss": [loss[:, None]]}


@register_op("space_to_depth")
def _space_to_depth(ctx, ins, attrs):
    x = ins["X"][0]
    b = attrs["blocksize"]
    n, c, h, w = x.shape
    x = x.reshape(n, c, h // b, b, w // b, b)
    x = jnp.transpose(x, (0, 3, 5, 1, 2, 4))
    return single(x.reshape(n, c * b * b, h // b, w // b))


@register_op("affine_channel")
def _affine_channel(ctx, ins, attrs):
    x = ins["X"][0]
    layout = attrs.get("data_layout", "NCHW")
    ch_axis = 1 if layout == "NCHW" else x.ndim - 1
    bshape = [1] * x.ndim
    bshape[ch_axis] = x.shape[ch_axis]
    out = x
    if ins.get("Scale"):
        out = out * ins["Scale"][0].reshape(bshape)
    if ins.get("Bias"):
        out = out + ins["Bias"][0].reshape(bshape)
    return single(out)


@register_op("gru_unit")
def _gru_unit(ctx, ins, attrs):
    """Single GRU step (ref: paddle/fluid/operators/gru_unit_op.cc).
    Input: (B, 3D) projected input; Weight: (D, 3D) with gate weights in
    the first 2D columns and candidate weights in the last D."""
    x = ins["Input"][0]            # (B, 3D)
    h_prev = ins["HiddenPrev"][0]  # (B, D)
    w = ins["Weight"][0]           # (D, 3D)
    b = ins["Bias"][0] if ins.get("Bias") else None
    d = h_prev.shape[-1]
    origin_mode = attrs.get("origin_mode", False)
    gate_act = attrs.get("gate_activation", "sigmoid")
    act = attrs.get("activation", "tanh")
    if b is not None:
        x = x + b.reshape((1, 3 * d))
    gates = x[:, : 2 * d] + h_prev @ w[:, : 2 * d]
    gact = jax.nn.sigmoid if gate_act == "sigmoid" else jnp.tanh
    cact = jnp.tanh if act == "tanh" else jax.nn.relu
    u = gact(gates[:, :d])
    r = gact(gates[:, d : 2 * d])
    reset_h = r * h_prev
    c = cact(x[:, 2 * d :] + reset_h @ w[:, 2 * d :])
    if origin_mode:
        h = u * h_prev + (1 - u) * c
    else:
        h = (1 - u) * h_prev + u * c
    return {"Hidden": [h], "ResetHiddenPrev": [reset_h], "Gate": [gates]}
