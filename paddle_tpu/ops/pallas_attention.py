"""Fused multi-head attention as Pallas TPU kernels (FlashAttention-2 style).

TPU-native replacement for the reference's attention pattern (ref:
python/paddle/fluid/nets.py:scaled_dot_product_attention and the
matmul+softmax+dropout+matmul chain in its transformer models). Instead of
materialising the (B, H, T, T) score tensor in HBM, the forward kernel keeps
one (block_q, block_k) tile in VMEM at a time with online-softmax
accumulation; backward recomputes tiles flash-style from the saved
log-sum-exp, so attention memory is O(T·D) instead of O(T²).

Design notes (TPU):
- grid = (B*H, Tq/block_q); K and V for one (batch, head) ride whole in VMEM
  (T·D ≤ ~1M elements covers T=16k at D=64 under the default scoped limit;
  the forward call asks for a larger limit where they need it, as 16k at
  D=128 does — beyond that, sequence parallelism via
  parallel/ring_attention.py splits T across chips anyway).
- QK^T and P·V hit the MXU via dot_general with f32 accumulation; the
  running max/sum rescale is VPU work fused around them.
- dropout uses a counter-based hash PRNG written in plain integer jnp ops
  (murmur3 finalizer over absolute tile coordinates), NOT pltpu.prng_*:
  the same bits are regenerated bit-exactly in the backward kernels and in
  interpret mode on CPU, which makes the dropout path unit-testable off-TPU.
- backward = two kernels (FlashAttention-2 split): dq over q-tiles, dk/dv
  over k-tiles, both re-forming P from the saved lse.

`flash_attention` carries a custom_vjp; `reference_attention` is the plain
jax oracle used by tests and by the `fused_multihead_attention` lowering
(`flash_attention` itself is called by `hybrid_ops._flash_gqa`).
"""
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "reference_attention", "window_attention",
           "kept_keys_attention"]

_NEG_INF = -1e30

# Mosaic tiles f32 as (8, 128) sublanes x lanes. Row-vector arrays (lse,
# delta, key-padding mask, dkpm) can't ride a (1, block) block shape on a
# real TPU, so — like jax's official flash kernel (MIN_BLOCK_SIZE) — they
# carry a broadcast trailing lane axis (.., 128) or a sublane axis (8, ..)
# and the kernels slice lane/sublane 0.
_LANES = 128
_SUBLANES = 8
_SCOPED_VMEM = 16 << 20    # what a kernel may hold unless it asks for more


# ---------------------------------------------------------------------------
# counter-based dropout bits (identical in fwd/bwd kernels and on CPU)
# ---------------------------------------------------------------------------
def fold_bh_seed(seed, bh):
    """Mix the (batch·head) grid index into the dropout seed so every head
    draws independent bits (also used by tests to rebuild the mask)."""
    return seed + bh.astype(jnp.int32) * jnp.int32(1000003)


def _tile_random_bits(seed, qi, kj, bq, bk):
    """uint32 bits for the (qi, kj) score tile; pure jnp integer ops."""
    rows = lax.broadcasted_iota(jnp.uint32, (bq, bk), 0)
    cols = lax.broadcasted_iota(jnp.uint32, (bq, bk), 1)
    h = (
        seed.astype(jnp.uint32)
        ^ (qi.astype(jnp.uint32) * jnp.uint32(0x9E3779B9))
        ^ (kj.astype(jnp.uint32) * jnp.uint32(0x85EBCA6B))
    )
    h = h + rows * jnp.uint32(0x27D4EB2F) + cols * jnp.uint32(0x165667B1)
    # murmur3 fmix32
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> 16)
    return h


def _keep_mask(seed, qi, kj, bq, bk, dropout_p):
    bits = _tile_random_bits(seed, qi, kj, bq, bk)
    threshold = jnp.uint32(min(int(dropout_p * 4294967296.0), 4294967295))
    return bits >= threshold


def _causal_mask_tile(qi, kj, bq, bk, offset=None):
    """Query row r of tile qi sees key column c of tile kj where ``r >= c``;
    with ``offset`` the queries stand ``offset`` positions into the keys."""
    rows = qi * bq + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    cols = kj * bk + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    if offset is not None:
        rows = rows + offset
    return rows >= cols


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------
def _fwd_kernel(seed_ref, kpm_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                sm_scale, causal, dropout_p, block_k, nk, off_ref=None):
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    d = v_ref.shape[2]       # the values' width (the keys' may differ)
    q = q_ref[0]                                     # (bq, D)
    seed = fold_bh_seed(seed_ref[0, 0], pl.program_id(0))
    # the row of the keys at which query 0 stands (a chunk of a longer
    # sequence against the rows written so far); None: row 0, and nothing
    # of it in the lowering
    offset = None if off_ref is None else off_ref[0, 0]

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(j * block_k, block_k), :]         # (bk, D)
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale                                          # (bq, bk)
        if kpm_ref is not None:
            # kpm block is (1, SUBLANES, tk) broadcast rows; take row 0
            s = s + kpm_ref[0, 0:1, pl.ds(j * block_k, block_k)]
        if causal:
            s = jnp.where(_causal_mask_tile(qi, j, bq, block_k, offset), s,
                          _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)                                # (bq, bk)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
        if dropout_p > 0.0:
            keep = _keep_mask(seed, qi, j, bq, block_k, dropout_p)
            p_use = jnp.where(keep, p, 0.0) * (1.0 / (1.0 - dropout_p))
        else:
            p_use = p
        v = v_ref[0, pl.ds(j * block_k, block_k), :]          # (bk, D)
        pv = lax.dot_general(
            p_use.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc = acc * alpha + pv
        return m_new, l, acc

    m0 = jnp.full((bq, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq, 1), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    if causal:
        # only tiles that intersect the lower triangle of this q block
        last = (qi + 1) * bq if offset is None else offset + (qi + 1) * bq
        upper = (last + block_k - 1) // block_k
        upper = jnp.minimum(upper, nk)
    else:
        upper = nk
    m, l, acc = lax.fori_loop(0, upper, body, (m0, l0, acc0))
    l_safe = jnp.where(l == 0.0, 1.0, l)
    # fully-masked query rows (m never rose above the mask floor) output 0 —
    # the framework-defined semantic for degenerate causal/padding combos
    dead = m <= _NEG_INF * 0.5
    o_ref[0] = jnp.where(dead, 0.0, acc / l_safe).astype(o_ref.dtype)
    lse_val = jnp.where(dead, _NEG_INF, m + jnp.log(l_safe))   # (bq, 1)
    lse_ref[0] = jnp.broadcast_to(lse_val, (bq, _LANES))


# ---------------------------------------------------------------------------
# backward kernels (FlashAttention-2 split)
# ---------------------------------------------------------------------------
def _p_tile(q, k, kpm_row, lse, qi, j, bq, bk, sm_scale, causal):
    """Recompute P = exp(S - lse) for tile (qi, j); f32. kpm_row is a
    (1, bk) row (sliced from the sublane-broadcast layout)."""
    s = lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * sm_scale
    if kpm_row is not None:
        s = s + kpm_row
    if causal:
        s = jnp.where(_causal_mask_tile(qi, j, bq, bk), s, _NEG_INF)
    # dead rows carry lse = _NEG_INF (see fwd); their P must be 0, not e^0
    return jnp.where(lse <= _NEG_INF * 0.5, 0.0, jnp.exp(s - lse))


def _dq_kernel(seed_ref, kpm_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, *, sm_scale, causal, dropout_p, block_k,
               nk):
    qi = pl.program_id(1)
    bq = q_ref.shape[1]
    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0][:, 0:1]                # (bq, 1) from lane-broadcast
    delta = delta_ref[0][:, 0:1]
    seed = fold_bh_seed(seed_ref[0, 0], pl.program_id(0))

    def body(j, dq_acc):
        k = k_ref[0, pl.ds(j * block_k, block_k), :]
        v = v_ref[0, pl.ds(j * block_k, block_k), :]
        kpm_row = (
            kpm_ref[0, 0:1, pl.ds(j * block_k, block_k)]
            if kpm_ref is not None else None
        )
        p = _p_tile(q, k, kpm_row, lse, qi, j, bq, block_k, sm_scale, causal)
        dpd = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (bq, bk)
        if dropout_p > 0.0:
            keep = _keep_mask(seed, qi, j, bq, block_k, dropout_p)
            dp = jnp.where(keep, dpd, 0.0) * (1.0 / (1.0 - dropout_p))
        else:
            dp = dpd
        ds = p * (dp - delta)                                 # (bq, bk)
        dq_acc = dq_acc + lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        return dq_acc

    if causal:
        upper = ((qi + 1) * bq + block_k - 1) // block_k
        upper = jnp.minimum(upper, nk)
    else:
        upper = nk
    dq = lax.fori_loop(
        0, upper, body, jnp.zeros((bq, q_ref.shape[2]), jnp.float32)
    )
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkdv_kernel(seed_ref, kpm_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 delta_ref, dk_ref, dv_ref, dkpm_ref=None, *, sm_scale,
                 causal, dropout_p, block_q, nq):
    kj = pl.program_id(1)
    bk = k_ref.shape[1]
    k = k_ref[0]
    v = v_ref[0]
    kpm_row = kpm_ref[0, 0:1, :] if kpm_ref is not None else None
    seed = fold_bh_seed(seed_ref[0, 0], pl.program_id(0))

    def body(i, carry):
        dk_acc, dv_acc, dkpm_acc = carry
        q = q_ref[0, pl.ds(i * block_q, block_q), :]
        do = do_ref[0, pl.ds(i * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * block_q, block_q), :][:, 0:1]
        delta = delta_ref[0, pl.ds(i * block_q, block_q), :][:, 0:1]
        p = _p_tile(q, k, kpm_row, lse, i, kj, block_q, bk, sm_scale, causal)
        if dropout_p > 0.0:
            keep = _keep_mask(seed, i, kj, block_q, bk, dropout_p)
            inv = 1.0 / (1.0 - dropout_p)
            pd = jnp.where(keep, p, 0.0) * inv
        else:
            pd = p
        dv_acc = dv_acc + lax.dot_general(
            pd.astype(do_ref.dtype), do.astype(do_ref.dtype),
            (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (bk, D)
        dpd = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )                                                     # (bq, bk)
        if dropout_p > 0.0:
            dp = jnp.where(keep, dpd, 0.0) * inv
        else:
            dp = dpd
        ds = p * (dp - delta)                                 # (bq, bk)
        dk_acc = dk_acc + lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale
        # kpm enters every S row additively -> dkpm[k] = sum over q of dS
        dkpm_acc = dkpm_acc + jnp.sum(ds, axis=0, keepdims=True)
        return dk_acc, dv_acc, dkpm_acc

    if causal:
        lower = (kj * bk) // block_q
    else:
        lower = 0
    d = k_ref.shape[2]
    dk, dv, dkpm = lax.fori_loop(
        lower, nq, body,
        (jnp.zeros((bk, d), jnp.float32), jnp.zeros((bk, d), jnp.float32),
         jnp.zeros((1, bk), jnp.float32)),
    )
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)
    if dkpm_ref is not None:
        dkpm_ref[0] = jnp.broadcast_to(dkpm, (_SUBLANES, bk))


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------
def _specs(bh, t, d, block, have_kpm, heads):
    """Common in_specs for (seed, kpm?, q, k, v) with q blocked over axis 1."""
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    q_spec = pl.BlockSpec((1, block, d), lambda b, i: (b, i, 0))
    kv_spec = pl.BlockSpec((1, t, d), lambda b, i: (b, 0, 0))
    kpm_spec = (
        pl.BlockSpec((1, _SUBLANES, t), lambda b, i: (b // heads, 0, 0))
        if have_kpm else None
    )
    return seed_spec, kpm_spec, q_spec, kv_spec


def _kpm3(kpm):
    """(B, T) additive mask -> sublane-broadcast (B, SUBLANES, T)."""
    return jnp.broadcast_to(
        kpm[:, None, :], (kpm.shape[0], _SUBLANES, kpm.shape[1])
    )


def _fwd_call(q, k, v, kpm, seed, sm_scale, causal, dropout_p, block_q,
              block_k, heads, interpret, offset=None):
    """``offset`` (1, 1) int32 or None: the row of the keys at which query 0
    stands (:func:`flash_attention`'s ``q_offset``), read from SMEM beside
    the seed; None adds no operand and the call is as it was. The values
    may be of another width than the queries and keys (latent attention: 192
    against 128); the output then has the values'."""
    bh, tq, d = q.shape
    tk, dv = k.shape[1], v.shape[2]
    nq = tq // block_q
    nk = tk // block_k
    seed_spec, kpm_spec, q_spec, kv_spec = _specs(
        bh, tk, d, block_q, kpm is not None, heads
    )
    kernel = functools.partial(
        _fwd_kernel if kpm is not None else _fwd_kernel_nokpm,
        sm_scale=sm_scale, causal=causal, dropout_p=dropout_p,
        block_k=block_k, nk=nk,
    )
    in_specs = [seed_spec]
    args = [seed]
    if offset is not None:
        kernel = functools.partial(_fwd_kernel_offset, kernel)
        in_specs.append(seed_spec)
        args.append(offset)
    if kpm is not None:
        in_specs.append(kpm_spec)
        args.append(_kpm3(kpm))
    in_specs += [q_spec, kv_spec,
                 pl.BlockSpec((1, tk, dv), lambda b, i: (b, 0, 0))]
    args += [q, k, v]
    # K and V of one head ride whole in VMEM, each double-buffered: past
    # the compiler's default scoped limit (16,384 positions of 128: 16 MiB
    # for them alone) the call asks for what it holds and as much again for
    # its tiles; below it nothing is passed and the call is as it was
    held = 2 * tk * (d + dv) * k.dtype.itemsize
    params = {}
    if held > _SCOPED_VMEM - (4 << 20):
        params["compiler_params"] = pltpu.CompilerParams(
            vmem_limit_bytes=held + _SCOPED_VMEM)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq),
        in_specs=in_specs,
        out_specs=(
            pl.BlockSpec((1, block_q, dv), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, tq, dv), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, _LANES), jnp.float32),
        ),
        interpret=interpret,
        name="flash_fwd" if offset is None else "flash_fwd_offset",
        **params,
    )(*args)
    return out, lse


def _fwd_kernel_nokpm(seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, **kw):
    _fwd_kernel(seed_ref, None, q_ref, k_ref, v_ref, o_ref, lse_ref, **kw)


def _fwd_kernel_offset(kernel, seed_ref, off_ref, *refs, **kw):
    kernel(seed_ref, *refs, off_ref=off_ref, **kw)


def _dq_kernel_nokpm(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                     delta_ref, dq_ref, **kw):
    _dq_kernel(seed_ref, None, q_ref, k_ref, v_ref, do_ref, lse_ref,
               delta_ref, dq_ref, **kw)


def _dkdv_kernel_nokpm(seed_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                       delta_ref, dk_ref, dv_ref, **kw):
    _dkdv_kernel(seed_ref, None, q_ref, k_ref, v_ref, do_ref, lse_ref,
                 delta_ref, dk_ref, dv_ref, None, **kw)


def _bwd_call(q, k, v, kpm, seed, do, lse, delta, sm_scale, causal,
              dropout_p, block_q, block_k, heads, interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]
    nq = tq // block_q
    nk = tk // block_k
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    kpm_spec = pl.BlockSpec(
        (1, _SUBLANES, tk), lambda b, i: (b // heads, 0, 0)
    )
    full_q = pl.BlockSpec((1, tq, d), lambda b, i: (b, 0, 0))
    full_k = pl.BlockSpec((1, tk, d), lambda b, i: (b, 0, 0))
    row_q = pl.BlockSpec((1, tq, _LANES), lambda b, i: (b, 0, 0))

    kpm3 = _kpm3(kpm) if kpm is not None else None
    # dq: grid over q tiles
    qb = pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0))
    lse_b = pl.BlockSpec((1, block_q, _LANES), lambda b, i: (b, i, 0))
    in_specs = [seed_spec]
    args = [seed]
    if kpm is not None:
        in_specs.append(kpm_spec)
        args.append(kpm3)
    in_specs += [qb, full_k, full_k, qb, lse_b, lse_b]
    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel if kpm is not None else _dq_kernel_nokpm,
            sm_scale=sm_scale, causal=causal, dropout_p=dropout_p,
            block_k=block_k, nk=nk,
        ),
        grid=(bh, nq),
        in_specs=in_specs,
        out_specs=qb,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="flash_dq",
    )(*(args + [q, k, v, do, lse, delta]))

    # dk/dv: grid over k tiles
    kb = pl.BlockSpec((1, block_k, d), lambda b, i: (b, i, 0))
    kpm_b = pl.BlockSpec(
        (1, _SUBLANES, block_k), lambda b, i: (b // heads, 0, i)
    )
    in_specs = [seed_spec]
    args = [seed]
    if kpm is not None:
        in_specs.append(kpm_b)
        args.append(kpm3)
    in_specs += [full_q, kb, kb, full_q, row_q, row_q]
    out_specs = [kb, kb]
    out_shape = [
        jax.ShapeDtypeStruct(k.shape, k.dtype),
        jax.ShapeDtypeStruct(v.shape, v.dtype),
    ]
    if kpm is not None:
        # per-(b·h) partial dkpm rows (sublane-broadcast); summed over
        # heads by the caller
        out_specs.append(
            pl.BlockSpec((1, _SUBLANES, block_k), lambda b, i: (b, 0, i))
        )
        out_shape.append(
            jax.ShapeDtypeStruct((bh, _SUBLANES, tk), jnp.float32)
        )
    outs = pl.pallas_call(
        functools.partial(
            _dkdv_kernel if kpm is not None else _dkdv_kernel_nokpm,
            sm_scale=sm_scale, causal=causal, dropout_p=dropout_p,
            block_q=block_q, nq=nq,
        ),
        grid=(bh, nk),
        in_specs=in_specs,
        out_specs=tuple(out_specs),
        out_shape=tuple(out_shape),
        interpret=interpret,
        name="flash_dkdv",
    )(*(args + [q, k, v, do, lse, delta]))
    if kpm is not None:
        dk, dv, dkpm_bh = outs
    else:
        (dk, dv), dkpm_bh = outs, None
    return dq, dk, dv, dkpm_bh


# ---------------------------------------------------------------------------
# public entry: (B, H, T, D) with custom vjp
# ---------------------------------------------------------------------------
def _pick_block(t, want):
    b = min(want, t)
    while t % b:
        b -= 1
    return b


def _pad_len(t, block):
    """Padded length: pad up to a block multiple rather than shrinking the
    tile (a divisor-poor T like a prime would otherwise degrade to 1-wide
    tiles and O(T²) grid steps)."""
    return (t + block - 1) // block * block


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10)
)
def _flash(q, k, v, kpm, seed, sm_scale, causal, dropout_p, block_q,
           block_k, interpret):
    return _flash_fwd(
        q, k, v, kpm, seed, sm_scale, causal, dropout_p, block_q, block_k,
        interpret,
    )[0]


def _flash_fwd(q, k, v, kpm, seed, sm_scale, causal, dropout_p, block_q,
               block_k, interpret):
    b, h, tq, d = q.shape
    tk = k.shape[2]
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    out, lse = _fwd_call(
        qf, kf, vf, kpm, seed, sm_scale, causal, dropout_p, block_q,
        block_k, h, interpret,
    )
    return out.reshape(b, h, tq, d), (q, k, v, kpm, seed, out, lse)


def _flash_bwd(sm_scale, causal, dropout_p, block_q, block_k, interpret,
               res, g):
    q, k, v, kpm, seed, out_f, lse = res
    b, h, tq, d = q.shape
    tk = k.shape[2]
    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    gf = g.reshape(b * h, tq, d)
    delta = jnp.sum(
        gf.astype(jnp.float32) * out_f.astype(jnp.float32), axis=-1
    )
    # same lane-broadcast layout as lse (see _LANES note at the top)
    delta = jnp.broadcast_to(delta[..., None], delta.shape + (_LANES,))
    dq, dk, dv, dkpm_bh = _bwd_call(
        qf, kf, vf, kpm, seed, gf, lse, delta, sm_scale, causal,
        dropout_p, block_q, block_k, h, interpret,
    )
    dkpm = None
    if kpm is not None:
        dkpm = (
            dkpm_bh[:, 0, :].reshape(b, h, tk).sum(axis=1).astype(kpm.dtype)
        )
    # the int32 seed's formal tangent type is float0 — returning an int32
    # zero relies on lenient custom_vjp checking and can break on upgrades
    dseed = np.zeros(seed.shape, dtype=jax.dtypes.float0)
    return (
        dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape),
        dkpm, dseed,
    )


_flash.defvjp(
    lambda *a: _flash_fwd(*a),
    _flash_bwd,
)


def flash_attention(q, k, v, key_padding_mask=None, seed=None, sm_scale=None,
                    causal=False, dropout_p=0.0, block_q=128, block_k=128,
                    interpret=False, q_offset=None):
    """Flash multi-head attention.

    q: (B, H, Tq, D); k, v: (B, H, Tk, D).
    key_padding_mask: optional additive f32 (B, Tk) (-inf/-1e30 at pads).
    seed: int32 scalar array driving dropout bits (ignored if dropout_p=0).
    q_offset: optional int32 scalar array (traced: one program serves every
    value), the row of the keys at which query 0 stands: query i sees the
    keys ``j <= q_offset + i``. A chunk of a longer sequence against the
    rows written so far (``Tk >= q_offset + Tq``); key tiles past the
    chunk's last query are never visited, so the chunks of a sequence cost
    its causal half between them. Causal and forward only (the call goes
    round the custom vjp), and on this path v may be of another width than q
    and k (the output then has v's). Without it the kernel has no such
    operand and lowers as it did.
    Returns (B, H, Tq, D) in q.dtype.
    """
    if q_offset is not None and not causal:
        raise ValueError("flash_attention(q_offset=...) is a causal call: "
                         "the offset places the queries among the keys")
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    tq, tk = q.shape[2], k.shape[2]
    # prefer exact tiling; for divisor-poor lengths pad up to the block
    # (padding + masking beats shrinking tiles to degenerate widths)
    bq = _pick_block(tq, block_q)
    bk = _pick_block(tk, block_k)
    pad_q = pad_k = 0
    if bq < min(block_q, tq) // 2:
        bq = min(block_q, tq)
        pad_q = _pad_len(tq, bq) - tq
    if bk < min(block_k, tk) // 2:
        bk = min(block_k, tk)
        pad_k = _pad_len(tk, bk) - tk
    if seed is None:
        if dropout_p > 0.0:
            raise ValueError(
                "flash_attention(dropout_p>0) needs an explicit integer "
                "seed (vary it per step, or dropout masks repeat)"
            )
        seed = jnp.zeros((1, 1), jnp.int32)
    else:
        seed = jnp.asarray(seed, jnp.int32).reshape((1, 1))
    kpm = None
    if key_padding_mask is not None:
        kpm = jnp.asarray(key_padding_mask, jnp.float32)
    if pad_k:
        # padded keys are masked out; pad/slice sit OUTSIDE the custom_vjp
        # so autodiff zeroes the pad cotangents for free
        if kpm is None:
            kpm = jnp.zeros((q.shape[0], tk), jnp.float32)
        kpm = jnp.pad(kpm, ((0, 0), (0, pad_k)), constant_values=_NEG_INF)
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    if q_offset is not None:
        b, h, _, d = q.shape
        dv = v.shape[-1]     # may differ from d on this path alone
        out, _ = _fwd_call(
            q.reshape(b * h, -1, d), k.reshape(b * h, -1, d),
            v.reshape(b * h, -1, dv), kpm, seed, float(sm_scale), True,
            float(dropout_p), bq, bk, h, interpret,
            offset=jnp.asarray(q_offset, jnp.int32).reshape((1, 1)))
        out = out.reshape(q.shape[:-1] + (dv,))
    else:
        out = _flash(
            q, k, v, kpm, seed, float(sm_scale), bool(causal),
            float(dropout_p), bq, bk, interpret,
        )
    if pad_q:
        out = out[:, :, :tq, :]
    return out


def reference_attention(q, k, v, key_padding_mask=None, sm_scale=None,
                        causal=False, dropout_p=0.0, dropout_rng=None):
    """Plain-jax oracle with the same semantics (dropout via jax.random —
    bits differ from the pallas kernel; use dropout_p=0 for exact compares).
    Used as the CPU lowering fallback of the fused_multihead_attention op."""
    if sm_scale is None:
        sm_scale = q.shape[-1] ** -0.5
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    if key_padding_mask is not None:
        s = s + key_padding_mask[:, None, None, :]
    if causal:
        tq, tk = q.shape[2], k.shape[2]
        rows = lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        cols = lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        s = jnp.where((rows >= cols)[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    # match the kernel semantic: fully-masked rows produce 0, not uniform
    dead = jnp.max(s, axis=-1, keepdims=True) <= _NEG_INF * 0.5
    p = jnp.where(dead, 0.0, p)
    if dropout_p > 0.0 and dropout_rng is not None:
        keep = jax.random.bernoulli(dropout_rng, 1.0 - dropout_p, p.shape)
        p = jnp.where(keep, p, 0.0) / (1.0 - dropout_p)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v)


# ---------------------------------------------------------------------------
# causal attention over a band (a window layer's prefill), forward only
# ---------------------------------------------------------------------------
def _window_kernel(q_ref, kp_ref, k_ref, vp_ref, v_ref, o_ref, *, sm_scale):
    """One (block of ``window`` queries, query head): the block's rows
    against the block of keys before it and its own, 2 x window columns. A
    row's whole band is here at once, so the softmax is one pass with no
    running maximum; query i sees column j of the block before where
    ``j > i`` (i - (j - window) < window) and of its own where ``j <= i``.
    Block 0 has no block before (its index is clamped to 0 and masked)."""
    c = pl.program_id(1)
    w = q_ref.shape[1]
    q = q_ref[0]                                              # (W, dh)

    def scores(k_ref):
        return lax.dot_general(
            q, k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale    # (W, W)

    rows = lax.broadcasted_iota(jnp.int32, (w, w), 0)
    cols = lax.broadcasted_iota(jnp.int32, (w, w), 1)
    sp = jnp.where((cols > rows) & (c > 0), scores(kp_ref), _NEG_INF)
    so = jnp.where(cols <= rows, scores(k_ref), _NEG_INF)
    m = jnp.maximum(jnp.max(sp, axis=1, keepdims=True),
                    jnp.max(so, axis=1, keepdims=True))
    pp = jnp.exp(sp - m)
    po = jnp.exp(so - m)
    l = (jnp.sum(pp, axis=1, keepdims=True)
         + jnp.sum(po, axis=1, keepdims=True))                # > 0: j == i

    def context(p, v_ref):
        return lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)               # (W, dh)

    o_ref[0] = ((context(pp, vp_ref) + context(po, v_ref)) / l
                ).astype(o_ref.dtype)


def window_attention(q, k, v, heads, kv_heads, window, interpret=False):
    """Causal attention in which query i sees keys ``i - window < j <= i``,
    in the layout the ops hold: q (B, T, heads * dh), k / v (B, T,
    kv_heads * dh) -> (B, T, heads * dh). One call: grid (batch, block of
    ``window`` queries, query head), the head innermost, so the
    ``heads // kv_heads`` consecutive query heads of a group find their key
    and value blocks already in fast memory. No head is repeated and
    nothing is transposed: a block is ``(window, dh)`` columns
    ``[h * dh, (h + 1) * dh)`` of the array as it stands, so ``dh`` and
    ``window`` are multiples of 128. The scores (window, 2 x window)
    float32 never leave the chip. A length the window does not divide is
    padded with zero rows (behind every real query, so none sees them).
    Operands in their own dtype, float32 accumulation and softmax, the
    probabilities cast to the values' dtype for the second product.
    Forward only: the caller differentiates the plain blocks."""
    b, t, _ = q.shape
    dh = q.shape[-1] // heads
    group = heads // kv_heads
    pad = (-t) % window
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (q, k, v))

    def spec(index):
        return pl.BlockSpec((1, window, dh), index)

    mine = spec(lambda i, c, h: (i, c, h))
    own = spec(lambda i, c, h: (i, c, h // group))
    before = spec(lambda i, c, h: (i, jnp.maximum(c - 1, 0), h // group))
    out = pl.pallas_call(
        functools.partial(_window_kernel, sm_scale=dh ** -0.5),
        grid=(b, (t + pad) // window, heads),
        in_specs=[mine, before, own, before, own],
        out_specs=mine,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="window_attn_fwd",
    )(q, k, k, v, v)
    return out[:, :t] if pad else out


def _kept_kernel(q_ref, k_ref, v_ref, keep_ref, o_ref, m_ref, l_ref, acc_ref,
                 *, sm_scale):
    """One (block of queries, head, block of keys) of causal attention over
    the keys a (T, T) int8 selection keeps: the scores of the tile, masked
    to the selection, join the head's running maximum, sum and weighted
    values (online softmax) in fast memory; the last tile of keys a query
    block may see writes the block's output. A tile of keys wholly beyond
    the queries' positions is skipped (its index is clamped, so nothing is
    fetched for it either). Every query keeps at least one key (its
    selection is never empty), so the sum is positive once its tile with a
    kept key has been met, and what a row gathered before that (tiles in
    which it keeps nothing) is wiped by the rescaling."""
    i, j = pl.program_id(1), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    @pl.when(j <= i)
    def _():
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
        s = jnp.where(keep_ref[0] != 0, s, _NEG_INF)          # (bq, bk)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_ref[:, :1] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(j == i)
    def _():
        o_ref[0] = (acc_ref[...] / l_ref[:, :1]).astype(o_ref.dtype)


def kept_keys_attention(q, k, v, keep, heads, sm_scale, block=512,
                        interpret=False):
    """Causal attention of ``heads`` heads in which query t sees only the
    keys s <= t with ``keep[t, s] != 0`` (a learned selection, the same for
    every head), in the layout the ops hold: q and k (B, T, heads * dqk), v
    (B, T, heads * dv), keep (B, T, T) int8 -> (B, T, heads * dv). One
    call: grid (batch, block of queries, head, block of keys), the keys
    innermost and sequential; a head's running maximum, sum and weighted
    values stay in fast memory, so neither the (heads, T, T) scores nor a
    block of them goes through HBM. Nothing is transposed: a tile is
    ``block`` rows of the columns ``[h * d, (h + 1) * d)`` of the array as
    it stands, so ``dqk``, ``dv`` and ``block`` are multiples of 128 and T
    of ``block``. Tiles of keys beyond a query block's positions are
    skipped: the work is the causal half. The selection saves no work here
    (a tile of 512 x 512 that keeps nothing is rare): it is a mask.
    Operands in their own dtype, float32 accumulation and softmax, the
    probabilities cast to the values' dtype for the second product.
    Forward only."""
    b, t, _ = q.shape
    dqk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    if t % block or dqk % _LANES or dv % _LANES or block % _LANES:
        raise ValueError(
            "kept_keys_attention tiles %d positions by blocks of %d and "
            "heads of %d / %d by 128 lanes" % (t, block, dqk, dv))
    n = t // block

    def rows(width, index):
        return pl.BlockSpec((1, block, width), index)

    def seen(i, j):
        """The tile of keys step j fetches: a skipped one's is the last
        fetched again, so nothing moves for it."""
        return jnp.minimum(j, i)

    return pl.pallas_call(
        functools.partial(_kept_kernel, sm_scale=sm_scale),
        grid=(b, n, heads, n),
        in_specs=[rows(dqk, lambda a, i, h, j: (a, i, h)),
                  rows(dqk, lambda a, i, h, j: (a, seen(i, j), h)),
                  rows(dv, lambda a, i, h, j: (a, seen(i, j), h)),
                  pl.BlockSpec((1, block, block),
                               lambda a, i, h, j: (a, i, seen(i, j)))],
        out_specs=rows(dv, lambda a, i, h, j: (a, i, h)),
        out_shape=jax.ShapeDtypeStruct((b, t, heads * dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, _LANES), jnp.float32),
                        pltpu.VMEM((block, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
        name="kept_keys_attn_fwd",
    )(q, k, v, keep)


# ---------------------------------------------------------------------------
# op registration (layer API: fluid.layers.fused_multihead_attention)
# ---------------------------------------------------------------------------
from .registry import register_op, single  # noqa: E402


@register_op("fused_multihead_attention")
def _fused_mha_lowering(ctx, ins, attrs):
    """Q/K/V: (B, H, T, D). Ring attention under an 'sp'-sharded mesh,
    the plain-jax einsum formulation otherwise: XLA fuses it on one chip
    (faster there than the flash kernel at every length measured, PERF.md
    §7) and partitions it over (dp, tp) for free."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    kpm = ins["KeyPaddingMask"][0] if ins.get("KeyPaddingMask") else None
    causal = bool(attrs.get("causal", False))
    p = float(attrs.get("dropout_prob", 0.0))
    if attrs.get("is_test", False) or ctx.is_test:
        p = 0.0
    key = ctx.next_rng() if p > 0.0 else None
    # Under an 'sp'-sharded mesh, exact RING attention keeps every chip
    # holding only its sequence shard of K/V (rotated over ICI via
    # ppermute) instead of the all-gather the einsum formulation would
    # cost — the long-context path. Falls back to einsum for kpm/dropout
    # or non-divisible shapes.
    sp = ctx.mesh_axes.get("sp")
    mesh = getattr(ctx, "mesh", None)
    if (
        sp is not None
        and mesh is not None
        and kpm is None
        and p == 0.0
        and q.shape == k.shape
        and q.shape[2] % mesh.shape[sp] == 0
    ):
        from functools import partial

        from jax.sharding import PartitionSpec as P

        from ..parallel.ring_attention import ring_attention

        dp = ctx.mesh_axes.get("dp")
        tp = ctx.mesh_axes.get("tp")
        dp = dp if dp in mesh.shape else None
        tp = tp if tp in mesh.shape else None
        if dp is not None and q.shape[0] % mesh.shape[dp] != 0:
            dp = None
        if tp is not None and q.shape[1] % mesh.shape[tp] != 0:
            tp = None
        # q/k/v are (B, H, T, D); ring_attention wants (B, T, H, D)
        spec = P(dp, tp, sp, None)

        def body(q_, k_, v_):
            qt = jnp.moveaxis(q_, 1, 2)
            kt = jnp.moveaxis(k_, 1, 2)
            vt = jnp.moveaxis(v_, 1, 2)
            ot = ring_attention(qt, kt, vt, axis_name=sp, causal=causal)
            return jnp.moveaxis(ot, 2, 1)

        out = jax.shard_map(
            body, mesh=mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False,
        )(q, k, v)
        return single(out)

    out = reference_attention(
        q, k, v, kpm, causal=causal, dropout_p=p, dropout_rng=key
    )
    return single(out)
