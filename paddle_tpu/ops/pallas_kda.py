"""The gated delta rule's chunked scan (``kda_scan``, ``ops/hybrid_ops.py``)
as one Pallas TPU kernel: the grid walks the chunks of two neighbouring heads
in order, and their chunks and float32 states never leave fast memory.

The arithmetic is the XLA form's (``hybrid_ops._kda_inputs``, ``_kda_chunks``
and the carry of ``_kda_scan_xla``), every product float32 at
``Precision.HIGHEST`` (Mosaic's ``contract_precision<fp32>``); what differs
is where it runs, the size of the blocks whose pairs are taken one by one
(8 positions, a vreg's sublanes, where the XLA form takes 16) and the order
of the sums inside a chunk.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

F32 = jnp.float32
_HI = lax.Precision.HIGHEST
GROUP = 128     # positions whose state-free part is made together: two
# chunks side by side on the lanes
ROWS = 256      # positions a grid step takes of each of its heads
HEADS = 2       # heads a grid step takes: their states need nothing of each
# other, and one's products fill the other's waits


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=F32, precision=_HI)


def _dot_nt(a, b):
    """a (M, K), b (N, K) -> a b^T (M, N)."""
    return lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                           preferred_element_type=F32, precision=_HI)


def _dot_tn(a, b):
    """a (K, M), b (K, N) -> a^T b (M, N)."""
    return lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                           preferred_element_type=F32, precision=_HI)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _tree_sum(terms):
    while len(terms) > 1:
        terms = [terms[i] + terms[i + 1] if i + 1 < len(terms) else terms[i]
                 for i in range(0, len(terms), 2)]
    return terms[0]


def _each(fn, *lists):
    return [fn(*xs) for xs in zip(*lists)]


def _firsts(x, b):
    """The first b of every 2 b rows."""
    return jnp.concatenate(
        [x[j:j + b] for j in range(0, x.shape[0], 2 * b)], 0)


def _spread(x, b):
    """:func:`_firsts` undone, zeros where the second halves' rows were."""
    gap = jnp.zeros((b, x.shape[1]), F32)
    return jnp.concatenate(
        [y for j in range(0, x.shape[0], b) for y in (x[j:j + b], gap)], 0)


def _groups_parts(qn, kn, vf, g, beta_col, beta_row, chunk, sub):
    """``hybrid_ops._kda_chunks`` for a few groups of GROUP positions (whole
    chunks), each of one head; every argument a list with one entry a group:
    qn, kn (L2-normed, q scaled), vf, g (P, D) float32, beta as a column (P,
    1) and as a row (1, P) -> lists of wu (P, 2 D) = ``[w | u]``, qkT (P, P)
    = qk transposed (key s down, query p across, zero between chunks), qe
    (P, D) = ``q exp(G)`` and gs (P, D) = G, the running sum of g inside
    each chunk. The groups need nothing of each other: every step is written
    for all of them before the next, so that one group's dependent steps
    fill the waits of another's (the compiler's scheduler overlaps what
    stands close together in the program). Matrices over positions are held
    transposed, as the lanes' layout makes them.

    Pairs in different blocks of ``sub`` positions (p in the second half of
    a block of 2 b rows, s in its first; b = sub, 2 sub, ..., chunk / 2)
    take the MXU: their decays ``exp(G_p - G_s)`` as ``exp(G_p - G_r)
    exp(G_r - G_s)`` with r the first half's last row, both exponents <= 0.
    Only the first halves' rows of a transposed matrix are not zero, so only
    they are computed. They are written first: the MXU works while the
    lanes roll.

    Pairs inside a block, the vector units' part, with the POSITIONS ON THE
    LANES (channels down): for every distance o < sub the row ``kk[p, p -
    o] = sum_d k_p k_(p-o) exp(G_p - G_(p-o))`` of all P positions at once,
    the partner a roll of o lanes away, the sum over channels a sum of
    vregs; pair by pair, no ``exp(-G)``. Then the blocks' unit lower systems
    ``(I + diag(beta) kk)^-1`` by forward substitution in the same layout,
    row r of every block at step r.

    The blocks' inverses are joined two and two: with A, B the inverses of
    neighbouring diagonal blocks and C the block below A, the joined block's
    inverse is ``[[A, 0], [-B C A, B]]`` (forward substitution by blocks,
    never a series in powers of L)."""
    n, d = kn[0].shape
    row, col = _iota((n, n), 0), _iota((n, n), 1)
    in_chunk = ((col <= row) & (row // chunk == col // chunk)).astype(F32)
    gs = _each(lambda x: _dot(in_chunk, x), g)

    def across(k_, q_, x, b):
        ref = jnp.concatenate(
            [jnp.broadcast_to(x[j + b - 1:j + b], (2 * b, d))
             for j in range(0, n, 2 * b)], 0)
        e = jnp.exp(-jnp.abs(x - ref))
        later = jnp.where((_iota((n, 1), 0) % (2 * b)) >= b, e, 0.0)
        return _dot_nt(_firsts(k_ * e, b),
                       jnp.concatenate([k_ * later, q_ * later], 0))

    halves = []             # (b, the pairs it holds, [kk^T | qk^T] a group)
    b = sub
    while b < chunk:
        halves.append((
            b, _firsts((row // (2 * b) == col // (2 * b))
                       & ((col % (2 * b)) >= b), b),
            _each(lambda k_, q_, x: across(k_, q_, x, b), kn, qn, gs)))
        b *= 2

    gt, kt, qt = (_each(lambda x: x.T, xs) for xs in (gs, kn, qn))   # (D, P)
    loc = _iota((1, n), 1) % sub                            # p in its block
    dist = _iota((sub, n), 1) % sub - _iota((sub, n), 0)    # loc(p) - s
    qk_in = _each(lambda a, b: jnp.where(
        dist == 0, jnp.sum(a * b, 0, keepdims=True), 0.0), qt, kt)
    low = [[None] for _ in kt]          # low[i][o][0, p] = L[p, p - o]
    for o in range(1, sub):
        live = loc >= o
        pair = _each(lambda a, b: jnp.exp(a - pltpu.roll(a, o, 1))
                     * pltpu.roll(b, o, 1), gt, kt)
        for i, (pr, a, br) in enumerate(zip(pair, kt, beta_row)):
            low[i].append(jnp.where(
                live, br * jnp.sum(pr * a, 0, keepdims=True), 0.0))
        qk_in = _each(lambda pr, a, old: jnp.where(
            (dist == o) & live, jnp.sum(pr * a, 0, keepdims=True), old),
            pair, qt, qk_in)
    unit = (dist == 0).astype(F32)                          # (sub, P)
    inv = [unit for _ in kt]                    # inv[j, p] = X[loc(p), j]
    for r in range(1, sub):
        inv = _each(lambda x, lo: jnp.where(loc == r, unit - _tree_sum(
            [lo[o] * pltpu.roll(x, o, 1) for o in range(1, r + 1)]), x),
            inv, low)

    def blocks(x):
        """(sub, P) blocks laid as columns -> (P, P), zero off the diagonal
        blocks of ``sub``."""
        return jnp.where(row // sub == col // sub,
                         jnp.concatenate([x] * (n // sub), 0), 0.0)

    inv_t, qk_t = _each(blocks, inv), _each(blocks, qk_in)
    for b, apart, both in halves:
        qk_t = _each(lambda x, y: x + _spread(
            jnp.where(apart, y[:, n:], 0.0), b), qk_t, both)
        half = _each(lambda x, y, br: _dot(_firsts(x, b), _spread(
            jnp.where(apart, y[:, :n], 0.0) * br, b)), inv_t, both, beta_row)
        inv_t = _each(lambda x, y: x - _spread(_dot(y, x), b), inv_t, half)
    eg = _each(jnp.exp, gs)
    wu = _each(lambda x, k_, e_, v_, bc: _dot_tn(
        x, jnp.concatenate([k_ * e_, v_], -1) * bc),
        inv_t, kn, eg, vf, beta_col)
    return wu, qk_t, _each(lambda a, b_: a * b_, qn, eg), gs


def _kernel(len_ref, q_ref, k_ref, v_ref, g_ref, beta_ref, betat_ref, a_ref,
            dtb_ref, s_ref, o_ref, so_ref, st_ref, *, heads, together, chunk,
            sub, beta_scale):
    """One grid step: ``rows`` positions of ``together`` neighbouring heads. What
    needs no state for all of them, GROUP positions of a head to a group;
    then the chunks in order, the heads' turn about (their states need
    nothing of each other: one's products fill the other's waits). The
    states ride in ``st_ref`` TRANSPOSED (values down, keys across), so
    that a chunk's decay ``exp(ge)`` is a row and scales its columns."""
    bh, i = pl.program_id(0), pl.program_id(1)
    rows, d = q_ref.shape[1], q_ref.shape[2] // together
    per_row = heads // together

    @pl.when(i == 0)
    def _():
        for j in range(together):
            st_ref[j] = s_ref[0, j].T

    def l2(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    left = len_ref[bh // per_row] - i * rows                 # real rows here
    # a group: (head j of this step's, its lanes, its rows)
    cuts = [(j, slice(j * d, (j + 1) * d), slice(at, at + GROUP))
            for at in range(0, rows, GROUP) for j in range(together)]
    beta_all = beta_ref[0].astype(F32)
    beta_col = [beta_scale * jax.nn.sigmoid(jnp.sum(jnp.where(
        _iota((rows, heads), 1) == (bh % per_row) * together + j, beta_all, 0.0),
        -1, keepdims=True)) for j in range(together)]
    beta_row = [beta_scale * jax.nn.sigmoid(betat_ref[j].astype(F32))
                for j in range(together)]
    live_col = [(_iota((GROUP, 1), 0) + r.start) < left for _, _, r in cuts]
    live_row = [(_iota((1, GROUP), 1) + r.start) < left for _, _, r in cuts]
    kn = [l2(k_ref[0, r, c].astype(F32)) for _, c, r in cuts]
    wu, qk_t, qe, gs = _groups_parts(
        [l2(q_ref[0, r, c].astype(F32)) * d ** -0.5 for _, c, r in cuts],
        kn, [v_ref[0, r, c].astype(F32) for _, c, r in cuts],
        [jnp.where(lv, a_ref[j] * jax.nn.softplus(
            g_ref[0, r, c].astype(F32) + dtb_ref[j]), 0.0)
         for (j, c, r), lv in zip(cuts, live_col)],
        [jnp.where(lv, beta_col[j][r], 0.0)
         for (j, _, r), lv in zip(cuts, live_col)],
        [jnp.where(lv, beta_row[j][:, r], 0.0)
         for (j, _, r), lv in zip(cuts, live_row)], chunk, sub)
    st = [st_ref[j] for j in range(together)]
    for at in range(0, len(cuts), together):
        us, heard = [[] for _ in range(together)], [[] for _ in range(together)]
        for lo in range(0, GROUP, chunk):
            one = slice(lo, lo + chunk)
            for j in range(together):
                n = at + j
                ge = gs[n][lo + chunk - 1:lo + chunk, :]     # (1, D)
                from_state = _dot_nt(
                    jnp.concatenate([qe[n][one], wu[n][one, :d]], 0), st[j])
                u = wu[n][one, d:] - from_state[chunk:]
                st[j] = st[j] * jnp.exp(ge) + _dot_tn(
                    u, kn[n][one] * jnp.exp(ge - gs[n][one]))
                us[j].append(u)
                heard[j].append(from_state[:chunk])
        for j in range(together):
            _, c, r = cuts[at + j]
            o = (jnp.concatenate(heard[j], 0)
                 + _dot_tn(qk_t[at + j], jnp.concatenate(us[j], 0)))
            o_ref[0, r, c] = o.astype(o_ref.dtype)
    for j in range(together):
        st_ref[j] = st[j]

    @pl.when(i == pl.num_programs(1) - 1)
    def _():
        for j in range(together):
            so_ref[0, j] = st[j].T


def kda_scan_fwd(q, k, v, g_raw, beta_raw, a_log, dt_bias, state, length,
                 heads, head_dim, beta_scale, chunk=64, sub=8,
                 interpret=False):
    """``kda_scan``'s forward pass in one call. q, k, v (B, T, heads *
    head_dim) after their convolutions, g_raw the same shape and beta_raw
    (B, T, heads) raw, a_log (heads), dt_bias (heads * head_dim), state (B,
    heads, head_dim, head_dim) float32, length (B, 1) integer: positions at
    or past it get ``beta = 0`` and ``g = 0`` -> (o (B, T, heads *
    head_dim) in q's dtype, the state after the last real position).

    Grid (B * heads / HEADS, T / rows), the second axis sequential: a step
    takes ``rows`` positions of HEADS neighbouring heads where they lie (a
    ``(1, rows, HEADS * head_dim)`` block of each operand: nothing is
    transposed in HBM) and walks their chunks in order. ``head_dim`` is a
    multiple of 128; T is padded to whole groups (the rows added lie past
    ``length``)."""
    b, t, _ = q.shape
    d = head_dim
    if d % 128 or chunk % sub or GROUP % chunk:
        raise ValueError(
            "kda_scan_fwd takes heads of a multiple of 128 channels and "
            "chunks of %d positions in sub-chunks of %d that tile %d lanes"
            % (chunk, sub, GROUP))
    together = HEADS if heads % HEADS == 0 else 1
    pad = (-t) % GROUP      # whole groups: the rows added are past `length`
    if pad:
        q, k, v, g_raw, beta_raw = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            for a in (q, k, v, g_raw, beta_raw))
    t += pad
    rows = ROWS if t % ROWS == 0 else GROUP
    per_row = heads // together

    def wide(index):
        return pl.BlockSpec((1, rows, together * d), index)

    def at_head(bh, i, n):
        return (bh // per_row, i, bh % per_row)

    def of_head(bh, i, n):
        return (bh % per_row, 0, 0)

    def of_state(bh, i, n):
        return (bh // per_row, bh % per_row, 0, 0)

    kernel = functools.partial(_kernel, heads=heads, together=together,
                               chunk=chunk, sub=sub,
                               beta_scale=float(beta_scale))
    out, state = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b * per_row, t // rows),
            in_specs=[wide(at_head), wide(at_head), wide(at_head),
                      wide(at_head),
                      pl.BlockSpec((1, rows, heads),
                                   lambda bh, i, n: (bh // per_row, i, 0)),
                      pl.BlockSpec((together, 1, rows),
                                   lambda bh, i, n: (bh, 0, i)),
                      pl.BlockSpec((together, 1, d), of_head),
                      pl.BlockSpec((together, 1, d), of_head),
                      pl.BlockSpec((1, together, d, d), of_state)],
            out_specs=(wide(at_head),
                       pl.BlockSpec((1, together, d, d), of_state)),
            scratch_shapes=[pltpu.VMEM((together, d, d), F32)]),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, heads, d, d), F32)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="kda_scan_fwd",
    )(length.reshape(b).astype(jnp.int32), q, k, v, g_raw, beta_raw,
      jnp.swapaxes(beta_raw, 1, 2).reshape(b * heads, 1, t),
      jnp.broadcast_to(-jnp.exp(a_log.astype(F32))[:, None, None],
                       (heads, 1, d)),
      dt_bias.astype(F32).reshape(heads, 1, d), state.astype(F32))
    return (out[:, :t - pad] if pad else out), state
