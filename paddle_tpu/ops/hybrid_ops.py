"""Lowerings for hybrid state-space / attention / sparse-expert decoders
(Mamba-2 + grouped-query attention + latent mixture of experts).

Precision contract shared by every lowering here: matrix products take
their operands as stored (bfloat16 in a served model) and accumulate in
float32; norms, the router, ``dt``, ``exp(dt A)`` and the state-space state
are float32; what goes back onto the residual stream is rounded to the
input's dtype.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single

F32 = jnp.float32


def _dot_f32(x, w, precision=None):
    """x (..., K) @ w (K, N) -> float32 (..., N), operands as stored."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           precision=precision, preferred_element_type=F32)


@register_op("dense_acc32")
def _dense_acc32(ctx, ins, attrs):
    """Matrix product that keeps its float32 accumulator (router logits,
    the output head): nothing is rounded after the sum."""
    return single(_dot_f32(ins["X"][0], ins["W"][0]))


@register_op("relu_squared")
def _relu_squared(ctx, ins, attrs):
    x = ins["X"][0]
    return single(jnp.square(jnp.maximum(x, 0)))


@register_op("rms_norm")
def _rms_norm(ctx, ins, attrs):
    """``w * x / sqrt(mean(x^2) + eps)`` over the last axis, or over each
    of ``groups`` equal parts of it; with ``Gate`` the input is first
    multiplied by ``silu(gate)`` (Mamba-2's gated norm)."""
    x, w = ins["X"][0], ins["Scale"][0]
    xf = x.astype(F32)
    if ins.get("Gate"):
        xf = xf * jax.nn.silu(ins["Gate"][0].astype(F32))
    groups = int(attrs.get("groups", 1))
    shape = xf.shape
    xg = xf.reshape(shape[:-1] + (groups, shape[-1] // groups))
    ms = jnp.mean(jnp.square(xg), -1, keepdims=True)
    y = (xg * lax.rsqrt(ms + float(attrs.get("epsilon", 1e-5))))
    y = y.reshape(shape) * w.astype(F32)
    return {"Y": [y.astype(x.dtype)]}


@register_op("causal_conv1d")
def _causal_conv1d(ctx, ins, attrs):
    """Depthwise causal convolution over time with the window carried
    between calls. X (B, T, C), Weight (C, K), Bias (C); ``State``
    (B, K-1, C), the K-1 columns before X (zeros without it). Out is
    ``act(conv(X) + b)``; StateOut the K-1 columns that precede the next
    call's first column: the last ones of X, or with ``Len`` (B, 1) the
    ones that end at each row's last real position ``len - 1`` (a
    right-padded prompt hands over the window of its last real token)."""
    x, w = ins["X"][0], ins["Weight"][0]
    k = w.shape[1]
    b, t, c = x.shape
    xf = x.astype(F32)
    if ins.get("State"):
        before = ins["State"][0].astype(F32)
    else:
        before = jnp.zeros((b, k - 1, c), F32)
    full = jnp.concatenate([before, xf], axis=1)          # (B, K-1+T, C)
    wf = w.astype(F32)
    out = sum(full[:, j:j + t] * wf[:, j] for j in range(k))
    if ins.get("Bias"):
        out = out + ins["Bias"][0].astype(F32)
    if attrs.get("activation") == "silu":
        out = jax.nn.silu(out)
    if ins.get("Len"):
        # column len - (K-1) + j of X is column len + j of `full`
        at = (ins["Len"][0].reshape(b, 1).astype(jnp.int32)
              + jnp.arange(k - 1, dtype=jnp.int32)[None, :])
        state = jnp.take_along_axis(full, at[:, :, None], axis=1)
    else:
        state = full[:, t:]
    sdt = ins["State"][0].dtype if ins.get("State") else x.dtype
    return {"Out": [out.astype(x.dtype)], "StateOut": [state.astype(sdt)]}


def _ssm_inputs(xbc, dt_raw, dt_bias, a_log, attrs):
    """Split the convolved [x | B | C] and turn the raw step sizes into
    float32 ``dt`` and ``A``. Leading axes are kept."""
    h, p = int(attrs["heads"]), int(attrs["head_dim"])
    g, n = int(attrs["groups"]), int(attrs["state"])
    lead = xbc.shape[:-1]
    xf = xbc.astype(F32)
    x = xf[..., :h * p].reshape(lead + (g, h // g, p))
    bm = xf[..., h * p:h * p + g * n].reshape(lead + (g, n))
    cm = xf[..., h * p + g * n:].reshape(lead + (g, n))
    dt = jax.nn.softplus(dt_raw.astype(F32) + dt_bias.astype(F32))
    a = -jnp.exp(a_log.astype(F32))
    return x, bm, cm, dt.reshape(lead + (g, h // g)), a.reshape(g, h // g)


@register_op("mamba2_step")
def _mamba2_step(ctx, ins, attrs):
    """One position of the Mamba-2 recurrence for every slot:
    ``h <- exp(dt A) h + dt x B^T``, ``y = h C + D x``. XBC (S, conv_dim)
    after the convolution, Dt (S, heads) raw, State (S, heads, head_dim,
    state) float32 -> Y (S, heads * head_dim), StateOut."""
    xbc, state = ins["XBC"][0], ins["State"][0]
    x, bm, cm, dt, a = _ssm_inputs(xbc, ins["Dt"][0], ins["DtBias"][0],
                                   ins["ALog"][0], attrs)
    s, g, hg, p = x.shape
    n = bm.shape[-1]
    hs = state.astype(F32).reshape(s, g, hg, p, n)
    decay = jnp.exp(dt * a)                                # (S, G, hg)
    hs = (hs * decay[..., None, None]
          + (dt[..., None] * x)[..., None] * bm[:, :, None, None, :])
    y = jnp.sum(hs * cm[:, :, None, None, :], -1)          # (S, G, hg, P)
    y = y + ins["D"][0].astype(F32).reshape(g, hg)[..., None] * x
    return {"Y": [y.reshape(s, g * hg * p).astype(xbc.dtype)],
            "StateOut": [hs.reshape(state.shape).astype(state.dtype)]}


@register_op("mamba2_scan")
def _mamba2_scan(ctx, ins, attrs):
    """The same recurrence over a whole (right-padded) sequence from a
    zero state, in the chunked form: inside a chunk every position sees
    the earlier ones through one masked product, between chunks a short
    scan carries the state. XBC (B, T, conv_dim), Dt (B, T, heads) raw,
    Len (B, 1): positions at or past ``len`` get ``dt = 0``, so the state
    stops at the last real token. -> Y (B, T, heads * head_dim),
    StateOut (B, heads, head_dim, state) float32."""
    xbc = ins["XBC"][0]
    x, bm, cm, dt, a = _ssm_inputs(xbc, ins["Dt"][0], ins["DtBias"][0],
                                   ins["ALog"][0], attrs)
    b, t, g, hg, p = x.shape
    n = bm.shape[-1]
    if ins.get("Len"):
        real = (jnp.arange(t, dtype=jnp.int32)[None, :]
                < ins["Len"][0].reshape(b, 1).astype(jnp.int32))
        dt = jnp.where(real[:, :, None, None], dt, 0.0)
    ln = min(int(attrs.get("chunk", 128)), t)
    pad = (-t) % ln
    if pad:
        x, bm, cm, dt = (jnp.pad(v, [(0, 0), (0, pad)] + [(0, 0)] * (v.ndim - 2))
                         for v in (x, bm, cm, dt))
    nc = (t + pad) // ln
    x = x.reshape(b, nc, ln, g, hg, p)
    bm = bm.reshape(b, nc, ln, g, n)
    cm = cm.reshape(b, nc, ln, g, n)
    dt = dt.reshape(b, nc, ln, g, hg)
    cs = jnp.cumsum(dt * a, axis=2)                        # (B,nc,L,G,hg)
    # inside a chunk: y[l] = sum_{s<=l} (C_l.B_s) exp(cs_l - cs_s) dt_s x_s
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cm, bm)
    cst = jnp.moveaxis(cs, 2, -1)                          # (B,nc,G,hg,L)
    seg = cst[..., :, None] - cst[..., None, :]            # [l, s]
    lower = jnp.tril(jnp.ones((ln, ln), bool))
    w = (jnp.exp(jnp.where(lower, seg, -jnp.inf)) * cb[:, :, :, None]
         * jnp.moveaxis(dt, 2, -1)[..., None, :])          # (B,nc,G,hg,L,L)
    y = jnp.einsum("bcghls,bcsghp->bclghp", w, x)
    # what each chunk adds to the state by its end
    to_end = jnp.exp(cs[:, :, -1:] - cs) * dt              # (B,nc,L,G,hg)
    added = jnp.einsum("bclgh,bclgn,bclghp->bcghpn", to_end, bm, x)
    whole = jnp.exp(cs[:, :, -1])                          # (B,nc,G,hg)

    def carry(hs, c):
        add, dec = c
        return hs * dec[..., None, None] + add, hs        # state at chunk start

    last, starts = lax.scan(
        carry, jnp.zeros((b, g, hg, p, n), F32),
        (jnp.moveaxis(added, 1, 0), jnp.moveaxis(whole, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                    # (B,nc,G,hg,P,N)
    y = y + jnp.einsum("bclgn,bcghpn,bclgh->bclghp", cm, starts,
                       jnp.exp(cs))
    y = y + ins["D"][0].astype(F32).reshape(g, hg)[..., None] * x
    y = y.reshape(b, nc * ln, g * hg * p)[:, :t]
    return {"Y": [y.astype(xbc.dtype)],
            "StateOut": [last.reshape(b, g * hg, p, n)]}


KDA_CHUNK = 64          # positions of a chunk of the delta rule's scan (the
# published kernels' choice); attr ``chunk`` overrides it
KDA_SUB = 16            # a chunk's decays are taken relative to the start of
# a sub-chunk of this many rows; inside one every pair has its own factor
# (the XLA form's; the Pallas kernel takes its pairs one by one in blocks of 8)
KDA_GROUP = 8           # the XLA form's: chunks whose triangular systems are
# solved together (its float32 pairs alive are heads x 8 x 4 x 16 x 16 x 128:
# 268 MB; the Pallas kernel holds a head's chunk in fast memory and has none)
KDA_PRECISION = lax.Precision.HIGHEST   # the scan's float32 products
_kda_dot = functools.partial(jnp.einsum, precision=KDA_PRECISION)


def _kda_inputs(q, k, v, g_raw, beta_raw, a_log, dt_bias, attrs):
    """The delta rule's operands in float32, heads split off: q and k
    (after their convolutions) L2-normed a head (eps 1e-6), q times
    ``head_dim^-1/2``; v; the log decay a channel ``g = -exp(A_log[h]) *
    softplus(g_raw + dt_bias)`` (<= 0); ``beta = beta_scale *
    sigmoid(beta_raw)`` a head. Leading axes are kept."""
    h, d = int(attrs["heads"]), int(attrs["head_dim"])
    lead = q.shape[:-1]

    def heads(x):
        return x.astype(F32).reshape(lead + (h, d))

    def l2(x):
        return x * lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    g = -jnp.exp(a_log.astype(F32))[:, None] * jax.nn.softplus(
        heads(g_raw) + dt_bias.astype(F32).reshape(h, d))
    beta = float(attrs.get("beta_scale", 1.0)) * jax.nn.sigmoid(
        beta_raw.astype(F32))
    return l2(heads(q)) * d ** -0.5, l2(heads(k)), heads(v), g, beta


@register_op("kda_step")
def _kda_step(ctx, ins, attrs):
    """One position of the gated delta rule with a decay a channel (Kimi
    Delta Attention) for every slot: ``S' = diag(exp(g)) S``, ``S <- S' +
    beta k (v - S'^T k)^T``, ``o = S^T q``. Q, K, V (S, heads * head_dim)
    after their convolutions, G (S, heads * head_dim) and Beta (S, heads)
    raw (:func:`_kda_inputs`), State (S, heads, head_dim, head_dim)
    float32, keys down and values across -> O (S, heads * head_dim),
    StateOut. Float32 multiplies and sums, no product the chip would
    round."""
    q, state = ins["Q"][0], ins["State"][0]
    qf, kf, vf, g, beta = _kda_inputs(
        q, ins["K"][0], ins["V"][0], ins["G"][0], ins["Beta"][0],
        ins["ALog"][0], ins["DtBias"][0], attrs)
    sp = state.astype(F32) * jnp.exp(g)[..., None]          # (S, H, Dk, Dv)
    r = vf - jnp.sum(sp * kf[..., None], -2)                # (S, H, Dv)
    sn = sp + (beta[..., None] * kf)[..., None] * r[..., None, :]
    o = jnp.sum(sn * qf[..., None], -2)
    return {"O": [o.reshape(q.shape).astype(q.dtype)],
            "StateOut": [sn.astype(state.dtype)]}


def _unit_lower_inverse(low):
    """(..., n, n) strictly lower triangular L -> ``(I + L)^-1`` by forward
    substitution, row by row (n - 1 dependent rows; a series in powers of L
    cancels catastrophically once ``beta`` nears 2)."""
    n = low.shape[-1]
    eye = jnp.eye(n, dtype=low.dtype)
    x = jnp.broadcast_to(eye, low.shape)
    for t in range(1, n):
        row = eye[t] - jnp.sum(low[..., t, :t, None] * x[..., :t, :], -2)
        x = x.at[..., t, :].set(row)
    return x


def _kda_chunks(qf, kf, vf, g, beta, sub):
    """The part of the chunked delta rule that needs no state, for a batch
    of chunks at once. qf, kf, vf, g (..., C, D) float32, beta (..., C) ->
    ``w`` (..., C, D), ``u`` (..., C, D), ``qk`` (..., C, C), ``qe``, ``ke``
    (..., C, D), ``ge`` (..., D) with which a chunk that starts from the
    state S gives ``U = u - w S``, ``O = qe S + qk U``, ``S <- exp(ge) S +
    ke^T U``.

    With G the running sum of g inside the chunk, ``kk[t, s] = sum_d k_t
    k_s exp(G_t - G_s)`` (s < t) and ``qk[t, s]`` the same with q_t (s <=
    t): ``exp(-G)`` alone overflows float32 after a dozen strong decays, so
    rows and columns of different sub-chunks take their decays relative to
    the start of the row's sub-chunk (both exponents <= 0) and pairs inside
    a sub-chunk take ``exp(G_t - G_s)`` pair by pair. ``[w | u] = (I +
    diag(beta) kk)^-1 diag(beta) [k exp(G) | v]`` by forward substitution:
    inside the sub-chunks row by row, between them block by block."""
    c, d = qf.shape[-2:]
    nb, dot = c // sub, _kda_dot
    gs = jnp.cumsum(g, axis=-2)                              # G, inclusive

    def blocks(x):
        return x.reshape(x.shape[:-2] + (nb, sub, x.shape[-1]))

    qb, kb, gb = blocks(qf), blocks(kf), blocks(gs)
    diff = gb[..., :, None, :] - gb[..., None, :, :]         # [t, s, d]
    seen = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    pair = jnp.exp(jnp.where(seen, diff, -jnp.inf)) * kb[..., None, :, :]
    kk_in = jnp.sum(pair * kb[..., :, None, :], -1)          # (.., nb, s, s)
    qk_in = jnp.sum(pair * qb[..., :, None, :], -1)
    kk_rows, qk_rows = [], []
    for i in range(nb):
        ref = gs[..., i * sub - 1, :][..., None, :] if i else None
        parts_k, parts_q = [], []
        if i:
            rows = jnp.exp(gb[..., i, :, :] - ref)           # <= 1
            cols = kf[..., :i * sub, :] * jnp.exp(ref - gs[..., :i * sub, :])
            parts_k.append(dot("...td,...sd->...ts", kb[..., i, :, :] * rows,
                               cols))
            parts_q.append(dot("...td,...sd->...ts", qb[..., i, :, :] * rows,
                               cols))
        zeros = jnp.zeros(kk_in.shape[:-3] + (sub, c - (i + 1) * sub), F32)
        kk_rows.append(jnp.concatenate(
            parts_k + [kk_in[..., i, :, :], zeros], -1))
        qk_rows.append(jnp.concatenate(
            parts_q + [qk_in[..., i, :, :], zeros], -1))
    qk = jnp.concatenate(qk_rows, -2)                        # s <= t
    low = jnp.concatenate(kk_rows, -2) * beta[..., None]     # diag(beta) kk
    low = jnp.where(jnp.tril(jnp.ones((c, c), bool), -1), low, 0.0)
    rhs = jnp.concatenate([kf * jnp.exp(gs), vf], -1) * beta[..., None]
    inv = _unit_lower_inverse(jnp.stack(
        [low[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
         for i in range(nb)], -3))                           # (.., nb, s, s)
    solved = []
    for i in range(nb):
        r = rhs[..., i * sub:(i + 1) * sub, :]
        if i:
            r = r - dot("...ts,...sd->...td",
                        low[..., i * sub:(i + 1) * sub, :i * sub],
                        jnp.concatenate(solved, -2))
        solved.append(dot("...ts,...sd->...td", inv[..., i, :, :], r))
    wu = jnp.concatenate(solved, -2)
    ge = gs[..., -1, :]
    return (wu[..., :d], wu[..., d:], qk, qf * jnp.exp(gs),
            kf * jnp.exp(ge[..., None, :] - gs), ge)


def _kda_scan_xla(q, k, v, g_raw, beta_raw, a_log, dt_bias, state, length,
                  attrs):
    """:func:`_kda_scan` through XLA, what every platform can run and the
    Pallas kernel's comparison and gradient. ``state`` (B, heads, head_dim,
    head_dim) float32, ``length`` (B, 1) or None (every position real) ->
    (o, state after the last real position).

    KDA_GROUP chunks at a time: what of a chunk needs no state
    (:func:`_kda_chunks`: the pairs' decays, the triangular system) for the
    whole group at once, then a scan over the group's chunks that carries
    the state and makes each chunk's output inside the carry. Nothing of
    the sequence's length is held in float32 but the op's own inputs: a
    group's operands are cut from them where they lie."""
    h, d = int(attrs["heads"]), int(attrs["head_dim"])
    b, t, _ = q.shape
    c = min(int(attrs.get("chunk") or KDA_CHUNK), t)
    sub = KDA_SUB if c % KDA_SUB == 0 else c
    nc = min(KDA_GROUP, -(-t // c))
    span = nc * c                                            # a group's rows
    groups = -(-t // span)
    real = jnp.arange(groups * span, dtype=jnp.int32)[None, :] < (
        length.reshape(b, 1).astype(jnp.int32) if length is not None else t)
    pad = groups * span - t
    if pad:
        q, k, v, g_raw, beta_raw = (
            jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
            for a in (q, k, v, g_raw, beta_raw))
    dot = _kda_dot

    def chunked(x):
        """(B, span, H, .) -> (B, H, nc, C, .)"""
        x = x.reshape((b, nc, c, h) + x.shape[3:])
        return jnp.moveaxis(x, 3, 1)

    def group(s, at):
        cut = [lax.dynamic_slice_in_dim(a, at * span, span, axis=1)
               for a in (q, k, v, g_raw, beta_raw)]
        live = lax.dynamic_slice_in_dim(real, at * span, span, axis=1)
        qf, kf, vf, g, beta = _kda_inputs(*cut, a_log, dt_bias, attrs)
        g = jnp.where(live[:, :, None, None], g, 0.0)
        beta = jnp.where(live[:, :, None], beta, 0.0)
        parts = _kda_chunks(chunked(qf), chunked(kf), chunked(vf),
                            chunked(g), chunked(beta), sub)

        def chunk(s, p):
            w, u, qk, qe, ke, ge = p                         # (B, H, C, .)
            u = u - dot("bhck,bhkv->bhcv", w, s)
            o = dot("bhck,bhkv->bhcv", qe, s) + dot("bhcs,bhsv->bhcv", qk, u)
            s = s * jnp.exp(ge)[..., None] + dot("bhck,bhcv->bhkv", ke, u)
            return s, o

        s, o = lax.scan(chunk, s, tuple(jnp.moveaxis(p, 2, 0) for p in parts))
        o = jnp.moveaxis(o, 0, 2).reshape(b, h, span, d)     # (B, H, span, D)
        return s, jnp.swapaxes(o, 1, 2).reshape(b, span, h * d).astype(
            q.dtype)

    state, out = lax.scan(group, state, jnp.arange(groups, dtype=jnp.int32))
    out = jnp.moveaxis(out, 0, 1).reshape(b, groups * span, h * d)[:, :t]
    return out, state


@functools.partial(jax.jit, static_argnums=(9, 10, 11, 12))
def _kda_scan_call(q, k, v, g_raw, beta_raw, a_log, dt_bias, state, length,
                   heads, head_dim, beta_scale, interpret):
    """The kernel's call as one jitted function: the call sites of a program
    (twelve in Solar's 16,384 prefill) and the programs of a process share
    ONE trace of the kernel's body and one lowering a module, where each
    site alone costs most of a second of a serving process's set-up."""
    from .pallas_kda import kda_scan_fwd

    return kda_scan_fwd(q, k, v, g_raw, beta_raw, a_log, dt_bias, state,
                        length, heads, head_dim, beta_scale, chunk=KDA_CHUNK,
                        interpret=interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11, 12))
def _kda_scan_kernel(q, k, v, g_raw, beta_raw, a_log, dt_bias, state, length,
                     heads, head_dim, beta_scale, interpret=False):
    """:func:`_kda_scan_xla`'s result from the Pallas kernel
    (``ops/pallas_kda.py`` ``kda_scan_fwd``: a head's chunk and its float32
    state in fast memory, the same float32 products at ``highest``).
    Forward only; its gradient is the XLA form's."""
    return _kda_scan_call(q, k, v, g_raw, beta_raw, a_log, dt_bias, state,
                          length, heads, head_dim, beta_scale, interpret)


def _kda_scan_kernel_bwd(heads, head_dim, beta_scale, interpret, res, g):
    *given, length = res
    attrs = {"heads": heads, "head_dim": head_dim, "beta_scale": beta_scale}
    return jax.vjp(lambda *a: _kda_scan_xla(*a, length, attrs),
                   *given)[1](g) + (None,)


_kda_scan_kernel.defvjp(
    lambda *a: (_kda_scan_kernel(*a), a[:9]), _kda_scan_kernel_bwd)


@register_op("kda_scan")
def _kda_scan(ctx, ins, attrs):
    """:func:`_kda_step`'s recurrence over a whole right-padded sequence, in
    the chunked form. Q, K, V (B, T, heads * head_dim) after their
    convolutions, G (B, T, heads * head_dim) and Beta (B, T, heads) raw;
    ``State`` (B, heads, head_dim, head_dim) float32 the state before the
    first position (zeros without it); ``Len`` (B, 1): positions at or past
    ``len`` get ``beta = 0`` and ``g = 0``, so the state stops at the last
    real token. -> O (B, T, heads * head_dim), StateOut.

    The op chooses from what it sees: on the TPU, in an unsharded program,
    with heads of a multiple of 128 channels and T whole chunks of the
    published 64 positions, the Pallas kernel (:func:`_kda_scan_kernel`);
    anything else (a CPU, a mesh, a narrow head, a ragged T, another
    ``chunk``) the same arithmetic through XLA (:func:`_kda_scan_xla`),
    which is also the kernel's gradient. Which one a lowering took is
    counted: ``ops.kda_scan.kernel`` / ``ops.kda_scan.xla``."""
    from .. import observability as obs

    q = ins["Q"][0]
    h, d = int(attrs["heads"]), int(attrs["head_dim"])
    b, t, _ = q.shape
    state = (ins["State"][0].astype(F32) if ins.get("State")
             else jnp.zeros((b, h, d, d), F32))
    length = ins["Len"][0] if ins.get("Len") else None
    given = (q, ins["K"][0], ins["V"][0], ins["G"][0], ins["Beta"][0],
             ins["ALog"][0], ins["DtBias"][0], state)
    if (getattr(ctx, "platform", None) == "tpu"
            and not getattr(ctx, "mesh_axes", None) and d % 128 == 0
            and int(attrs.get("chunk") or KDA_CHUNK) == KDA_CHUNK
            and t % KDA_CHUNK == 0):
        obs.inc("ops.kda_scan.kernel")
        if length is None:
            length = jnp.full((b, 1), t, jnp.int32)
        out, state = _kda_scan_kernel(
            *given, length, h, d, float(attrs.get("beta_scale", 1.0)))
    else:
        obs.inc("ops.kda_scan.xla")
        out, state = _kda_scan_xla(*given, length, attrs)
    return {"O": [out], "StateOut": [state]}


def rotary_inv_freq(rot, theta, yarn=None):
    """(rot / 2,) float32 turning rates of a rotary term over ``rot``
    dimensions, and the factor on its cos and sin. Plain:
    ``theta^(-2i/rot)``, factor 1. ``yarn`` ((factor, original positions,
    beta_fast, beta_slow, attention_factor); Peng et al. 2023,
    arXiv:2309.00071, as ``transformers`` computes it): pair i keeps its
    rate where it turns more than ``beta_fast`` times over the original
    positions, has it divided by ``factor`` where it turns fewer than
    ``beta_slow`` times, and a linear blend between (the corrections
    floored and ceiled, clipped to [0, rot - 1])."""
    import math

    import numpy as np

    f = float(theta) ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if not yarn:
        return (1.0 / f).astype(np.float32), 1.0
    factor, original, fast, slow, attention_factor = yarn

    def correction(turns):
        return (rot * math.log(original / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(fast)), 0)
    high = min(math.ceil(correction(slow)), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 - ramp) / f + ramp / (factor * f)
    return inv.astype(np.float32), float(attention_factor)


@register_op("rotary_embedding")
def _rotary_embedding(ctx, ins, attrs):
    """Rotary position term: X (B, T, heads, dh); the row at t along axis
    1 stands at position t, or with ``Pos`` (B, 1) at ``pos[b] + t`` (a
    decode step's row at its slot's position). Over the first
    ``rotary_dim`` dimensions of a head (default: all) pair i is ``(x[i],
    x[i + rotary_dim/2])`` (half-split) or, with ``interleaved``,
    ``(x[2i], x[2i + 1])``, turned by ``position * rate_i``
    (:func:`rotary_inv_freq`; ``yarn`` blends and scales); the other
    dimensions pass unturned. Either way the turned pair i comes out at
    ``(i, i + rotary_dim/2)``: an interleaved head leaves de-interleaved,
    as ``transformers`` hands it on (queries and keys alike, so their
    products do not see it). Computed in float32, returned in X's
    dtype."""
    x = ins["X"][0]
    t, dh = x.shape[1], x.shape[-1]
    rot = int(attrs.get("rotary_dim") or dh)
    half = rot // 2
    freq, factor = rotary_inv_freq(rot, float(attrs["theta"]),
                                   attrs.get("yarn") or None)
    at = jnp.arange(t, dtype=F32)[None, :]                    # (1, T)
    if ins.get("Pos"):
        at = at + ins["Pos"][0].reshape(-1, 1).astype(F32)    # (B, T)
    ang = at[:, :, None] * jnp.asarray(freq)[None, None, :]   # (., T, rot/2)
    cos = (jnp.cos(ang) * factor)[:, :, None, :]
    sin = (jnp.sin(ang) * factor)[:, :, None, :]
    xf = x.astype(F32)
    if attrs.get("interleaved"):
        pairs = xf[..., :rot].reshape(xf.shape[:-1] + (half, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = xf[..., :half], xf[..., half:rot]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                           xf[..., rot:]], -1)
    return single(out.astype(x.dtype))


FLASH_MIN_SEQ = 1024    # a causal call this long takes the flash kernels
FLASH_BLOCK = 512       # query and key tile of the flash kernels here:
# 23.6 ms forward + backward at 4 x 32 heads x 4,096 x 64 where 256 reads
# 41.7 and 128 reads 81.5; 1,024 runs out of fast memory (PERF.md)


def _flash_gqa(q, k, v, nh, nkv, offset=None):
    """Causal attention through the Pallas flash kernels (forward, dq,
    dk/dv; ``ops/pallas_attention.py``): no (T, T) score array exists in
    either pass. The kernels take one key/value head per query head, so
    the key/value heads are repeated; the sum over a group in the
    backward pass is the repeat's own transpose. With ``offset`` (a traced
    int32 scalar) the queries stand that many rows into the keys
    (``flash_attention``'s ``q_offset``; forward only)."""
    from .pallas_attention import flash_attention

    b, t, _ = q.shape
    dh = q.shape[-1] // nh

    def heads_first(x, n):
        return jnp.swapaxes(x.reshape(b, -1, n, dh), 1, 2)  # (B, n, T, dh)

    kh = jnp.repeat(heads_first(k, nkv), nh // nkv, axis=1)
    vh = jnp.repeat(heads_first(v, nkv), nh // nkv, axis=1)
    out = flash_attention(heads_first(q, nh), kh, vh, causal=True,
                          block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
                          q_offset=offset)
    return jnp.swapaxes(out, 1, 2).reshape(b, t, nh * dh)


def _banded_gqa(q, k, v, nh, nkv, window):
    """Causal attention in which query i sees keys i - window < j <= i,
    without (T, T) scores and at the window's cost: the queries in blocks
    of ``window`` rows, each against its own block of keys and the one
    before it (2 x window columns, masked to the band), one block a trip
    of a loop, so the scores alive are (heads, window, 2 x window). The
    plain path: float32 scores through HBM, what the CPU, a sharded
    program and a window or head that does not tile take; the Pallas
    kernel's comparison and its gradient (:func:`_window_gqa`)."""
    b, t, _ = q.shape
    dh = q.shape[-1] // nh
    pad = (-t) % window
    if pad:
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (q, k, v))
    nb = (t + pad) // window
    qb = q.reshape(b, nb, window, nkv, nh // nkv, dh)

    def blocks(a):
        """(nb, B, 2 x window, kv heads, dh): block c - 1 (zeros before the
        first) and block c of the keys or values."""
        a = a.reshape(b, nb, window, nkv, dh)
        before = jnp.concatenate([jnp.zeros_like(a[:, :1]), a[:, :-1]], 1)
        return jnp.moveaxis(jnp.concatenate([before, a], 2), 1, 0)

    row = jnp.arange(window, dtype=jnp.int32)[:, None]
    col = jnp.arange(2 * window, dtype=jnp.int32)[None, :] - window
    band = (col <= row) & (row - col < window)             # (W, 2W)

    def one(args):
        c, qc, kc, vc = args
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qc, kc,
                            preferred_element_type=F32) * dh ** -0.5
        seen = band & ((col >= 0) | (c > 0))               # no block before 0
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(vc.dtype), vc,
                          preferred_element_type=F32).astype(q.dtype)

    out = lax.map(one, (jnp.arange(nb, dtype=jnp.int32),
                        jnp.moveaxis(qb, 1, 0), blocks(k), blocks(v)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t + pad, nh * dh)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _window_gqa(q, k, v, nh, nkv, window, interpret=False):
    """:func:`_banded_gqa`'s result from the Pallas kernel for the band
    (``ops/pallas_attention.py`` ``window_attention``): one call, the
    op's own layout, the scores never in HBM. Forward only; its gradient
    is the banded blocks' own."""
    from .pallas_attention import window_attention

    return window_attention(q, k, v, nh, nkv, window, interpret=interpret)


def _window_gqa_bwd(nh, nkv, window, interpret, res, g):
    return jax.vjp(lambda q, k, v: _banded_gqa(q, k, v, nh, nkv, window),
                   *res)[1](g)


_window_gqa.defvjp(lambda q, k, v, *a: (_window_gqa(q, k, v, *a), (q, k, v)),
                   _window_gqa_bwd)


@register_op("gqa_attention")
def _gqa_attention(ctx, ins, attrs):
    """Softmax attention with fewer key/value heads than query heads; no
    position term of its own. Q (B, Tq, heads * dh), K/V (B, Tk,
    kv_heads * dh). With ``Pos`` (B, 1) the keys are a slot cache and row
    b sees columns <= pos[b]: the rows of a sequence, or a ring of the
    last Tk positions written at ``position mod Tk`` (every column of it
    once ``pos >= Tk - 1``; a softmax does not mind the order). With
    ``Offset`` (1, 1) the queries are a chunk of a longer sequence whose rows
    so far are the keys: query i stands at row ``offset + i`` and sees the
    columns up to it (Tk >= offset + Tq). With neither, Tq == Tk and the
    mask is causal, with ``window`` > 0 cut to the last ``window`` positions.
    The op chooses from what it sees, no caller sets anything. A sequence
    longer than its window, on the TPU, in an unsharded program, with a
    head size and a window that tile (multiples of 128): the Pallas kernel
    for the band
    (:func:`_window_gqa`); any other sequence longer than its window
    (the CPU, a mesh, small windows): the banded blocks through XLA
    (:func:`_banded_gqa`), which are also the kernel's gradient. A plain
    causal call of at least FLASH_MIN_SEQ positions, a chunk's too, runs
    through the flash kernels on the TPU (a training sequence of 4,096 would
    otherwise hold (B, heads, T, T) float32 scores); shorter calls, other
    platforms and a sharded program take the products below. Which of the
    two window paths a lowering took is counted:
    ``ops.gqa_attention.window_kernel`` / ``.window_banded``."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    nh, nkv = int(attrs["heads"]), int(attrs["kv_heads"])
    window = int(attrs.get("window") or 0)
    b, tq, _ = q.shape
    tk = k.shape[1]
    dh = q.shape[-1] // nh
    if ins.get("Pos") and window:
        raise ValueError("gqa_attention over a slot cache takes no window: "
                         "a window layer's cache is a ring of that length")
    offset = None
    if ins.get("Offset"):
        if ins.get("Pos") or window:
            raise ValueError("gqa_attention of a chunk (Offset) takes "
                             "neither Pos nor a window")
        offset = ins["Offset"][0].reshape(()).astype(jnp.int32)
    unsharded_tpu = (getattr(ctx, "platform", None) == "tpu"
                     and not getattr(ctx, "mesh_axes", None))
    if window and tq > window:
        from .. import observability as obs

        if unsharded_tpu and dh % 128 == 0 and window % 128 == 0:
            obs.inc("ops.gqa_attention.window_kernel")
            return single(_window_gqa(q, k, v, nh, nkv, window))
        obs.inc("ops.gqa_attention.window_banded")
        return single(_banded_gqa(q, k, v, nh, nkv, window))
    if not ins.get("Pos") and tq >= FLASH_MIN_SEQ and unsharded_tpu:
        return single(_flash_gqa(q, k, v, nh, nkv, offset))
    qg = q.reshape(b, tq, nkv, nh // nkv, dh)
    kg = k.reshape(b, tk, nkv, dh)
    vg = v.reshape(b, tk, nkv, dh)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, kg,
                        preferred_element_type=F32) * dh ** -0.5
    at = jnp.arange(tk, dtype=jnp.int32)
    if ins.get("Pos"):
        seen = at[None, :] <= ins["Pos"][0].reshape(b, 1).astype(jnp.int32)
        seen = seen[:, None, None, None, :]
    else:
        rows = (at if offset is None
                else jnp.arange(tq, dtype=jnp.int32) + offset)
        seen = (at[None, :] <= rows[:, None])[None, None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    ctxv = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), vg,
                      preferred_element_type=F32)
    return single(ctxv.reshape(b, tq, nh * dh).astype(q.dtype))


@register_op("kv_ring_gather")
def _kv_ring_gather(ctx, ins, attrs):
    """What a prefill hands a window layer's ring: X (B, T, C) one row a
    position, Len (B, 1) -> Out (B, window, C) whose column j is the row
    of the last real position p < len with ``p mod window == j`` (zeros
    where there is none yet)."""
    x, window = ins["X"][0], int(attrs["window"])
    last = ins["Len"][0].reshape(-1, 1).astype(jnp.int32) - 1     # (B, 1)
    j = jnp.arange(window, dtype=jnp.int32)[None, :]
    at = last - jnp.mod(last - j, window)                         # (B, W)
    rows = jnp.take_along_axis(x, jnp.maximum(at, 0)[:, :, None], axis=1)
    return single(jnp.where((at >= 0)[:, :, None], rows, 0).astype(x.dtype))


DSA_QUERY_BLOCK = 128   # queries a trip of the selection's and the masked
# attention's loops over a prompt: the float32 scores alive are (index heads,
# 128, keys) and (heads, 128, keys), 0.27 and 0.54 GB at 16,384 keys
KEPT_BLOCK = 512        # query and key tile of the kept-keys kernel
DSA_TIERS = 4           # a prompt's queries in this many runs, run j against
# the keys [0, (j + 1) T / tiers): 0.625 of the square where one run is all
# of it and the causal half is 0.5


def _ordered_bits(x):
    """float32 -> uint32 in the same order (no NaN; -0.0 counts as 0.0)."""
    b = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(b >> 31 == 1, ~b, b | jnp.uint32(1 << 31))


def _kth_largest(keys, k):
    """keys (..., n) uint32 -> (...,) the k-th largest of each row, exact:
    the largest v with at least k keys >= v, built bit by bit from the top
    in 32 passes of compare and count; no sort. 0 where a row has fewer
    than k keys."""
    def bit(i, ans):
        cand = ans | jnp.uint32(1 << 31) >> i.astype(jnp.uint32)
        enough = jnp.sum(keys >= cand[..., None], -1, dtype=jnp.int32) >= k
        return jnp.where(enough, cand, ans)

    return lax.fori_loop(0, 32, bit, jnp.zeros(keys.shape[:-1], jnp.uint32))


def _index_scores(qi, ki, w, heads):
    """The indexer's score of every key for every query: qi (B, Tq, heads *
    d) and ki (B, Tk, d) as stored, w (B, Tq, heads) float32 -> (B, Tq, Tk)
    float32 ``sum_j w_j relu(qi_j . ki)``; the products accumulate in
    float32 and the weighted sum over the heads is float32 arithmetic, not
    a product the chip would round."""
    b, tq, _ = qi.shape
    per_head = jnp.einsum("bqjd,bkd->bqjk", qi.reshape(b, tq, heads, -1), ki,
                          preferred_element_type=F32)
    return jnp.sum(jnp.maximum(per_head, 0.0) * w[..., None], axis=2)


def _tiers(t):
    """(block, tiers) of a prompt of t rows: blocks of DSA_QUERY_BLOCK
    queries (t itself where it is shorter or no multiple), in DSA_TIERS
    runs of equal length where each holds at least two blocks."""
    blk = DSA_QUERY_BLOCK if t % DSA_QUERY_BLOCK == 0 else t
    tiers = DSA_TIERS if t % (2 * DSA_TIERS * blk) == 0 else 1
    return blk, tiers


def _over_query_blocks(one, t, per_query, per_key):
    """``one(rows (blk,) int32, *blocks, *keys)`` over a prompt's queries, a
    block a trip, tier by tier. ``per_query`` arrays (B, T, ...) are handed
    over a block at a time (B, blk, ...); ``per_key`` pairs (array, axis)
    are cut to the tier's keys ``[0, tk)`` along ``axis`` ONCE a tier,
    outside its loop (a slice inside the body is copied every trip: 0.5 GB
    of keys a block at 16,384). -> the tiers' results (B, queries of the
    tier, ...), in order."""
    blk, tiers = _tiers(t)
    nq = t // tiers
    outs = []
    for j in range(tiers):
        tk = (j + 1) * nq
        rows = (j * nq + jnp.arange(nq, dtype=jnp.int32)).reshape(-1, blk)
        parts = [jnp.moveaxis(
            a[:, j * nq:tk].reshape((a.shape[0], nq // blk, blk)
                                    + a.shape[2:]), 1, 0) for a in per_query]
        keys = [lax.slice_in_dim(a, 0, tk, axis=axis) for a, axis in per_key]
        out = lax.map(lambda args, keys=keys: one(*args, *keys),
                      (rows, *parts))
        out = jnp.moveaxis(out, 0, 1)
        outs.append(out.reshape((out.shape[0], nq) + out.shape[3:]))
    return outs


@register_op("dsa_select")
def _dsa_select(ctx, ins, attrs):
    """The learned selection of keys of a sparse-attention layer (DeepSeek
    sparse attention's lightning indexer): Q (B, Tq, heads * d) the
    indexer's queries, K (B, Tk, d) its keys (one for all heads), both
    already turned by their positions, W (B, Tq, heads) float32 the heads'
    weights; query t scores key s ``I[t, s] = sum_j W[t, j] relu(Q[t, j] .
    K[s])`` and keeps the ``topk`` visible keys of largest score, all of
    them while it sees no more than that. Exact: no approximate top-k.

    Without ``Pos`` a prompt (Tq == Tk, key s visible to query t iff s <=
    t): Selected (B, T, T) int8, 1 where query t keeps key s. The row's
    threshold is the ``topk``-th largest score (:func:`_kth_largest`, 32
    counting passes, no sort and no gather), over blocks of queries so that
    the scores alive are (heads, DSA_QUERY_BLOCK, keys), and in DSA_TIERS
    runs of queries each against the keys up to its own end.

    With ``Pos`` (B, 1) a decode step (Tq == 1) over the slots' indexer
    rows K (B, cache_len, d), row b seeing columns <= pos[b]: Selected (B,
    min(topk, cache_len)) int32, the kept columns in the order of their
    scores, -1 where the slot has fewer (``lax.top_k``: exact)."""
    q, k, w = ins["Q"][0], ins["K"][0], ins["W"][0].astype(F32)
    heads, topk = int(attrs["heads"]), int(attrs["topk"])
    b, tq, _ = q.shape
    tk = k.shape[1]
    if ins.get("Pos"):
        at = jnp.arange(tk, dtype=jnp.int32)[None, :]
        seen = at <= ins["Pos"][0].reshape(b, 1).astype(jnp.int32)
        scores = jnp.where(seen, _index_scores(q, k, w, heads)[:, 0],
                           -jnp.inf)
        best, idx = lax.top_k(scores, min(topk, tk))
        return {"Selected": [jnp.where(best > -jnp.inf, idx, -1)
                             .astype(jnp.int32)]}

    def one(rows, qb, wb, kb):
        tkeys = kb.shape[1]
        seen = jnp.arange(tkeys, dtype=jnp.int32)[None, :] <= rows[:, None]
        scores = jnp.where(seen, _index_scores(qb, kb, wb, heads), -jnp.inf)
        keys = _ordered_bits(scores)
        kept = (keys >= _kth_largest(keys, topk)[..., None]) & seen
        return jnp.pad(kept.astype(jnp.int8),
                       ((0, 0), (0, 0), (0, tk - tkeys)))

    return {"Selected": [jnp.concatenate(
        _over_query_blocks(one, tq, (q, w), [(k, 1)]), axis=1)]}


def _mla_causal(ctx, q, lat, wuk, wuv, heads, nope, rope, vd, offset):
    """Latent attention with no selection, a prompt or a chunk of one:
    expanded. Query i stands at row ``offset + i`` (row i without an offset)
    of the latent rows ``lat`` (B, Tk, .) and sees every row up to its own.
    Keys ``[ckv Wuk_h | k_rope]`` and values ``ckv Wuv_h`` are made for all
    Tk rows. On an unsharded TPU program whose lengths FLASH_BLOCK divides,
    the flash forward kernel with a query offset (``pallas_attention.
    flash_attention(q_offset=)``): queries and keys padded with zeros to a
    whole number of 128 lanes (192 -> 256: the MXU contracts 128 at a time,
    so the zeros cost fast memory and no pass), the values at their own
    width; everywhere else blocks of DSA_QUERY_BLOCK queries through XLA
    against all Tk keys. Counted: ``ops.mla_attention.causal_kernel`` /
    ``.causal_blocks``."""
    from .. import observability as obs

    b, tq, _ = q.shape
    tk, rank = lat.shape[1], wuk.shape[0]
    dqk, scale = nope + rope, (nope + rope) ** -0.5
    ckv, k_rope = lat[..., :rank], lat[..., rank:rank + rope]
    k_nope = _dot_f32(ckv, wuk).astype(q.dtype).reshape(b, tk, heads, nope)
    keys = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, tk, heads, rope))], -1)
    values = _dot_f32(ckv, wuv).astype(q.dtype).reshape(b, tk, heads, vd)
    qh = q.reshape(b, tq, heads, dqk)
    if (getattr(ctx, "platform", None) == "tpu"
            and not getattr(ctx, "mesh_axes", None)
            and tq >= FLASH_MIN_SEQ and tq % FLASH_BLOCK == 0
            and tk % FLASH_BLOCK == 0 and vd % 128 == 0):
        from .pallas_attention import flash_attention

        obs.inc("ops.mla_attention.causal_kernel")
        pad = (-dqk) % 128
        if pad:
            qh = jnp.pad(qh, ((0, 0), (0, 0), (0, 0), (0, pad)))
            keys = jnp.pad(keys, ((0, 0), (0, 0), (0, 0), (0, pad)))
        out = flash_attention(
            jnp.swapaxes(qh, 1, 2), jnp.swapaxes(keys, 1, 2),
            jnp.swapaxes(values, 1, 2), causal=True, sm_scale=scale,
            block_q=FLASH_BLOCK, block_k=FLASH_BLOCK,
            q_offset=jnp.zeros((), jnp.int32) if offset is None else offset)
        return jnp.swapaxes(out, 1, 2).reshape(b, tq, heads * vd)
    obs.inc("ops.mla_attention.causal_blocks")
    blk = DSA_QUERY_BLOCK if tq % DSA_QUERY_BLOCK == 0 else tq
    first = jnp.zeros((), jnp.int32) if offset is None else offset
    col = jnp.arange(tk, dtype=jnp.int32)[None, :]

    def one(args):
        rows, qb = args                              # (blk,), (B, blk, h, d)
        scores = jnp.einsum("bqhd,bkhd->bhqk", qb, keys,
                            preferred_element_type=F32) * scale
        seen = col <= (first + rows)[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -1e30), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), values,
                          preferred_element_type=F32
                          ).astype(q.dtype).reshape(b, blk, heads * vd)

    out = lax.map(one, (jnp.arange(tq, dtype=jnp.int32).reshape(-1, blk),
                        jnp.moveaxis(qh.reshape(b, -1, blk, heads, dqk),
                                     1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, tq, heads * vd)


@register_op("mla_attention")
def _mla_attention(ctx, ins, attrs):
    """Multi-head latent attention, over a selection of keys or over all of
    them. Q (B, Tq, heads * (nope + rope)) per head ``[q_nope | q_rope]``,
    the second part turned by its position; Latent (B, Tk, >= rank + rope) a
    position's row ``[ckv | k_rope | zeros]`` (the normed latent and the one
    turned key part all heads share, then whatever padding the cache's width
    carries); Wuk (rank, heads * nope) and Wuv (rank, heads * v) expand
    a latent to a head's key and value; scale ``(nope + rope)^-1/2``;
    Selected from ``dsa_select``, or absent: every earlier position is seen.
    Out (B, Tq, heads * v). The paths, the same numbers:

    a prompt over a selection (no ``Pos``; Selected (B, T, T) int8):
    expanded. ``k_nope = ckv Wuk`` and ``v = ckv Wuv`` for every position
    and head, score ``q_nope . k_nope + q_rope . k_rope``, softmax over the
    kept keys, ``sum p v``. On an unsharded TPU program whose length
    KEPT_BLOCK divides and whose head widths are multiples of 128, one
    Pallas kernel (``pallas_attention.kept_keys_attention``: the scores stay
    on the chip, the work is the causal half); everywhere else blocks of
    DSA_QUERY_BLOCK queries x all heads through XLA, run j of DSA_TIERS
    against the keys up to its end, so no (heads, T, T) array exists either
    way. The op chooses from what it sees; which path a lowering took is
    counted (``ops.mla_attention.kept_kernel`` / ``.kept_blocks``).

    a prompt or a chunk of one with no selection (no ``Pos``, no Selected;
    with ``Offset`` (1, 1) the queries stand that many rows into Latent, the
    sequence's rows so far, Tk >= offset + Tq): expanded and plainly causal
    (:func:`_mla_causal`).

    a decode step (``Pos`` given, Tq == 1; Latent the slots' rows (B,
    cache_len, .)): absorbed. ``q~ = q_nope Wuk_h^T`` (rank) scores a row as
    ``q~ . ckv + q_rope . k_rope``, and ``(sum p ckv) Wuv_h`` is the head's
    output: no key or value is expanded. With Selected (B, k) int32 columns,
    -1 for none, the kept rows are gathered and the cache is read at them
    alone; without, the rows are the slot's whole cache where it lies, no
    gather, row b seeing columns <= pos[b] (counted:
    ``ops.mla_attention.dense_step``)."""
    q, lat = ins["Q"][0], ins["Latent"][0]
    wuk, wuv = ins["Wuk"][0], ins["Wuv"][0]
    sel = ins["Selected"][0] if ins.get("Selected") else None
    heads, nope, rope, vd = (int(attrs[k]) for k in
                             ("heads", "nope_dim", "rope_dim", "v_dim"))
    b, tq, _ = q.shape
    rank, width = wuk.shape[0], lat.shape[-1]
    scale = (nope + rope) ** -0.5
    if ins.get("Pos"):
        if sel is None:
            from .. import observability as obs

            obs.inc("ops.mla_attention.dense_step")
            rows = lat                                    # (B, cache, width)
        else:
            rows = jax.vmap(lambda c, i: jnp.take(c, i, axis=0))(
                lat, jnp.maximum(sel, 0))                 # (B, k, width)
        qh = q.reshape(b, heads, nope + rope)
        absorbed = jnp.einsum("bhd,chd->bhc", qh[..., :nope],
                              wuk.reshape(rank, heads, nope),
                              preferred_element_type=F32).astype(q.dtype)
        # the query in the row's own layout, zeros against its padding: the
        # gathered rows are read where they lie, not cut to size first
        ql = jnp.concatenate(
            [absorbed, qh[..., nope:],
             jnp.zeros((b, heads, width - rank - rope), q.dtype)], -1)
        scores = jnp.einsum("bhc,bkc->bhk", ql, rows,
                            preferred_element_type=F32) * scale
        seen = (sel >= 0) if sel is not None else (
            jnp.arange(lat.shape[1], dtype=jnp.int32)[None, :]
            <= ins["Pos"][0].reshape(b, 1).astype(jnp.int32))
        probs = jax.nn.softmax(
            jnp.where(seen[:, None, :], scores, -1e30), -1)
        mixed = jnp.einsum("bhk,bkc->bhc", probs.astype(q.dtype),
                           rows[..., :rank],
                           preferred_element_type=F32).astype(q.dtype)
        out = jnp.einsum("bhc,chv->bhv", mixed, wuv.reshape(rank, heads, vd),
                         preferred_element_type=F32)
        return single(out.reshape(b, 1, heads * vd).astype(q.dtype))
    if sel is None:
        offset = (ins["Offset"][0].reshape(()).astype(jnp.int32)
                  if ins.get("Offset") else None)
        return single(_mla_causal(ctx, q, lat, wuk, wuv, heads, nope, rope,
                                  vd, offset))
    if ins.get("Offset"):
        raise ValueError("mla_attention of a chunk (Offset) takes no "
                         "selection: a selection's prompt is filled whole")

    from .. import observability as obs

    ckv, k_rope = lat[..., :rank], lat[..., rank:rank + rope]
    k_nope = _dot_f32(ckv, wuk).astype(q.dtype).reshape(b, tq, heads, nope)
    keys = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (b, tq, heads, rope))], -1)
    values = _dot_f32(ckv, wuv).astype(q.dtype)
    if (getattr(ctx, "platform", None) == "tpu"
            and not getattr(ctx, "mesh_axes", None)
            and tq % KEPT_BLOCK == 0 and (nope + rope) % 128 == 0
            and vd % 128 == 0):
        from .pallas_attention import kept_keys_attention

        obs.inc("ops.mla_attention.kept_kernel")
        return single(kept_keys_attention(
            q, keys.reshape(b, tq, -1), values, sel, heads, scale,
            block=KEPT_BLOCK))
    obs.inc("ops.mla_attention.kept_blocks")
    kh = jnp.swapaxes(keys, 1, 2)
    vh = jnp.swapaxes(values.reshape(b, tq, heads, vd), 1, 2)

    def one(rows, qb, kept, kb, vb):
        scores = jnp.einsum("bqhd,bhkd->bhqk",
                            qb.reshape(b, -1, heads, nope + rope), kb,
                            preferred_element_type=F32) * scale
        probs = jax.nn.softmax(
            jnp.where(kept[:, None, :, :kb.shape[2]] > 0, scores, -1e30), -1)
        return jnp.einsum("bhqk,bhkd->bqhd", probs.astype(q.dtype), vb,
                          preferred_element_type=F32
                          ).astype(q.dtype).reshape(b, -1, heads * vd)

    return single(jnp.concatenate(
        _over_query_blocks(one, tq, (q, sel), [(kh, 2), (vh, 2)]), axis=1))


@register_op("moe_route_topk")
def _moe_route_topk(ctx, ins, attrs):
    """Top-k routing in float32 over ALL experts: scores ``sigmoid(x
    W_g)`` (``score_func`` "softmax": ``softmax(x W_g)``), the k largest of
    ``score + bias`` chosen (of the scores alone without ``Bias``), their
    scores normalised over the k chosen (``norm_eps`` added to their sum)
    and scaled. -> Index (T, k) int32, Weight (T, k) float32. Operands
    stored in float32 (a trained router's master weights) are multiplied
    at full precision: the TPU's default would round them to bfloat16.
    The weights carry the gradient to ``Gate`` and to ``X`` (with
    ``detach_input`` to ``Gate`` alone); the choice carries none.

    With ``bias_update_rate`` > 0 the op also runs the auxiliary-loss-free
    balancing rule on its score correction (Wang et al. 2024,
    arXiv:2408.15664): ``BiasOut`` = ``Bias`` raised by the rate for every
    expert that got fewer than the even share ``T k / experts`` of this
    call's assignments and lowered by it for every one that got more. The
    layer binds it to the ``Bias`` variable itself, so the next step
    chooses with it; this step's choice used the old one."""
    x, gate = ins["X"][0], ins["Gate"][0]
    if attrs.get("detach_input"):
        x = lax.stop_gradient(x)
    full = x.dtype == F32 and gate.dtype == F32
    logits = _dot_f32(x, gate, lax.Precision.HIGHEST if full else None)
    if attrs.get("score_func", "sigmoid") == "softmax":
        s = jax.nn.softmax(logits, -1)
    else:
        s = jax.nn.sigmoid(logits)
    chosen_by = s + ins["Bias"][0].astype(F32) if ins.get("Bias") else s
    _, idx = lax.top_k(chosen_by, int(attrs["k"]))
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True)
             + float(attrs.get("norm_eps", 1e-20)))
    out = {"Index": [idx.astype(jnp.int32)],
           "Weight": [w * float(attrs.get("scale", 1.0))]}
    rate = float(attrs.get("bias_update_rate", 0.0))
    if rate:
        bias = ins["Bias"][0]
        # one comparison a (assignment, expert) pair: no scatter of 65,536
        # rows into 32 counters
        got = jnp.sum(idx.reshape(-1, 1) == jnp.arange(bias.shape[0]),
                      axis=0).astype(F32)
        even = idx.size / float(bias.shape[0])
        out["BiasOut"] = [(bias.astype(F32) + rate * jnp.sign(even - got))
                          .astype(bias.dtype)]
    return out


GMM_ROWS = 128          # row tile where an expert sees a handful of rows
GMM_TILE_ELEMENTS = 3 << 20   # one expert's matrix held whole in fast memory
GMM_TRAIN_ROWS = 256    # row tile where an expert sees hundreds of rows
GMM_TRAIN_WIDTH = 1024  # widest tile of the output's columns there
GMM_TRAIN_DEPTH = 2048  # widest tile of the contracted width there


def _width_tile(d, cap):
    """The largest multiple of 128 that divides ``d`` and is at most
    ``cap``."""
    return max(t for t in range(128, min(d, cap) + 1, 128) if d % t == 0)


def gmm_tiling(m, k, n, groups):
    """(rows, k, n) of one tile of the grouped kernel for ``m`` sorted rows
    over ``groups`` matrices of (k, n): the tile follows the shape. With a
    handful of rows a group (a decode step, a prompt) the kernel is bound
    by reading the matrices: 128 rows and one expert's whole matrix a
    tile, so each touched matrix streams once, as long as it fits
    (GMM_TILE_ELEMENTS). With hundreds of rows a group (a training step
    puts about 2,048 on each) it is bound by arithmetic: 256 rows (a
    taller tile is revisited across more group boundaries), the contracted
    width whole where it is at most GMM_TRAIN_DEPTH (one pass, no
    accumulator carried between tiles: 0.76 ms against 1.03 at half of it,
    PERF.md), output columns of at most GMM_TRAIN_WIDTH, so that a tile's
    operands and its float32 accumulator fit fast memory whatever the
    matrix. Widths that are no multiple of 128 are refused, not sent down
    a slower kernel."""
    if k % 128 or n % 128:
        raise ValueError(
            "grouped_dot on the TPU tiles both widths of an expert's "
            "(%d, %d) matrix by multiples of 128" % (k, n))
    many = m >= GMM_TRAIN_ROWS * 2 * groups
    if not many and k * n <= GMM_TILE_ELEMENTS:
        return GMM_ROWS, k, n
    return (GMM_TRAIN_ROWS if many else GMM_ROWS,
            _width_tile(k, GMM_TRAIN_DEPTH), _width_tile(n, GMM_TRAIN_WIDTH))


def _pad_rows(x, multiple):
    pad = (-x.shape[0]) % multiple
    return jnp.pad(x, ((0, pad), (0, 0))) if pad else x


def _megablox():
    """(the package, its kernel module): looked up when a program is
    traced, so a test can hand both an interpreted kernel."""
    import importlib

    name = "jax.experimental.pallas.ops.tpu.megablox"
    return importlib.import_module(name), importlib.import_module(
        name + ".gmm")


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(xs, w, sizes, out_dtype):
    return _gmm_fwd(xs, w, sizes, out_dtype)[0]


def _gmm_fwd(xs, w, sizes, out_dtype):
    m, k = xs.shape
    tiles = gmm_tiling(m, k, w.shape[2], w.shape[0])
    out = _megablox()[0].gmm(_pad_rows(xs, tiles[0]), w, sizes, out_dtype,
                             tiles)[:m]
    return out, (xs, w, sizes)


def _gmm_bwd(out_dtype, res, g):
    """The two products of the backward pass, each tiled for its own
    shape: ``dX = g W^T`` by the same kernel with the matrices read
    transposed, ``dW_e = X_e^T g_e`` by its transposed sibling (``tgmm``
    in a device trace), which zeroes an expert no row chose."""
    kernels = _megablox()[1]
    xs, w, sizes = res
    m, k = xs.shape
    groups, _, n = w.shape
    g = g.astype(xs.dtype)
    rows, tn, tk = gmm_tiling(m, n, k, groups)        # contracts over n
    dxs = kernels.gmm(_pad_rows(g, rows), w, sizes, xs.dtype, (rows, tn, tk),
                      transpose_rhs=True)[:m]
    # tgmm's accumulator is a (k, n) tile: both at most GMM_TRAIN_WIDTH
    tiles = (rows, _width_tile(k, GMM_TRAIN_WIDTH),
             _width_tile(n, GMM_TRAIN_WIDTH))
    dw = kernels.tgmm(_pad_rows(xs, rows).swapaxes(0, 1), _pad_rows(g, rows),
                      sizes, w.dtype, tiles)
    return dxs, dw, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_dot(xs, w, sizes, platform=None, out_dtype=F32):
    """Rows sorted by group times their group's matrix: xs (m, k), w
    (groups, k, n), sizes (groups,) -> ``out_dtype`` (m, n), accumulated
    in float32; rows past ``sum(sizes)`` come back undefined.

    On the TPU this is the Pallas grouped matrix product that ships with
    jax (``pallas.ops.tpu.megablox``, the kernels ``gmm`` and, for the
    weight gradient, ``tgmm`` in a device trace), tiled by
    :func:`gmm_tiling`: with a handful of rows per expert it streams every
    touched expert's weights once, where the compiler's own ragged-dot
    kernel spends a 256- or 512-row tile of arithmetic on every expert
    (2.5-2.8 x its time at these sizes, PERF.md). Off the TPU,
    ``lax.ragged_dot``. Differentiable either way."""
    if platform != "tpu":
        return lax.ragged_dot(xs, w, sizes,
                              preferred_element_type=F32).astype(out_dtype)
    return _gmm(xs, w, sizes, jnp.dtype(out_dtype))


def _sort_by_held_expert(idx, first, held_n, live):
    """The (T, k) assignments in the order the grouped product wants them:
    those that land on the held experts [first, first + held_n) sorted by
    expert, all others after them. -> (order, sizes (held_n,), here
    (T*k,) bool before the sort)."""
    k = idx.shape[1]
    e = idx.reshape(-1) - jnp.int32(first)
    here = (e >= 0) & (e < held_n)
    if live is not None:
        here = here & jnp.repeat(live.reshape(-1).astype(bool), k)
    key = jnp.where(here, e, held_n)                       # elsewhere: last
    order = jnp.argsort(key)                               # stable
    sizes = jnp.zeros((held_n + 1,), jnp.int32).at[key].add(1)[:held_n]
    return order, sizes, here


def _counts(here, sizes):
    return jnp.stack([jnp.sum(here.astype(jnp.int32)), jnp.max(sizes),
                      jnp.sum((sizes > 0).astype(jnp.int32))]).astype(
                          jnp.int32)


GATED_CHUNK_ROWS = 2048    # sorted rows a trip of the gated experts' loops


def _chunk(v, c, r):
    """Rows [c r, (c + 1) r) of v."""
    return lax.dynamic_slice_in_dim(v, c * r, r, axis=0)


def _put(buf, c, r, rows):
    """buf with its rows [c r, (c + 1) r) replaced: in place, in a loop's
    carry."""
    return lax.dynamic_update_slice_in_dim(buf, rows, c * r, axis=0)


def _live(c, r, n):
    """(r, 1) bool: the rows of chunk c that are held rows."""
    return (c * r + lax.iota(jnp.int32, r) < n)[:, None]


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def gated_experts_sum(x, idx, wt, w1, w3, w2, first, platform=None):
    """sum over a token's chosen experts that lie in [first, first + held)
    of ``wt * W2_e (silu(W1_e x) * W3_e x)`` (SwiGLU experts); experts
    elsewhere add nothing. Sorted and grouped as :func:`held_experts_sum`,
    and differentiable in ``x``, the three matrices and ``wt`` (through
    which the router learns). The sort lists the n assignments that landed
    here first, and between the sort and the sum back nothing outside the
    grouped kernels touches a sorted row past them: the gather of the
    tokens' rows and the gate are loops over chunks of GATED_CHUNK_ROWS
    sorted rows whose trip count ``ceil(n / rows)`` the device computes,
    and so are their gradients (a hand-written backward pass of the same
    trips). ONE pass reads the static bound instead: the weighted sum back
    into the tokens (and its mirror, ``d x``) goes token by token through
    the sort's inverse (:func:`_sum_into_tokens`), a read for each of a
    token's k choices, held or not, and one write of the token's row. That
    is tokens x k reads where a scatter-add over the held rows makes n
    read-modify-writes, and it wins although n is a quarter of the bound
    in the cells that run it, because the chip reads a row several times
    faster than it adds one into a row it must read and write back, one
    row after another (PERF.md, PR 36). The buffers keep the static bound
    of tokens x experts per token, so any load is exact: nothing has a
    capacity. The hidden activations and each expert's output are kept in
    ``x``'s dtype. -> (out (T, D) float32, counts as
    :func:`held_experts_sum` and fourth the sorted rows the loops over the
    held rows covered, trips x GATED_CHUNK_ROWS)."""
    return _gated_fwd(x, idx, wt, w1, w3, w2, first, platform)[0]


def _gather_rows(x, tok, trips, r, into):
    """``into`` with its first trips x r rows replaced by the rows of x
    that ``tok`` names."""
    return lax.fori_loop(
        0, trips, lambda c, xs: _put(xs, c, r, jnp.take(
            x, _chunk(tok, c, r), axis=0).astype(xs.dtype)), into)


def _gate(a, b, trips, r, weight=None):
    """``silu(a) * b`` (times each row's weight) over the first trips x r
    rows, in float32, rounded to a's dtype."""
    def body(c, hid):
        h = (jax.nn.silu(_chunk(a, c, r).astype(F32))
             * _chunk(b, c, r).astype(F32))
        if weight is not None:
            h = h * _chunk(weight, c, r)[:, None]
        return _put(hid, c, r, h.astype(hid.dtype))

    return lax.fori_loop(0, trips, body, lax.empty(a.shape, a.dtype))


def _sum_into_tokens(rows, inv, here, weight=None):
    """(T, D) float32: for each token the sum over its k choices of the
    sorted row of ``rows`` that ``inv`` (T, k) names (times the choice's
    ``weight``); a choice not ``here`` is left out by a select, since its
    row is undefined. Reads only, and each token's row is written once: a
    trip takes a quarter of GATED_CHUNK_ROWS tokens and gathers that many
    rows a choice (512 at a time read fastest at both cells' shapes, one
    gather of all k x 512 slowest: PERF.md, PR 36); the last chunk is
    moved back to end on the last token."""
    t, k = inv.shape
    inv = jnp.where(here, inv, 0)
    tc = min(t, GATED_CHUNK_ROWS // 4)

    def body(c, out):
        at = jnp.minimum(c * tc, t - tc)
        p, h, w = (v if v is None else lax.dynamic_slice_in_dim(v, at, tc)
                   for v in (inv, here, weight))
        acc = jnp.zeros((tc, rows.shape[1]), F32)
        for j in range(k):
            term = jnp.take(rows, p[:, j], axis=0).astype(F32)
            if w is not None:
                term = term * w[:, j, None]
            acc = acc + jnp.where(h[:, j, None], term, 0.0)
        return lax.dynamic_update_slice_in_dim(out, acc, at, 0)

    return lax.fori_loop(0, -(-t // tc), body,
                         lax.empty((t, rows.shape[1]), F32))


def _gated_fwd(x, idx, wt, w1, w3, w2, first, platform):
    t, k = idx.shape
    order, sizes, here = _sort_by_held_expert(idx, first, w1.shape[0], None)
    r, n = min(GATED_CHUNK_ROWS, t * k), jnp.sum(sizes)
    trips = (n + (r - 1)) // r
    pad = (-t * k) % r                      # buffers hold whole chunks
    tok = jnp.pad(order // k, (0, pad))     # a sorted row's token
    wts = jnp.pad(jnp.take(wt.reshape(-1), order), (0, pad))
    dot = functools.partial(grouped_dot, sizes=sizes, platform=platform,
                            out_dtype=x.dtype)
    xs = _gather_rows(x, tok, trips, r,
                      lax.empty((t * k + pad, x.shape[1]), x.dtype))
    a, b = dot(xs, w1), dot(xs, w3)
    outs = dot(_gate(a, b, trips, r), w2)
    inv, here = jnp.argsort(order).reshape(t, k), here.reshape(t, k)
    out = _sum_into_tokens(outs, inv, here, wt)
    counts = jnp.concatenate([_counts(here, sizes), (trips * r)[None]])
    return (out, counts), (x, inv, here, sizes, n, trips, tok, wts, a, b,
                           w1, w3, w2)


def _gated_bwd(first, platform, res, cts):
    """The forward pass's loops mirrored, over the trips it made. Kept from
    it are the two products under the gate alone: the gathered rows and the
    gate are made again (a quarter of a pass each, where keeping them is
    0.5 GB an expert layer), and the experts' outputs are not needed: the
    weight on a row goes into the gate's side of the last product, so that
    ``d wt = <g W2^T, hid>`` falls out of the gate's own loop. Loops write
    over a buffer of their shape that is dead by then where there is one.
    Rows of the last chunk past the held ones are undefined on the
    kernels' side: masked wherever they would be summed."""
    x, inv, here, sizes, n, trips, tok, wts, a, b, w1, w3, w2 = res
    g = cts[0]
    r = min(GATED_CHUNK_ROWS, inv.size)

    def pull(v, w, ct):
        """(d v, d w) of ``grouped_dot(v, w)``: on the TPU its own backward
        rule called as the rule, so that a device trace names the kernels
        `gmm` and `tgmm` as in every other program."""
        if platform == "tpu":
            return _gmm_bwd(v.dtype, (v, w, sizes), ct)[:2]
        return jax.vjp(lambda v_, w_: grouped_dot(
            v_, w_, sizes, platform, v.dtype), v, w)[1](ct)

    gs = _gather_rows(g, tok, trips, r,
                      lax.empty((tok.shape[0], g.shape[1]), x.dtype))
    dhid, dw2 = pull(_gate(a, b, trips, r, wts), w2, gs)
    # gs is written over below: only once both products have read it
    gs, dhid, dw2 = lax.optimization_barrier((gs, dhid, dw2))

    def gate(c, carry):
        da, db, dwt = carry                 # a and b so far
        av, bv, dh = (_chunk(v, c, r).astype(F32) for v in (da, db, dhid))
        sig, live = jax.nn.sigmoid(av), _live(c, r, n)
        dot = jnp.sum(dh * (av * sig * bv), -1, keepdims=True)
        dh = dh * _chunk(wts, c, r)[:, None]
        d_silu = sig * (1.0 + av * (1.0 - sig))
        return (_put(da, c, r, jnp.where(live, dh * bv * d_silu, 0.0)
                     .astype(da.dtype)),
                _put(db, c, r, jnp.where(live, dh * av * sig, 0.0)
                     .astype(db.dtype)),
                _put(dwt, c, r, jnp.where(live, dot, 0.0)[:, 0]))

    da, db, dwt = lax.fori_loop(0, trips, gate, (a, b, jnp.zeros_like(wts)))
    xs = _gather_rows(x, tok, trips, r, gs)
    (dxa, dw1), (dxb, dw3) = pull(xs, w1, da), pull(xs, w3, db)
    # one read a choice, not two: the two products' rows added first, over
    # the held rows, into the first's buffer (one more rounding to x's
    # dtype of what both kernels had just rounded to it)
    dxs = lax.fori_loop(0, trips, lambda c, s: _put(s, c, r, (
        _chunk(s, c, r).astype(F32) + _chunk(dxb, c, r).astype(F32)
    ).astype(s.dtype)), dxa)
    dx = _sum_into_tokens(dxs, inv, here)
    dwt = jnp.take(dwt, inv)
    return dx.astype(x.dtype), None, dwt, dw1, dw3, dw2


gated_experts_sum.defvjp(_gated_fwd, _gated_bwd)


def held_experts_sum(x, idx, wt, w1, w2, first, live=None, platform=None):
    """sum over a token's chosen experts that lie in [first, first + held)
    of ``wt * W2_e relu(W1_e x)^2``; experts elsewhere add nothing. The
    assignments are sorted by expert and the two products are grouped
    (:func:`grouped_dot`), so the work follows the assignments that land
    here, not tokens x experts held; nothing has a capacity and no token
    is dropped. -> (out (T, D) float32, counts): counts is int32
    ``[assignments held, largest count on one held expert, held experts
    that got any]``."""
    t, k = idx.shape
    order, sizes, here = _sort_by_held_expert(idx, first, w1.shape[0], live)
    xs = jnp.take(x, order // k, axis=0)                   # (T*k, D)
    hid = grouped_dot(xs, w1, sizes, platform)
    hid = jnp.square(jnp.maximum(hid, 0)).astype(x.dtype)
    out = grouped_dot(hid, w2, sizes, platform)
    # rows past the held assignments belong to no group: keep none of them
    keep = jnp.take(here, order)
    out = jnp.where(keep[:, None],
                    out * jnp.take(wt.reshape(-1), order)[:, None], 0.0)
    back = jnp.argsort(order)                              # undo the sort
    out = jnp.take(out, back, axis=0).reshape(t, k, -1).sum(1)
    return out, _counts(here, sizes)


@register_op("held_experts_ffn")
def _held_experts_ffn(ctx, ins, attrs):
    """A chip's share of a routed expert layer. X (T, D), Index/Weight
    (T, k) from the router over all experts, W1 (held, D, F), W2 (held, F,
    D): squared-ReLU experts (:func:`held_experts_sum`); with ``W3``
    (held, D, F) gated ones, ``W2 (silu(W1 x) * W3 x)``
    (:func:`gated_experts_sum`), which train. ``Live`` (T, 1) masks rows
    that carry no token (a dead decode slot, a prompt's padding). Counts is
    int32 ``[assignments held, largest count on one held expert, held
    experts that got any]``, gated also the sorted rows the loops
    covered."""
    x = ins["X"][0]
    live = ins["Live"][0] if ins.get("Live") else None
    platform = getattr(ctx, "platform", None)
    first = int(attrs["first_expert"])
    if ins.get("W3"):
        idx = ins["Index"][0]
        if live is not None:
            # a row that carries no token chooses no expert: -1 lies in no
            # held range (a served program; nothing differentiates it)
            idx = jnp.where(live.reshape(-1, 1).astype(bool), idx, -1)
        out, counts = gated_experts_sum(
            x, idx, ins["Weight"][0], ins["W1"][0],
            ins["W3"][0], ins["W2"][0], first, platform)
    else:
        out, counts = held_experts_sum(
            x, ins["Index"][0], ins["Weight"][0], ins["W1"][0],
            ins["W2"][0], first, live, platform)
    return {"Out": [out.astype(x.dtype)], "Counts": [counts]}
