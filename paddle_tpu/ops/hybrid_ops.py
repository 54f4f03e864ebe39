"""Lowerings for hybrid state-space / attention / sparse-expert decoders
(Mamba-2 + grouped-query attention + latent mixture of experts).

Precision contract shared by every lowering here: matrix products take
their operands as stored (bfloat16 in a served model) and accumulate in
float32; norms, the router, ``dt``, ``exp(dt A)`` and the state-space state
are float32; what goes back onto the residual stream is rounded to the
input's dtype.
"""
import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single

F32 = jnp.float32


def _dot_f32(x, w):
    """x (..., K) @ w (K, N) -> float32 (..., N), operands as stored."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=F32)


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


@register_op("gqa_attention")
def _gqa_attention(ctx, ins, attrs):
    """Softmax attention with fewer key/value heads than query heads; no
    position term. Q (B, Tq, heads * dh), K/V (B, Tk, kv_heads * dh).
    With ``Pos`` (B, 1) the keys are a slot cache and row b sees
    positions <= pos[b]; without it Tq == Tk and the mask is causal."""
    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    nh, nkv = int(attrs["heads"]), int(attrs["kv_heads"])
    b, tq, _ = q.shape
    tk = k.shape[1]
    dh = q.shape[-1] // nh
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
        seen = (at[None, :] <= at[:, None])[None, None, None]
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
    ctxv = jnp.einsum("bgrqk,bkgd->bqgrd", probs.astype(v.dtype), vg,
                      preferred_element_type=F32)
    return single(ctxv.reshape(b, tq, nh * dh).astype(q.dtype))


@register_op("moe_route_topk")
def _moe_route_topk(ctx, ins, attrs):
    """Sigmoid top-k routing in float32 over ALL experts: scores
    ``sigmoid(x W_g)``, the k largest of ``score + bias`` chosen, their
    scores normalised over the k chosen and scaled. -> Index (T, k) int32,
    Weight (T, k) float32."""
    s = jax.nn.sigmoid(_dot_f32(ins["X"][0], ins["Gate"][0]))
    _, idx = lax.top_k(s + ins["Bias"][0].astype(F32), int(attrs["k"]))
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    return {"Index": [idx.astype(jnp.int32)],
            "Weight": [w * float(attrs.get("scale", 1.0))]}


GMM_ROWS = 128          # row tile of the grouped kernel
GMM_TILE_ELEMENTS = 3 << 20   # one expert's matrix held whole in fast memory


def grouped_dot(xs, w, sizes, platform=None):
    """Rows sorted by group times their group's matrix: xs (m, k), w
    (groups, k, n), sizes (groups,) -> float32 (m, n); rows past
    ``sum(sizes)`` come back undefined.

    On the TPU this is the Pallas grouped matrix product that ships with
    jax (``pallas.ops.tpu.megablox.gmm``, the kernel ``gmm`` in a device
    trace) with 128-row tiles and one expert's whole matrix per tile: with a
    handful of rows per expert it streams every touched expert's weights
    once, where the compiler's own ragged-dot kernel spends a 256- or
    512-row tile of arithmetic on every expert (2.5-2.8 x its time at these
    sizes, PERF.md). Widths that tile cannot take are refused there, not
    sent down the slower kernel. Off the TPU, ``lax.ragged_dot``."""
    if platform != "tpu":
        return lax.ragged_dot(xs, w, sizes, preferred_element_type=F32)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    m, k = xs.shape
    n = w.shape[2]
    if k % 128 or n % 128 or k * n > GMM_TILE_ELEMENTS:
        raise ValueError(
            "grouped_dot on the TPU holds one expert's (%d, %d) matrix "
            "whole in fast memory: both widths must be multiples of 128 "
            "and their product at most %d" % (k, n, GMM_TILE_ELEMENTS))
    pad = (-m) % GMM_ROWS
    if pad:
        xs = jnp.pad(xs, ((0, pad), (0, 0)))
    return gmm(xs, w, sizes, F32, (GMM_ROWS, k, n))[:m]


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
    held_n = w1.shape[0]
    e = idx.reshape(-1) - jnp.int32(first)
    here = (e >= 0) & (e < held_n)
    if live is not None:
        here = here & jnp.repeat(live.reshape(-1).astype(bool), k)
    key = jnp.where(here, e, held_n)                       # elsewhere: last
    order = jnp.argsort(key)                               # stable
    sizes = jnp.zeros((held_n + 1,), jnp.int32).at[key].add(1)[:held_n]
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
    counts = jnp.stack([jnp.sum(here.astype(jnp.int32)), jnp.max(sizes),
                        jnp.sum((sizes > 0).astype(jnp.int32))])
    return out, counts.astype(jnp.int32)


@register_op("held_experts_ffn")
def _held_experts_ffn(ctx, ins, attrs):
    """A chip's share of a routed expert layer (see
    :func:`held_experts_sum`). X (T, D), Index/Weight (T, k) from the
    router over all experts, W1 (held, D, F), W2 (held, F, D); ``Live``
    (T, 1) masks rows that carry no token. Counts is int32
    ``[assignments held, largest count on one held expert, held experts
    that got any]``."""
    x = ins["X"][0]
    live = ins["Live"][0] if ins.get("Live") else None
    out, counts = held_experts_sum(
        x, ins["Index"][0], ins["Weight"][0], ins["W1"][0], ins["W2"][0],
        int(attrs["first_expert"]), live,
        platform=getattr(ctx, "platform", None))
    return {"Out": [out.astype(x.dtype)], "Counts": [counts]}
