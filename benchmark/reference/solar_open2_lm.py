"""Plain reference of the served Solar-Open2 decoder (`solar_open2`: one
softmax grouped-query attention layer without a position term, under an
elementwise gate, then three gated delta-rule linear-attention layers with a
decay a channel; every layer with sigmoid-routed SwiGLU experts plus a shared
expert): one full causal forward pass over prompt + served tokens in float32
at matmul precision "highest", no cache, no state handed over, no chunks, no
kernels: the delta rule position by position (a `lax.scan` over tokens), the
experts a loop over the held range, the softmax attention a loop over blocks
of queries (so that 16,896 positions fit: the scores alive are heads x
QUERY_BLOCK x T). Imports nothing of paddle_tpu.

Layer l over the stream x (T, 4096), u = RMSNorm(x), eps 1e-5:
  a softmax layer (l in `gqa_layers`): q = u Wq (64 heads x 128), k, v = u
  Wk, u Wv (8 x 128), no position term, o_h = softmax(q_h k_g^T / sqrt(128)
  + causal mask) v_g with g = h // 8; y = x + (concat_h o_h * sigmoid(u Wg))
  Wo, the gate elementwise over all 8,192;
  a delta-rule layer (the `gqa_interval` layers after it; Kimi Delta
  Attention, arXiv:2510.26692, as fla's KimiDeltaAttention computes it):
  q = l2norm(silu(conv4(u Wq))) / sqrt(128), k = l2norm(silu(conv4(u Wk))),
  v = silu(conv4(u Wv)), depthwise causal convolutions of 4 taps without
  bias, the L2 norm a head of 128 with eps 1e-6; g_t = -exp(A_log[h]) *
  softplus((u Wfa) Wfb + dt_bias) a channel; beta_t = 2 sigmoid(u Wb) a
  head; S (128 x 128 a head, keys down, values across) from zeros:
  S' = diag(exp(g_t)) S_{t-1}, S_t = S' + beta_t k_t (v_t - S'^T k_t)^T,
  o_t = S_t^T q_t; y = x + (RMSNorm_head(o_t) * sigmoid((u Wga) Wgb)) Wo,
  the norm over each head's 128 with one learned gain of 128;
  then w = RMSNorm(y) and every layer adds, over the 8 experts of largest
  sigmoid(w Wr) + bias that are held here, s_e / (sum_chosen s + 1e-20) x
  expert_e(w), plus the shared expert.
Final RMSNorm, untied head over the held rows.

Departures from the published description are the configuration file's
`assumed`: the ranks of the decay's and the gate's low-rank pairs (128, the
head size), the eps of the L2 norm, the gate of the softmax layer elementwise,
no QK norm there, sigmoid scores with a correction used for the choice only,
the shared expert ungated.

The seeded weights are made on the device, leaf by leaf, and kept as the
values the system holds (bfloat16; `A_log`, `dt_bias` and the router's score
correction float32); a layer's weights are upcast when the layer runs, so the
whole model never exists in float32. `A_log` is drawn so that exp(A_log) lies
log-uniformly in [1, 16] a head and `dt_bias` so that softplus(dt_bias) lies
log-uniformly in [0.001, 0.3] a channel: g ranges from -0.001 to -4.8 a
position before the input's own term, strong and weak decays side by side in
one head. The convolutions' taps are normal(0, 0.3) (a depthwise convolution
of 4 taps starts near 1 / sqrt(4), not near `initializer_range`).

`m` is the configuration file's published keys plus `linear_attn_config`,
`gqa_layers` (the softmax layers of the cut), `router_experts` (the router's
width) and `first_expert` (where the held range starts); `n_routed_experts`
is the number held. `m["fault"]`, if there, names ONE planted departure (the
controls of the adapter's `check`): "beta_not_doubled", "decay_head_mean",
"alpha_one", "conv_window_shifted", "k_norm_dropped", "held_shifted",
"gqa_gate_dropped".
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .glm5_lm import _freeze, route, routed_errors, routed_gap  # noqa: F401
from .laguna_lm import rms_gap, rms_norm, swiglu, token_gaps  # noqa: F401

BF16, F32 = jnp.bfloat16, jnp.float32
QUERY_BLOCK = 128


def layer_kinds(m):
    """("gqa" | "kda") of every layer of the cut: each softmax layer and the
    `gqa_interval` delta-rule layers after it."""
    return [kind for _ in m["gqa_layers"]
            for kind in ["gqa"] + ["kda"] * m["gqa_interval"]]


def kda_sizes(m):
    """-> (heads, head size, rank of the decay's and the gate's pairs)."""
    lin = m["linear_attn_config"]
    return lin["num_heads"], lin["head_dim"], m.get("kda_rank",
                                                    lin["head_dim"])


def weight_shapes(m):
    """{name: (shape, dtype, how it is initialised)}."""
    h, dh = m["hidden_size"], m["head_dim"]
    qw, kvw = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    lh, ld, rank = kda_sizes(m)
    lw, taps = lh * ld, m["linear_attn_config"]["short_conv_kernel_size"]
    f, held = m["moe_intermediate_size"], m["n_routed_experts"]
    out = {"so.emb": ((m["vocab_size"], h), BF16, "normal"),
           "so.head.w": ((h, m["vocab_size"]), BF16, "normal"),
           "so.norm_f.w": ((h,), BF16, "gain")}
    for i, kind in enumerate(layer_kinds(m)):
        n = "so%d." % i
        out.update({n + "attn_norm.w": ((h,), BF16, "gain"),
                    n + "mlp_norm.w": ((h,), BF16, "gain")})
        if kind == "gqa":
            out.update({n + "attn.q.w": ((h, qw), BF16, "normal"),
                        n + "attn.k.w": ((h, kvw), BF16, "normal"),
                        n + "attn.v.w": ((h, kvw), BF16, "normal"),
                        n + "attn.g.w": ((h, qw), BF16, "normal"),
                        n + "attn.o.w": ((qw, h), BF16, "normal")})
        else:
            for part in "qkv":
                out[n + "kda.%s.w" % part] = ((h, lw), BF16, "normal")
                out[n + "kda.%s_conv.w" % part] = ((lw, taps), BF16, "taps")
            out.update({n + "kda.fa.w": ((h, rank), BF16, "normal"),
                        n + "kda.fb.w": ((rank, lw), BF16, "normal"),
                        n + "kda.A_log": ((lh,), F32, "a_log"),
                        n + "kda.dt_bias": ((lw,), F32, "dt_bias"),
                        n + "kda.b.w": ((h, lh), BF16, "normal"),
                        n + "kda.ga.w": ((h, rank), BF16, "normal"),
                        n + "kda.gb.w": ((rank, lw), BF16, "normal"),
                        n + "kda.o_norm.w": ((ld,), BF16, "gain"),
                        n + "kda.o.w": ((lw, h), BF16, "normal")})
        fs = f * m["n_shared_experts"]
        for part, wide in (("w1", True), ("w3", True), ("w2", False)):
            out[n + "moe.shared.%s.w" % part] = (
                (h, fs) if wide else (fs, h), BF16, "normal")
            out[n + "moe.experts." + part] = (
                (held, h, f) if wide else (held, f, h), BF16, "normal")
        out.update({
            n + "moe.gate.w": ((h, m["router_experts"]), BF16, "normal"),
            n + "moe.gate.bias": ((m["router_experts"],), F32, "normal")})
    return out


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, dtype, how, std):
    def log_uniform(key, lo, hi):
        return jnp.exp(jax.random.uniform(key, shape, F32, np.log(lo),
                                          np.log(hi)))

    @jax.jit
    def make(key):
        if how == "a_log":                  # exp(A_log) in [1, 16]
            return jnp.log(log_uniform(key, 1.0, 16.0)).astype(dtype)
        if how == "dt_bias":                # softplus(dt_bias) in [.001, .3]
            return jnp.log(jnp.expm1(log_uniform(key, 1e-3, 0.3))).astype(
                dtype)
        x = (0.3 if how == "taps" else std) * jax.random.normal(key, shape,
                                                                F32)
        return (1.0 + x if how == "gain" else x).astype(dtype)

    return make


def make_weights(m, seed):
    """Every leaf from the seed, on the default device, one jitted draw per
    leaf (leaves of one shape share a program); the largest float32
    temporary is one leaf."""
    key = blocks.mask_key(seed)
    std = float(m.get("initializer_range", 0.02))
    return {name: _leaf_maker(tuple(shape), dtype, how, std)(
                jax.random.fold_in(key, i))
            for i, (name, (shape, dtype, how)) in enumerate(
                sorted(weight_shapes(m).items()))}


def gqa_block(x, bw, m, rnd, at=None):
    """What a softmax layer's attention block adds to the stream x (T, H),
    at the query rows `at` (default: every row), queries a block at a
    time."""
    heads, nkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                      m["head_dim"])
    t = x.shape[0]
    u = rms_norm(x, bw["attn_norm.w"], m["rms_norm_eps"])
    every = jnp.arange(t, dtype=jnp.int32)
    at = every if at is None else jnp.asarray(at, jnp.int32)
    n = at.shape[0]
    rows = jnp.pad(at, (0, (-n) % QUERY_BLOCK))
    uq = jnp.take(u, rows, axis=0)
    q = blocks.matmul(uq, bw["attn.q.w"], rnd).reshape(-1, heads, dh)
    k = blocks.matmul(u, bw["attn.k.w"], rnd).reshape(t, nkv, dh)
    v = blocks.matmul(u, bw["attn.v.w"], rnd).reshape(t, nkv, dh)

    def one(args):
        qb, ib = args                            # (QB, heads, dh), (QB,)
        qg = qb.reshape(QUERY_BLOCK, nkv, heads // nkv, dh)
        scores = jnp.einsum("qgrd,kgd->grqk", rnd(qg), rnd(k),
                            precision="highest") * dh ** -0.5
        seen = every[None, :] <= ib[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        ctx = jnp.einsum("grqk,kgd->qgrd", rnd(probs), rnd(v),
                         precision="highest")
        return ctx.reshape(QUERY_BLOCK, heads * dh)

    o = jax.lax.map(one, (q.reshape(-1, QUERY_BLOCK, heads, dh),
                          rows.reshape(-1, QUERY_BLOCK)))
    o = o.reshape(-1, heads * dh)
    if m.get("fault") != "gqa_gate_dropped":
        o = o * jax.nn.sigmoid(blocks.matmul(uq, bw["attn.g.w"], rnd))
    return blocks.matmul(o, bw["attn.o.w"], rnd)[:n]


def causal_conv(x, taps, handed_at=None):
    """Depthwise causal convolution over time, no bias: x (T, C), taps (C,
    K) -> y_t = sum_j taps[:, j] x_{t - (K - 1) + j}, zeros before the
    first position. `handed_at` plants the fault "conv_window_shifted":
    from that position on the positions before it are seen one position
    late (the window a fill hands over holds the K - 1 columns that end one
    short of the prompt's last)."""
    t, k = x.shape[0], taps.shape[1]

    def conv(seq):
        full = jnp.concatenate([jnp.zeros((k - 1, seq.shape[1]), F32), seq])
        return sum(full[j:j + t] * taps[:, j] for j in range(k))

    y = conv(x)
    if handed_at is None:
        return y
    late = jnp.concatenate([jnp.zeros((1, x.shape[1]), F32), x[:-1]])
    before = (jnp.arange(t) < handed_at)[:, None]
    return jnp.where(before, y, conv(jnp.where(before, late, x)))


def delta_rule(q, k, v, g, beta, stop=None):
    """The recurrence position by position from a zero state: q, k, v, g
    (T, heads, D), beta (T, heads) -> (o (T, heads, D), the state after
    position `stop` - 1 (the last, without `stop`) (heads, D, D))."""
    t, h, d = q.shape
    stop = t if stop is None else stop

    def one(carry, xs):
        s, kept = carry
        i, qt, kt, vt, gt, bt = xs
        sp = s * jnp.exp(gt)[:, :, None]
        r = vt - jnp.sum(sp * kt[:, :, None], 1)
        s = sp + (bt[:, None] * kt)[:, :, None] * r[:, None, :]
        return (s, jnp.where(i == stop - 1, s, kept)), jnp.sum(
            s * qt[:, :, None], 1)

    zero = jnp.zeros((h, d, d), F32)
    (_, kept), o = jax.lax.scan(
        one, (zero, zero), (jnp.arange(t), q, k, v, g, beta))
    return o, kept


def kda_block(x, bw, m, rnd, stop=None):
    """What a delta-rule layer's block adds to the stream x (T, H), every
    row, and the state after position `stop` - 1."""
    heads, d, _ = kda_sizes(m)
    t, fault = x.shape[0], m.get("fault")
    u = rms_norm(x, bw["attn_norm.w"], m["rms_norm_eps"])
    handed_at = stop if fault == "conv_window_shifted" else None

    def mixed(part):
        y = causal_conv(blocks.matmul(u, bw["kda.%s.w" % part], rnd),
                        bw["kda.%s_conv.w" % part], handed_at)
        return jax.nn.silu(y).reshape(t, heads, d)

    def l2(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, -1, keepdims=True) + 1e-6)

    q, k, v = l2(mixed("q")) * d ** -0.5, mixed("k"), mixed("v")
    if fault != "k_norm_dropped":
        k = l2(k)
    raw = blocks.matmul(blocks.matmul(u, bw["kda.fa.w"], rnd),
                        bw["kda.fb.w"], rnd) + bw["kda.dt_bias"]
    g = (-jnp.exp(bw["kda.A_log"])[None, :, None]
         * jax.nn.softplus(raw).reshape(t, heads, d))
    if fault == "decay_head_mean":
        g = jnp.broadcast_to(jnp.mean(g, -1, keepdims=True), g.shape)
    if fault == "alpha_one":
        g = jnp.zeros_like(g)
    beta = jax.nn.sigmoid(blocks.matmul(u, bw["kda.b.w"], rnd))
    if fault != "beta_not_doubled":
        beta = 2.0 * beta
    o, state = delta_rule(q, k, v, g, beta, stop)
    o = rms_norm(o, bw["kda.o_norm.w"], m["rms_norm_eps"]).reshape(t, -1)
    gate = jax.nn.sigmoid(blocks.matmul(
        blocks.matmul(u, bw["kda.ga.w"], rnd), bw["kda.gb.w"], rnd))
    return blocks.matmul(o * gate, bw["kda.o.w"], rnd), state


def routed_part(h, weights, bw, rnd):
    """sum over the held experts e of weights[:, e] x expert_e(h), one
    expert at a time over all tokens."""

    def one(acc, ew):
        e1, e3, e2 = (e.astype(F32) for e in ew[:3])       # this expert alone
        return acc + ew[3][:, None] * swiglu(h, e1, e3, e2, rnd), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (bw["moe.experts.w1"], bw["moe.experts.w3"], bw["moe.experts.w2"],
         jnp.swapaxes(weights, 0, 1)))
    return acc


def feed_forward(h, bw, m, rnd):
    """-> (what the layer's second half adds (T, H), the held experts' part
    of it)."""
    first, held = m["first_expert"], m["n_routed_experts"]
    if m.get("fault") == "held_shifted":
        first += 1
    part = routed_part(h, route(h, bw, m, rnd)[:, first:first + held], bw,
                       rnd)
    shared = swiglu(h, bw["moe.shared.w1.w"], bw["moe.shared.w3.w"],
                    bw["moe.shared.w2.w"], rnd)
    return part + shared, part


def _upcast(bw):
    """This layer's leaves in float32; the held experts (2.5 GB of them so)
    stay as stored and are upcast one at a time where they run."""
    return {k: v if k.startswith("moe.experts.") else v.astype(F32)
            for k, v in bw.items()}


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, bw):
        bw = _upcast(bw)
        a = (gqa_block(x, bw, m, rnd) if kind == "gqa"
             else kda_block(x, bw, m, rnd)[0])
        y = x + a
        out, part = feed_forward(
            rms_norm(y, bw["mlp_norm.w"], m["rms_norm_eps"]), bw, m, rnd)
        return y + out, part

    return run


@functools.lru_cache(maxsize=None)
def _mixer_fn(kind, frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, stop, bw):
        bw, x = _upcast(bw), x.astype(F32)
        if kind == "gqa":
            return gqa_block(x, bw, m, rnd, at), None
        out, state = kda_block(x, bw, m, rnd, stop)
        return jnp.take(out, at, axis=0), state

    return run


@functools.lru_cache(maxsize=None)
def _head_fn(frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, norm_w, head_w):
        x = rms_norm(jnp.take(x, at, axis=0), norm_w.astype(F32),
                     m["rms_norm_eps"])
        return blocks.matmul(x, head_w.astype(F32), rnd)

    return run


def layer_weights(w, i):
    n = "so%d." % i
    return {k[len(n):]: v for k, v in w.items() if k.startswith(n)}


def forward(w, ids, m, precision="float32", keep_streams=False,
            on_part=None):
    """ids (T,) -> (the stream (T, H) before the final norm; with
    `keep_streams` the stream before each layer (T, H) rounded to bfloat16
    (what the system's own stream is held in) as host arrays, else None;
    the held experts' part (T, H) of every layer, or what `on_part(j,
    part)` makes of the j-th), layer by layer. What a caller does not ask
    for is not kept: at 16,896 positions a float32 (T, H) is 0.28 GB."""
    fm = _freeze(m)
    x = jnp.take(w["so.emb"], jnp.asarray(ids), axis=0).astype(F32)
    streams, held = [], []
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(layer_kinds(m)):
            if keep_streams:
                streams.append(np.asarray(x.astype(BF16)))
            x, part = _layer_fn(kind, fm, precision)(x, layer_weights(w, i))
            held.append(on_part(i, part) if on_part else part)
    return x, streams if keep_streams else None, held


def head_logits(w, x, at, m, precision="float32"):
    """The stream x (T, H) -> float32 logits (len(at), vocab) at `at`."""
    with jax.default_matmul_precision("highest"):
        return _head_fn(_freeze(m), precision)(
            x, jnp.asarray(at), w["so.norm_f.w"], w["so.head.w"])


def logits_at(w, ids, at, m, precision="float32"):
    """ids (T,) -> float32 logits (len(at), vocab) at the positions `at`."""
    return head_logits(w, forward(w, ids, m, precision)[0], at, m, precision)


def mixer_at(w, layer, x, at, m, precision="float32", stop=None):
    """Layer `layer`'s attention or delta-rule block over the GIVEN stream x
    (T, H) (the system's own, say), at the rows `at` -> ((len(at), H)
    float32; for a delta-rule layer the state after position `stop` - 1
    (heads, D, D), else None). The rows are padded to whole query blocks,
    so that a few lengths share one compiled program."""
    at = np.asarray(at, np.int32)
    rows = np.pad(at, (0, (-len(at)) % QUERY_BLOCK), mode="edge")
    stop = x.shape[0] if stop is None else stop
    with jax.default_matmul_precision("highest"):
        out, state = _mixer_fn(layer_kinds(m)[layer], _freeze(m), precision)(
            jnp.asarray(x), jnp.asarray(rows), jnp.int32(stop),
            layer_weights(w, layer))
    return out[:len(at)], state
