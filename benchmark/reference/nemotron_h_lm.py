"""Plain reference of the served hybrid decoder (Nemotron-H family: Mamba-2
+ grouped-query attention + latent mixture of experts): one full causal
forward pass over prompt + served tokens in float32 at matmul precision
"highest", no cache, no slots, no chunked scan (the state-space layers are
a `lax.scan` over positions, the recurrence as written), the experts a loop
over the held range. Imports nothing of paddle_tpu.

Every block: ``x <- x + mixer(RMSNorm(x))``; ``M`` Mamba-2, ``*`` attention
without any position term, ``E`` LatentMoE (sigmoid router over ALL experts,
top-k, weights normalised over the k chosen and scaled; routed path in the
latent over the experts held here, the others' part left out exactly as the
system leaves it out; shared expert on ``x`` itself). Final RMSNorm, untied
head.

The seeded weights are made on the device, leaf by leaf, and kept as the
bfloat16 values the system holds; a block's weights are upcast when the
block runs, so the whole model never exists in float32.

`m` is the configuration file's top-level values (the published names)
plus `router_experts` (the router's width) and `first_expert` (where the
held range starts); `n_routed_experts` is the number held.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks

BF16, F32 = jnp.bfloat16, jnp.float32


def weight_shapes(m):
    """{name: (shape, dtype, how it is initialised)}."""
    h, pattern = m["hidden_size"], m["hybrid_override_pattern"]
    d_inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv_dim = d_inner + 2 * m["n_groups"] * m["ssm_state_size"]
    nh, kvw = m["mamba_num_heads"], m["num_key_value_heads"] * m["head_dim"]
    qw = m["num_attention_heads"] * m["head_dim"]
    lat, held = m["moe_latent_size"], m["n_routed_experts"]
    out = {"nh.emb": ((m["vocab_size"], h), BF16, "normal"),
           "nh.head.w": ((h, m["vocab_size"]), BF16, "normal"),
           "nh.norm_f.w": ((h,), BF16, "one")}
    for i, kind in enumerate(pattern):
        n = "nh%d." % i
        out[n + "norm.w"] = ((h,), BF16, "one")
        if kind == "M":
            out.update({
                n + "mixer.in.w": ((h, d_inner + conv_dim + nh), BF16,
                                   "normal"),
                n + "mixer.conv.w": ((conv_dim, m["conv_kernel"]), BF16,
                                     "conv"),
                n + "mixer.conv.b": ((conv_dim,), BF16, "conv"),
                n + "mixer.dt_bias": ((nh,), F32, "dt"),
                n + "mixer.A_log": ((nh,), F32, "A"),
                n + "mixer.D": ((nh,), F32, "one"),
                n + "mixer.norm.w": ((d_inner,), BF16, "one"),
                n + "mixer.out.w": ((d_inner, h), BF16, "out")})
        elif kind == "*":
            out.update({n + "attn.q.w": ((h, qw), BF16, "normal"),
                        n + "attn.k.w": ((h, kvw), BF16, "normal"),
                        n + "attn.v.w": ((h, kvw), BF16, "normal"),
                        n + "attn.o.w": ((qw, h), BF16, "out")})
        else:
            f, sf = (m["moe_intermediate_size"],
                     m["moe_shared_expert_intermediate_size"])
            out.update({
                n + "moe.gate.w": ((h, m["router_experts"]), BF16, "normal"),
                n + "moe.gate.bias": ((m["router_experts"],), F32, "zero"),
                n + "moe.down.w": ((h, lat), BF16, "normal"),
                n + "moe.up.w": ((lat, h), BF16, "normal"),
                n + "moe.experts.w1": ((held, lat, f), BF16, "normal"),
                n + "moe.experts.w2": ((held, f, lat), BF16, "normal"),
                n + "moe.shared.fc1.w": ((h, sf), BF16, "normal"),
                n + "moe.shared.fc2.w": ((sf, h), BF16, "normal")})
    return out


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, dtype, how, std, out_scale, conv_kernel, dt_lo,
                dt_hi, dt_floor):
    """The published initialisation where the config gives it."""

    @jax.jit
    def make(key):
        if how == "one":
            x = jnp.ones(shape, F32)
        elif how == "zero":
            x = jnp.zeros(shape, F32)
        elif how == "normal":
            x = std * jax.random.normal(key, shape, F32)
        elif how == "out":      # rescale_prenorm_residual
            x = std * out_scale * jax.random.normal(key, shape, F32)
        elif how == "conv":     # the framework default of a conv1d layer
            bound = 1.0 / math.sqrt(conv_kernel)
            x = jax.random.uniform(key, shape, F32, -bound, bound)
        elif how == "A":        # A uniform in [1, 16], kept as its log
            x = jnp.log(jax.random.uniform(key, shape, F32, 1.0, 16.0))
        else:                   # "dt": log-uniform step, inverse softplus
            dt = jnp.exp(jax.random.uniform(key, shape, F32)
                         * (math.log(dt_hi) - math.log(dt_lo))
                         + math.log(dt_lo))
            dt = jnp.maximum(dt, dt_floor)
            x = dt + jnp.log(-jnp.expm1(-dt))
        return x.astype(dtype)

    return make


def make_weights(m, seed):
    """Every leaf from the seed, on the default device, one jitted draw per
    leaf (leaves of one shape share a program), in the dtype the system
    holds: the largest float32 temporary is one leaf."""
    key = blocks.mask_key(seed)
    out_scale = 1.0 / math.sqrt(len(m["hybrid_override_pattern"]))
    w = {}
    for i, (name, (shape, dtype, how)) in enumerate(
            sorted(weight_shapes(m).items())):
        make = _leaf_maker(tuple(shape), dtype, how,
                           float(m.get("initializer_range", 0.02)), out_scale,
                           m["conv_kernel"], m["time_step_min"],
                           m["time_step_max"], m["time_step_floor"])
        w[name] = make(jax.random.fold_in(key, i))
    return w


def rms_norm(x, w, eps, groups=1):
    shape = x.shape
    xg = x.reshape(shape[:-1] + (groups, shape[-1] // groups))
    xg = xg * jax.lax.rsqrt(jnp.mean(jnp.square(xg), -1, keepdims=True) + eps)
    return xg.reshape(shape) * w


def relu2(x):
    return jnp.square(jnp.maximum(x, 0.0))


def mamba_block(h, bw, m, rnd):
    """h (T, H) -> (T, H): the recurrence as written, position by
    position."""
    nh, p = m["mamba_num_heads"], m["mamba_head_dim"]
    g, n, k = m["n_groups"], m["ssm_state_size"], m["conv_kernel"]
    d_inner, t = nh * p, h.shape[0]
    zxbcdt = blocks.matmul(h, bw["mixer.in.w"], rnd)
    z, xbc, dt = jnp.split(zxbcdt, [d_inner, zxbcdt.shape[-1] - nh], -1)
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[-1])), xbc], 0)
    conv = sum(padded[j:j + t] * bw["mixer.conv.w"][:, j] for j in range(k))
    xbc = jax.nn.silu(conv + bw["mixer.conv.b"])
    x = xbc[:, :d_inner].reshape(t, nh, p)
    bm = jnp.repeat(xbc[:, d_inner:d_inner + g * n].reshape(t, g, n),
                    nh // g, axis=1)                      # (T, heads, N)
    cm = jnp.repeat(xbc[:, d_inner + g * n:].reshape(t, g, n),
                    nh // g, axis=1)
    dt = jax.nn.softplus(dt + bw["mixer.dt_bias"])        # (T, heads)
    a = -jnp.exp(bw["mixer.A_log"])

    def step(hs, inp):
        x_t, b_t, c_t, dt_t = inp
        hs = (jnp.exp(dt_t * a)[:, None, None] * hs
              + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        return hs, jnp.sum(hs * c_t[:, None, :], -1)

    _, y = jax.lax.scan(step, jnp.zeros((nh, p, n), F32), (x, bm, cm, dt))
    y = (y + bw["mixer.D"][:, None] * x).reshape(t, d_inner)
    y = rms_norm(y * jax.nn.silu(z), bw["mixer.norm.w"],
                 m["layer_norm_epsilon"], groups=g)
    return blocks.matmul(y, bw["mixer.out.w"], rnd)


def attention_block(h, bw, m, rnd):
    """Causal grouped-query attention, no position term."""
    nq, nkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    t = h.shape[0]
    q = blocks.matmul(h, bw["attn.q.w"], rnd).reshape(t, nq, dh)
    k = blocks.matmul(h, bw["attn.k.w"], rnd).reshape(t, nkv, dh)
    v = blocks.matmul(h, bw["attn.v.w"], rnd).reshape(t, nkv, dh)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    scores = blocks.matmul(jnp.swapaxes(q, 0, 1),
                           jnp.swapaxes(k, 0, 1).swapaxes(1, 2), rnd)
    scores = scores * dh ** -0.5                           # (heads, T, T)
    pos = jnp.arange(t)
    scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
    ctx = blocks.matmul(jax.nn.softmax(scores, -1), jnp.swapaxes(v, 0, 1),
                        rnd)
    return blocks.matmul(jnp.swapaxes(ctx, 0, 1).reshape(t, nq * dh),
                         bw["attn.o.w"], rnd)


def route(h, bw, m, rnd):
    """-> (T, router_experts) float32: each token's weight on every expert
    (zero on those it did not choose), normalised over the k chosen BEFORE
    any expert is left out, times the routed scaling factor."""
    s = jax.nn.sigmoid(blocks.matmul(h, bw["moe.gate.w"], rnd))
    _, idx = jax.lax.top_k(s + bw["moe.gate.bias"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
    w = w * m["routed_scaling_factor"]
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, idx].set(w)


def routed_latent(lat, weights, w1, w2, rnd):
    """sum over the given experts e of weights[:, e] * W2_e relu(W1_e l)^2,
    one expert at a time over all tokens."""

    def one(acc, ew):
        e1, e2, col = ew
        out = blocks.matmul(relu2(blocks.matmul(lat, e1, rnd)), e2, rnd)
        return acc + col[:, None] * out, None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(lat),
                          (w1, w2, jnp.swapaxes(weights, 0, 1)))
    return acc


def moe_parts(h, bw, m, rnd):
    """-> (the block's output (T, H), the held experts' part (T, latent)
    before its up-projection)."""
    first, held = m["first_expert"], m["n_routed_experts"]
    weights = route(h, bw, m, rnd)[:, first:first + held]
    lat = blocks.matmul(h, bw["moe.down.w"], rnd)
    r = routed_latent(lat, weights, bw["moe.experts.w1"],
                      bw["moe.experts.w2"], rnd)
    shared = blocks.matmul(
        relu2(blocks.matmul(h, bw["moe.shared.fc1.w"], rnd)),
        bw["moe.shared.fc2.w"], rnd)
    return blocks.matmul(r, bw["moe.up.w"], rnd) + shared, r


MIXERS = {"M": mamba_block, "*": attention_block}


@functools.lru_cache(maxsize=None)
def _block_fn(kind, frozen_m, precision):
    m, rnd = dict(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, bw):
        bw = {k: v.astype(F32) for k, v in bw.items()}    # this block alone
        h = rms_norm(x, bw["norm.w"], m["layer_norm_epsilon"])
        if kind == "E":
            out, held = moe_parts(h, bw, m, rnd)
            return x + out, held
        return x + MIXERS[kind](h, bw, m, rnd), None

    return run


@functools.lru_cache(maxsize=None)
def _head_fn(frozen_m, precision):
    m, rnd = dict(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, norm_w, head_w):
        x = rms_norm(jnp.take(x, at, axis=0), norm_w.astype(F32),
                     m["layer_norm_epsilon"])
        return blocks.matmul(x, head_w.astype(F32), rnd)

    return run


def _freeze(m):
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def forward(w, ids, m, precision="float32"):
    """ids (T,) -> (the stream (T, H) before the final norm, the held
    experts' part (T, latent) of every expert layer), block by block."""
    fm = _freeze(m)
    x = jnp.take(w["nh.emb"], jnp.asarray(ids), axis=0).astype(F32)
    held = []
    for i, kind in enumerate(m["hybrid_override_pattern"]):
        n = "nh%d." % i
        bw = {k[len(n):]: v for k, v in w.items() if k.startswith(n)}
        x, part = _block_fn(kind, fm, precision)(x, bw)
        if part is not None:
            held.append(part)
    return x, held


def logits_at(w, ids, at, m, precision="float32"):
    """ids (T,) -> float32 logits (len(at), vocab) at the positions `at`."""
    with jax.default_matmul_precision("highest"):
        x, _ = forward(w, ids, m, precision)
        return _head_fn(_freeze(m), precision)(
            x, jnp.asarray(at), w["nh.norm_f.w"], w["nh.head.w"])


def routed_parts(w, ids, m, precision="float32"):
    """ids (T,) -> per expert layer the held experts' part (T, latent),
    float32 on the host."""
    with jax.default_matmul_precision("highest"):
        return [np.asarray(p) for p in forward(w, ids, m, precision)[1]]


def routed_gap(got, want):
    """How far one sequence's held experts' parts `got` lie from the
    reference's `want` (both: per expert layer (T, latent)): per layer the
    MEDIAN over positions of |got_t - want_t| in units of the layer's
    root-mean-square |want_t|, and of the layers the largest. The median,
    because top-k routing is not continuous: where two experts' scores
    nearly tie, a sound lower precision may choose the other one, and that
    position's part is then another expert's output; a fault in the layer
    moves every position."""
    worst = 0.0
    for g, r in zip(got, want):
        g, r = np.asarray(g, np.float64), np.asarray(r, np.float64)
        scale = np.sqrt(np.mean(np.sum(r * r, -1)))
        err = np.sqrt(np.sum((g - r) ** 2, -1)) / scale
        worst = max(worst, float(np.median(err)))
    return worst


def served_gaps(w, requests, m, seq_len, out_len, control=None):
    """For each request (prompt ids, served tokens): the reference's logits
    at every position that produced a served token, and from them, in units
    of that position's logit standard deviation, how far the served token
    lies below the reference's best. With `control` (a precision name) the
    token judged is not the served one but the one that precision puts
    first at the same position of the same sequence.

    Returns the list of per-token gaps, request by request."""
    gaps = []
    for prompt, served in requests:
        n = len(served)
        seq = np.zeros((seq_len,), np.int32)
        full = list(prompt) + list(served)
        seq[:len(full)] = full
        at = np.minimum(len(prompt) - 1 + np.arange(out_len),
                        seq_len - 1).astype(np.int32)
        ref = np.asarray(logits_at(w, seq, at, m))[:n]
        tok = (np.asarray(logits_at(w, seq, at, m, control))[:n].argmax(-1)
               if control else np.asarray(served))
        gap = ref.max(-1) - ref[np.arange(n), tok]
        gaps.append(gap / ref.std(-1))
    return gaps
