"""Plain reference of the served Laguna decoder (window and full
grouped-query attention with a gate per head, two rotary terms, softmax-
routed SwiGLU experts plus a shared expert): one full causal forward pass
over prompt + served tokens in float32 at matmul precision "highest", no
cache, no ring, no kernels, the experts a loop over the held range, the
attention a loop over blocks of queries (so that 8,704 positions fit: the
scores alive are heads x QUERY_BLOCK x T). Imports nothing of paddle_tpu.

Layer l, with H_l query heads, 8 K/V heads of 128, u = RMSNorm(x):
  q, k, v = u Wq, u Wk, u Wv; q and k turned at the token's position
  (`rope_tables`: sliding layers all 128 dimensions at theta 10,000; full
  layers the first 64 of each head by YaRN's blended rates, cos and sin
  times attention_factor, the other 64 unturned);
  o_h = softmax(q_h k_g^T / sqrt(128) + mask) v_g, g = h // (H_l / 8), key j
  seen from query i iff j <= i and, on a sliding layer, i - j < 512;
  y = x + concat_h(sigmoid(u Wg)_h o_h) Wo;
  w = RMSNorm(y); a dense layer adds Wdown(silu(Wgate w) * Wup w); a sparse
  layer adds sum over the 10 experts with the largest softmax(w Wr) that are
  held here of 2.5 s_e / sum_chosen(s) x expert_e(w), plus the shared expert.
Final RMSNorm, untied head over the held rows.

The seeded weights are made on the device, leaf by leaf, and kept as the
bfloat16 values the system holds; a layer's weights are upcast when the
layer runs, so the whole model never exists in float32.

`m` is the configuration file's published keys, the lists among them
(`layer_types`, `mlp_layer_types`, `num_attention_heads_per_layer`,
`rope_parameters`), plus `router_experts` (the router's width) and
`first_expert` (where the held range starts); `num_experts` is the number
held.
"""
import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks

BF16, F32 = jnp.bfloat16, jnp.float32
QUERY_BLOCK = 256


def weight_shapes(m):
    """{name: (shape, how it is initialised)}, all bfloat16."""
    h, dh = m["hidden_size"], m["head_dim"]
    kvw, held = m["num_key_value_heads"] * dh, m["num_experts"]
    out = {"lg.emb": ((m["vocab_size"], h), "normal"),
           "lg.head.w": ((h, m["vocab_size"]), "normal"),
           "lg.norm_f.w": ((h,), "gain")}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), "normal"),
                    name + ".w3.w": ((h, width), "normal"),
                    name + ".w2.w": ((width, h), "normal")})

    for i, heads in enumerate(m["num_attention_heads_per_layer"]):
        n = "lg%d." % i
        out.update({n + "attn_norm.w": ((h,), "gain"),
                    n + "mlp_norm.w": ((h,), "gain"),
                    n + "attn.q.w": ((h, heads * dh), "normal"),
                    n + "attn.k.w": ((h, kvw), "normal"),
                    n + "attn.v.w": ((h, kvw), "normal"),
                    n + "attn.g.w": ((h, heads), "normal"),
                    n + "attn.o.w": ((heads * dh, h), "normal")})
        if m["mlp_layer_types"][i] == "dense":
            ffn(n + "mlp", m["intermediate_size"])
            continue
        f = m["moe_intermediate_size"]
        ffn(n + "moe.shared", m["shared_expert_intermediate_size"])
        out.update({n + "moe.gate.w": ((h, m["router_experts"]), "normal"),
                    n + "moe.experts.w1": ((held, h, f), "normal"),
                    n + "moe.experts.w3": ((held, h, f), "normal"),
                    n + "moe.experts.w2": ((held, f, h), "normal")})
    return out


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, how, std):
    @jax.jit
    def make(key):
        x = std * jax.random.normal(key, shape, F32)
        return (1.0 + x if how == "gain" else x).astype(BF16)

    return make


def make_weights(m, seed):
    """Every leaf from the seed, on the default device, one jitted draw per
    leaf (leaves of one shape share a program): normal(0, std), norm gains
    1 + that; the largest float32 temporary is one leaf."""
    key = blocks.mask_key(seed)
    std = float(m.get("initializer_range", 0.02))
    return {name: _leaf_maker(tuple(shape), how, std)(
                jax.random.fold_in(key, i))
            for i, (name, (shape, how)) in enumerate(
                sorted(weight_shapes(m).items()))}


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rope_rates(m, kind):
    """-> (rates (rot / 2,) float32, the factor on cos and sin, rot): the
    turning rates of layer type `kind` over the first `rot` dimensions of a
    head. YaRN as `transformers` computes it (`_compute_yarn_parameters`)."""
    r = m["rope_parameters"][kind]
    rot = int(round(m["head_dim"] * r.get("partial_rotary_factor", 1)))
    theta = float(r["rope_theta"])
    f = theta ** (np.arange(0, rot, 2, dtype=np.float64) / rot)
    if r.get("rope_type", "default") != "yarn":
        return (1.0 / f).astype(np.float32), 1.0, rot

    def correction(turns):
        return (rot * math.log(r["original_max_position_embeddings"]
                               / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction(r["beta_fast"])), 0)
    high = min(math.ceil(correction(r["beta_slow"])), rot - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(rot // 2) - low) / (high - low), 0.0, 1.0)
    rates = (1.0 - ramp) / f + ramp / (r["factor"] * f)
    return rates.astype(np.float32), float(r["attention_factor"]), rot


def rotary(x, positions, m, kind):
    """x (T, heads, head_dim) at `positions` (T,): pair (x[i], x[i + rot/2])
    of the first rot dimensions of every head turned by position x rate_i
    (`rotate_half`), cos and sin times the factor; float32."""
    rates, factor, rot = rope_rates(m, kind)
    ang = positions.astype(F32)[:, None] * jnp.asarray(rates)[None, :]
    cos = (jnp.cos(ang) * factor)[:, None, :]
    sin = (jnp.sin(ang) * factor)[:, None, :]
    x1, x2, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           -1)


def attention_block(x, bw, m, kind, heads, rnd, at=None):
    """What layer's attention block adds to the stream x (T, H), at the
    query rows `at` (default: every row): Wo (sigmoid(u Wg) * Attn(u)),
    queries a block at a time."""
    nkv, dh, t = m["num_key_value_heads"], m["head_dim"], x.shape[0]
    u = rms_norm(x, bw["attn_norm.w"], m["rms_norm_eps"])
    every = jnp.arange(t, dtype=jnp.int32)
    at = every if at is None else jnp.asarray(at, jnp.int32)
    n = at.shape[0]
    pad = (-n) % QUERY_BLOCK
    rows = jnp.pad(at, (0, pad))
    uq = jnp.take(u, rows, axis=0)
    q = rotary(blocks.matmul(uq, bw["attn.q.w"], rnd).reshape(-1, heads, dh),
               rows, m, kind)
    k = rotary(blocks.matmul(u, bw["attn.k.w"], rnd).reshape(t, nkv, dh),
               every, m, kind)
    v = blocks.matmul(u, bw["attn.v.w"], rnd).reshape(t, nkv, dh)
    window = m["sliding_window"] if kind == "sliding_attention" else None

    def one(args):
        qb, ib = args                            # (QB, heads, dh), (QB,)
        qg = qb.reshape(QUERY_BLOCK, nkv, heads // nkv, dh)
        scores = jnp.einsum("qgrd,kgd->grqk", rnd(qg), rnd(k),
                            precision="highest") * dh ** -0.5
        seen = every[None, :] <= ib[:, None]
        if window:
            seen &= ib[:, None] - every[None, :] < window
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        ctx = jnp.einsum("grqk,kgd->qgrd", rnd(probs), rnd(v),
                         precision="highest")
        return ctx.reshape(QUERY_BLOCK, heads, dh)

    o = jax.lax.map(one, (q.reshape(-1, QUERY_BLOCK, heads, dh),
                          rows.reshape(-1, QUERY_BLOCK)))
    o = o.reshape(-1, heads, dh)
    gate = jax.nn.sigmoid(blocks.matmul(uq, bw["attn.g.w"], rnd))
    return blocks.matmul((o * gate[:, :, None]).reshape(-1, heads * dh),
                         bw["attn.o.w"], rnd)[:n]


def swiglu(h, w1, w3, w2, rnd):
    return blocks.matmul(jax.nn.silu(blocks.matmul(h, w1, rnd))
                         * blocks.matmul(h, w3, rnd), w2, rnd)


def route(h, bw, m, rnd):
    """-> (T, router_experts) float32: each token's weight on every expert
    (zero on those it did not choose): softmax over ALL experts, the k
    largest, normalised over the k chosen BEFORE any expert is left out,
    times the routed scaling factor."""
    s = jax.nn.softmax(blocks.matmul(h, bw["moe.gate.w"], rnd), -1)
    chosen, idx = jax.lax.top_k(s, m["num_experts_per_tok"])
    w = (chosen / jnp.sum(chosen, -1, keepdims=True)
         * m["moe_routed_scaling_factor"])
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)


def routed_part(h, weights, bw, rnd):
    """sum over the held experts e of weights[:, e] x expert_e(h), one
    expert at a time over all tokens."""

    def one(acc, ew):
        e1, e3, e2, col = ew
        return acc + col[:, None] * swiglu(h, e1, e3, e2, rnd), None

    acc, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (bw["moe.experts.w1"], bw["moe.experts.w3"], bw["moe.experts.w2"],
         jnp.swapaxes(weights, 0, 1)))
    return acc


def feed_forward(h, bw, m, sparse, rnd):
    """-> (what the layer's second half adds (T, H), the held experts' part
    of it or None)."""
    if not sparse:
        return swiglu(h, bw["mlp.w1.w"], bw["mlp.w3.w"], bw["mlp.w2.w"],
                      rnd), None
    first, held = m["first_expert"], m["num_experts"]
    part = routed_part(h, route(h, bw, m, rnd)[:, first:first + held], bw,
                       rnd)
    shared = swiglu(h, bw["moe.shared.w1.w"], bw["moe.shared.w3.w"],
                    bw["moe.shared.w2.w"], rnd)
    return part + shared, part


def _freeze(m):
    return json.dumps(m, sort_keys=True)


@functools.lru_cache(maxsize=None)
def _layer_fn(kind, heads, sparse, frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, bw):
        bw = {k: v.astype(F32) for k, v in bw.items()}    # this layer alone
        a = attention_block(x, bw, m, kind, heads, rnd)
        y = x + a
        out, part = feed_forward(
            rms_norm(y, bw["mlp_norm.w"], m["rms_norm_eps"]), bw, m, sparse,
            rnd)
        return y + out, a, part

    return run


@functools.lru_cache(maxsize=None)
def _attention_fn(kind, heads, frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, bw):
        bw = {k: v.astype(F32) for k, v in bw.items()}
        return attention_block(x.astype(F32), bw, m, kind, heads, rnd, at)

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
    n = "lg%d." % i
    return {k[len(n):]: v for k, v in w.items() if k.startswith(n)}


def forward(w, ids, m, precision="float32"):
    """ids (T,) -> (the stream (T, H) before the final norm, what each
    layer's attention block added (T, H), the held experts' part (T, H) of
    every sparse layer), layer by layer."""
    fm = _freeze(m)
    x = jnp.take(w["lg.emb"], jnp.asarray(ids), axis=0).astype(F32)
    attn, held = [], []
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(m["layer_types"]):
            x, a, part = _layer_fn(
                kind, m["num_attention_heads_per_layer"][i],
                m["mlp_layer_types"][i] == "sparse", fm, precision)(
                    x, layer_weights(w, i))
            attn.append(a)
            if part is not None:
                held.append(part)
    return x, attn, held


def head_logits(w, x, at, m, precision="float32"):
    """The stream x (T, H) -> float32 logits (len(at), vocab) at `at`."""
    with jax.default_matmul_precision("highest"):
        return _head_fn(_freeze(m), precision)(
            x, jnp.asarray(at), w["lg.norm_f.w"], w["lg.head.w"])


def logits_at(w, ids, at, m, precision="float32"):
    """ids (T,) -> float32 logits (len(at), vocab) at the positions `at`."""
    return head_logits(w, forward(w, ids, m, precision)[0], at, m, precision)


def attention_at(w, layer, x, at, m, precision="float32"):
    """Layer `layer`'s attention block over the GIVEN stream x (T, H) (the
    system's own, say), at the query rows `at` -> (len(at), H) float32.
    The rows are padded to whole query blocks, so that a few lengths share
    one compiled program."""
    at = np.asarray(at, np.int32)
    rows = np.pad(at, (0, (-len(at)) % QUERY_BLOCK), mode="edge")
    with jax.default_matmul_precision("highest"):
        return _attention_fn(
            m["layer_types"][layer],
            m["num_attention_heads_per_layer"][layer], _freeze(m),
            precision)(jnp.asarray(x), jnp.asarray(rows),
                       layer_weights(w, layer))[:len(at)]


def rms_gap(got, want):
    """|got - want| over rows (n, H) in units of the root-mean-square
    |want|: sqrt(mean_t |got_t - want_t|^2 / mean_t |want_t|^2)."""
    got, want = jnp.asarray(got, F32), jnp.asarray(want, F32)
    return float(jnp.sqrt(jnp.sum(jnp.square(got - want))
                          / jnp.sum(jnp.square(want))))


def routed_gap(got, want):
    """How far one sequence's held experts' parts `got` lie from the
    reference's `want` (both: per sparse layer (T, H)): per layer the
    MEDIAN over positions of |got_t - want_t| in units of the layer's
    root-mean-square |want_t|, and of the layers the largest. The median,
    because top-k routing is not continuous: where two experts' scores
    nearly tie, a sound lower precision may choose the other one, and that
    position's part is then another expert's output; a fault in the layer
    moves every position."""
    worst = 0.0
    for g, r in zip(got, want):
        g, r = jnp.asarray(g, F32), jnp.asarray(r, F32)
        scale = jnp.sqrt(jnp.mean(jnp.sum(r * r, -1)))
        err = jnp.sqrt(jnp.sum(jnp.square(g - r), -1)) / scale
        worst = max(worst, float(jnp.median(err)))
    return worst


def token_gaps(ref_logits, tokens):
    """In units of each position's logit standard deviation, how far
    `tokens` lie below the reference's best."""
    ref = np.asarray(ref_logits)
    n = len(tokens)
    gap = ref[:n].max(-1) - ref[np.arange(n), np.asarray(tokens)]
    return gap / ref[:n].std(-1)


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
        ref = logits_at(w, seq, at, m)
        tok = (np.asarray(logits_at(w, seq, at, m, control))[:n].argmax(-1)
               if control else np.asarray(served))
        gaps.append(token_gaps(ref, tok))
    return gaps
