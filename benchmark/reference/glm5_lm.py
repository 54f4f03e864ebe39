"""Plain reference of the served GLM-5 decoder (`glm_moe_dsa`: multi-head
latent attention over a learned sparse selection of keys, sigmoid-routed
SwiGLU experts plus a shared expert): one full causal forward pass over
prompt + served tokens in float32 at matmul precision "highest", no cache,
no gather, no absorbed products, no kernels; the experts a loop over the held
range, the attention a loop over blocks of queries (so that 17,408 positions
fit: the scores alive are heads x QUERY_BLOCK x T). Imports nothing of
paddle_tpu.

Layer l over the stream x (T, 6144), u = RMSNorm(x), eps 1e-5:
  cq = RMSNorm(u Wdq) (2,048); q = cq Wuq -> 64 heads x [q_nope 192 | q_rope
  64], q_rope turned at the token's position (theta 1e6, interleaved pairs
  (2i, 2i + 1), handed on de-interleaved as `transformers` does);
  [ckv | k_rope] = u Wdkv (512 + 64); ckv = RMSNorm(ckv); k_rope turned, one
  for all heads;
  indexer: qi = cq Wiq -> 32 heads x 128, ki = LayerNorm(u Wik) (128), the
  first 64 dimensions of each turned (interleaved pairs); w = u Wiw (32) x
  32^-1/2 x 128^-1/2; I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s]); S_t =
  the 2,048 positions s <= t of largest I[t, s] (`lax.top_k`; all of them
  while t < 2,048);
  k_nope[s, h] = ckv[s] Wuk[h] (192), v[s, h] = ckv[s] Wuv[h] (256);
  o_h = softmax over S_t of (q_nope . k_nope + q_rope . k_rope) / 16, times v;
  y = x + concat_h(o_h) Wo;
  w = RMSNorm(y); a dense layer adds Wdown(silu(Wgate w) * Wup w); a sparse
  layer adds, over the 8 experts of largest sigmoid(w Wr) + bias that are
  held here, 2.5 s_e / (sum_chosen s + 1e-20) x expert_e(w), plus the shared
  expert.
Final RMSNorm, untied head over the held rows.

Departures from the published description (the configuration file's
`assumed`): the family's Hadamard turn and float8 storage of qi and ki are a
precision choice that leaves I unchanged in exact arithmetic, not taken; no
multi-token-prediction layer.

The seeded weights are made on the device, leaf by leaf, and kept as the
bfloat16 values the system holds (the router's score correction float32); a
layer's weights are upcast when the layer runs, so the whole model never
exists in float32.

`m` is the configuration file's published keys (`rope_parameters` among
them) plus `router_experts` (the router's width) and `first_expert` (where
the held range starts); `n_routed_experts` is the number held, the first
`first_k_dense_replace` of `num_hidden_layers` layers are dense.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .laguna_lm import rms_gap, rms_norm, swiglu, token_gaps  # noqa: F401

BF16, F32 = jnp.bfloat16, jnp.float32
QUERY_BLOCK = 128


def weight_shapes(m):
    """{name: (shape, dtype, how it is initialised)}."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    qr, kvr = m["q_lora_rank"], m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    ih, idim = m["index_n_heads"], m["index_head_dim"]
    held = m["n_routed_experts"]
    out = {"glm.emb": ((m["vocab_size"], h), BF16, "normal"),
           "glm.head.w": ((h, m["vocab_size"]), BF16, "normal"),
           "glm.norm_f.w": ((h,), BF16, "gain")}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), BF16, "normal"),
                    name + ".w3.w": ((h, width), BF16, "normal"),
                    name + ".w2.w": ((width, h), BF16, "normal")})

    for i in range(m["num_hidden_layers"]):
        n = "glm%d." % i
        out.update({
            n + "attn_norm.w": ((h,), BF16, "gain"),
            n + "mlp_norm.w": ((h,), BF16, "gain"),
            n + "mla.q_a.w": ((h, qr), BF16, "normal"),
            n + "mla.q_norm.w": ((qr,), BF16, "gain"),
            n + "mla.q_b.w": ((qr, heads * (nope + rope)), BF16, "normal"),
            n + "mla.kv_a.w": ((h, kvr + rope), BF16, "normal"),
            n + "mla.kv_norm.w": ((kvr,), BF16, "gain"),
            n + "mla.uk.w": ((kvr, heads * nope), BF16, "normal"),
            n + "mla.uv.w": ((kvr, heads * vd), BF16, "normal"),
            n + "mla.o.w": ((heads * vd, h), BF16, "normal"),
            n + "idx.q.w": ((qr, ih * idim), BF16, "normal"),
            n + "idx.k.w": ((h, idim), BF16, "normal"),
            n + "idx.k_norm.w": ((idim,), BF16, "gain"),
            n + "idx.k_norm.b": ((idim,), BF16, "normal"),
            n + "idx.w.w": ((h, ih), BF16, "normal")})
        if i < m["first_k_dense_replace"]:
            ffn(n + "mlp", m["intermediate_size"])
            continue
        f = m["moe_intermediate_size"]
        ffn(n + "moe.shared", f * m["n_shared_experts"])
        out.update({
            n + "moe.gate.w": ((h, m["router_experts"]), BF16, "normal"),
            n + "moe.gate.bias": ((m["router_experts"],), F32, "normal"),
            n + "moe.experts.w1": ((held, h, f), BF16, "normal"),
            n + "moe.experts.w3": ((held, h, f), BF16, "normal"),
            n + "moe.experts.w2": ((held, f, h), BF16, "normal")})
    return out


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, dtype, how, std):
    @jax.jit
    def make(key):
        x = std * jax.random.normal(key, shape, F32)
        return (1.0 + x if how == "gain" else x).astype(dtype)

    return make


def make_weights(m, seed):
    """Every leaf from the seed, on the default device, one jitted draw per
    leaf (leaves of one shape share a program): normal(0, std), norm gains
    1 + that; the largest float32 temporary is one leaf."""
    key = blocks.mask_key(seed)
    std = float(m.get("initializer_range", 0.02))
    return {name: _leaf_maker(tuple(shape), dtype, how, std)(
                jax.random.fold_in(key, i))
            for i, (name, (shape, dtype, how)) in enumerate(
                sorted(weight_shapes(m).items()))}


def rotary(x, positions, m, rot):
    """x (T, ..., d) at `positions` (T,): the first `rot` dimensions in
    interleaved pairs (x[2i], x[2i + 1]), pair i turned by position x
    theta^(-2i/rot); the turned pair comes out at (i, i + rot/2), as
    `transformers`' `apply_rotary_pos_emb_interleave` hands it on; float32."""
    theta = float(m["rope_parameters"]["rope_theta"])
    rates = theta ** -(np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = positions.astype(F32)[:, None] * jnp.asarray(rates, F32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rot // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    pairs = x[..., :rot].reshape(x.shape[:-1] + (rot // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rot:]], -1)


def attention_block(x, bw, m, rnd, at=None, topk=None, index_rotary=True):
    """What the layer's attention block adds to the stream x (T, H) at the
    query rows `at` (default: every row), and which keys each of those rows
    kept: -> ((n, H), (n, T) bool). `topk` (default: the configuration's
    `index_topk`) and `index_rotary` are for the controls: another number of
    kept keys, a selection by scores without the rotary term."""
    heads, t = m["num_attention_heads"], x.shape[0]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    rank, ih, idim = m["kv_lora_rank"], m["index_n_heads"], m["index_head_dim"]
    topk = min(int(topk or m["index_topk"]), t)
    eps = m["rms_norm_eps"]
    u = rms_norm(x, bw["attn_norm.w"], eps)
    every = jnp.arange(t, dtype=jnp.int32)
    at = every if at is None else jnp.asarray(at, jnp.int32)
    n = at.shape[0]
    rows = jnp.pad(at, (0, (-n) % QUERY_BLOCK))
    uq = jnp.take(u, rows, axis=0)
    cq = rms_norm(blocks.matmul(uq, bw["mla.q_a.w"], rnd),
                  bw["mla.q_norm.w"], eps)
    q = rotary_last(blocks.matmul(cq, bw["mla.q_b.w"], rnd).reshape(
        -1, heads, nope + rope), rows, m, nope, rope)
    kv = blocks.matmul(u, bw["mla.kv_a.w"], rnd)
    ckv = rms_norm(kv[:, :rank], bw["mla.kv_norm.w"], eps)
    k_rope = rotary(kv[:, rank:], every, m, rope)               # (T, rope)
    k_nope = blocks.matmul(ckv, bw["mla.uk.w"], rnd).reshape(t, heads, nope)
    v = blocks.matmul(ckv, bw["mla.uv.w"], rnd).reshape(t, heads, vd)
    # the indexer
    qi = blocks.matmul(cq, bw["idx.q.w"], rnd).reshape(-1, ih, idim)
    ki = blocks.layer_norm(blocks.matmul(u, bw["idx.k.w"], rnd),
                           bw["idx.k_norm.w"], bw["idx.k_norm.b"], 1e-6)
    if index_rotary:
        qi, ki = rotary(qi, rows, m, rope), rotary(ki, every, m, rope)
    wi = blocks.matmul(uq, bw["idx.w.w"], rnd) * (ih ** -0.5 * idim ** -0.5)

    def one(args):
        qb, qib, wb, ib = args
        seen = every[None, :] <= ib[:, None]                    # (QB, T)
        per_head = jnp.einsum("qjd,kd->qjk", rnd(qib), rnd(ki),
                              precision="highest")
        index = jnp.sum(jnp.maximum(per_head, 0.0) * wb[:, :, None], 1)
        _, best = jax.lax.top_k(jnp.where(seen, index, -jnp.inf), topk)
        kept = jnp.zeros(seen.shape, bool).at[
            jnp.arange(QUERY_BLOCK)[:, None], best].set(True) & seen
        scores = (jnp.einsum("qhd,khd->hqk", rnd(qb[..., :nope]),
                             rnd(k_nope), precision="highest")
                  + jnp.einsum("qhd,kd->hqk", rnd(qb[..., nope:]),
                               rnd(k_rope), precision="highest")
                  ) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(kept[None], scores, -jnp.inf), -1)
        ctx = jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v),
                         precision="highest")
        return ctx.reshape(QUERY_BLOCK, heads * vd), kept

    o, kept = jax.lax.map(one, (
        q.reshape(-1, QUERY_BLOCK, heads, nope + rope),
        qi.reshape(-1, QUERY_BLOCK, ih, idim),
        wi.reshape(-1, QUERY_BLOCK, ih), rows.reshape(-1, QUERY_BLOCK)))
    out = blocks.matmul(o.reshape(-1, heads * vd), bw["mla.o.w"], rnd)
    return out[:n], kept.reshape(-1, t)[:n]


def rotary_last(x, positions, m, nope, rope):
    """x (T, heads, nope + rope): the last `rope` dimensions turned."""
    return jnp.concatenate(
        [x[..., :nope], rotary(x[..., nope:], positions, m, rope)], -1)


def route(h, bw, m, rnd):
    """-> (T, router_experts) float32: each token's weight on every expert
    (zero on those it did not choose): sigmoid scores over ALL experts, the
    k largest of score + correction, the chosen scores normalised over the
    k chosen (+ 1e-20) BEFORE any expert is left out, times the routed
    scaling factor (`noaux_tc` with one group)."""
    s = jax.nn.sigmoid(blocks.matmul(h, bw["moe.gate.w"], rnd))
    _, idx = jax.lax.top_k(s + bw["moe.gate.bias"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    w = (w / (jnp.sum(w, -1, keepdims=True) + 1e-20)
         * m["routed_scaling_factor"])
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
    first, held = m["first_expert"], m["n_routed_experts"]
    part = routed_part(h, route(h, bw, m, rnd)[:, first:first + held], bw,
                       rnd)
    shared = swiglu(h, bw["moe.shared.w1.w"], bw["moe.shared.w3.w"],
                    bw["moe.shared.w2.w"], rnd)
    return part + shared, part


def _freeze(m):
    return json.dumps(m, sort_keys=True)


def _upcast(bw):
    return {k: v.astype(F32) for k, v in bw.items()}      # this layer alone


@functools.lru_cache(maxsize=None)
def _layer_fn(sparse, frozen_m, precision):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, bw):
        bw = _upcast(bw)
        a, _ = attention_block(x, bw, m, rnd)
        y = x + a
        out, part = feed_forward(
            rms_norm(y, bw["mlp_norm.w"], m["rms_norm_eps"]), bw, m, sparse,
            rnd)
        return y + out, a, part

    return run


@functools.lru_cache(maxsize=None)
def _attention_fn(frozen_m, precision, topk, index_rotary):
    m, rnd = json.loads(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, bw):
        return attention_block(x.astype(F32), _upcast(bw), m, rnd, at, topk,
                               index_rotary)

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
    n = "glm%d." % i
    return {k[len(n):]: v for k, v in w.items() if k.startswith(n)}


def forward(w, ids, m, precision="float32", keep_streams=False,
            on_part=None):
    """ids (T,) -> (the stream (T, H) before the final norm; with
    `keep_streams` the stream before each layer (T, H) rounded to bfloat16
    (what the system's own stream is held in) as host arrays, else None;
    the held experts' part (T, H) of every sparse layer, or what
    `on_part(j, part)` makes of the j-th), layer by layer. What a caller does not ask for is not kept:
    at 17,408 positions a float32 (T, H) is 0.43 GB."""
    fm = _freeze(m)
    x = jnp.take(w["glm.emb"], jnp.asarray(ids), axis=0).astype(F32)
    streams, held = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(m["num_hidden_layers"]):
            if keep_streams:      # on the host: the layers' programs need
                streams.append(np.asarray(x.astype(BF16)))       # the room
            x, _, part = _layer_fn(i >= m["first_k_dense_replace"], fm,
                                   precision)(x, layer_weights(w, i))
            if part is not None:
                held.append(on_part(len(held), part) if on_part else part)
    return x, streams if keep_streams else None, held


def head_logits(w, x, at, m, precision="float32"):
    """The stream x (T, H) -> float32 logits (len(at), vocab) at `at`."""
    with jax.default_matmul_precision("highest"):
        return _head_fn(_freeze(m), precision)(
            x, jnp.asarray(at), w["glm.norm_f.w"], w["glm.head.w"])


def logits_at(w, ids, at, m, precision="float32"):
    """ids (T,) -> float32 logits (len(at), vocab) at the positions `at`."""
    return head_logits(w, forward(w, ids, m, precision)[0], at, m, precision)


def attention_at(w, layer, x, at, m, precision="float32", topk=None,
                 index_rotary=True):
    """Layer `layer`'s attention block over the GIVEN stream x (T, H) (the
    system's own, say), at the query rows `at` -> ((len(at), H) float32,
    (len(at), T) bool the keys each row kept). The rows are padded to whole
    query blocks, so that a few lengths share one compiled program."""
    at = np.asarray(at, np.int32)
    rows = np.pad(at, (0, (-len(at)) % QUERY_BLOCK), mode="edge")
    with jax.default_matmul_precision("highest"):
        out, kept = _attention_fn(_freeze(m), precision, topk, index_rotary)(
            jnp.asarray(x), jnp.asarray(rows), layer_weights(w, layer))
    return out[:len(at)], kept[:len(at)]


def routed_errors(got, want):
    """One sequence's held experts' part of one sparse layer, `got` against
    the reference's `want` (both (T, H)): at the positions the reference
    routes to a held expert, |got_t - want_t| in units of the
    root-mean-square |want_t| over those positions -> 1-D float32 on the
    host. Only those positions: with 16 of 256 experts held and 8 chosen,
    six positions in ten route nothing here and both parts are zero
    there."""
    g, r = jnp.asarray(got, F32), jnp.asarray(want, F32)
    norm2 = jnp.sum(r * r, -1)
    here = np.asarray(norm2 > 0)
    scale = jnp.sqrt(jnp.sum(norm2) / max(int(here.sum()), 1))
    err = jnp.sqrt(jnp.sum(jnp.square(g - r), -1)) / scale
    return np.asarray(err)[here]


def routed_gap(errors):
    """`errors`: per sparse layer the `routed_errors` of every sampled
    sequence -> per layer the MEDIAN over all of them, and of the layers
    the largest. The median, because top-k routing is not continuous: where
    two experts' scores nearly tie a sound lower precision may choose the
    other one, while a fault in the layer moves every position; over the
    whole sample's positions, because one short sequence routes a handful
    of positions here and three flips among them would be its median."""
    pooled = [np.concatenate(layer) for layer in errors if len(layer)]
    return max((float(np.median(e)) for e in pooled if e.size), default=0.0)


def overlap(got, want):
    """got, want (n, T) bool, the keys each of n queries kept: the smallest
    over the queries of |got and want| / max(|got|, |want|); and the number
    of queries whose two counts differ."""
    got, want = jnp.asarray(got, bool), jnp.asarray(want, bool)
    n_got, n_want = got.sum(-1), want.sum(-1)
    both = (got & want).sum(-1)
    share = both / jnp.maximum(jnp.maximum(n_got, n_want), 1)
    return float(share.min()), int((n_got != n_want).sum())
