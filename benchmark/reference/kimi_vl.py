"""Plain reference of the served Kimi-VL decoder and its vision tower: float32
`jax.numpy` at matmul precision "highest", no cache, no absorbed products, no
kernels, nothing of paddle_tpu. The decoder is one full causal forward pass
over prompt + served tokens, the attention the EXPANDED form only, a loop over
blocks of queries (the scores alive are heads x QUERY_BLOCK x T); the experts
a loop over all 64; the tower an image at a time.

Decoder layer l over the stream x (T, 2048), u = RMSNorm(x), eps 1e-5:
  q = u Wq -> 16 heads x [q_nope 128 | q_rope 64] (no low rank: `q_lora_rank`
  null); [ckv 512 | k_rope 64] = u Wkv_a; ckv = RMSNorm(ckv); q_rope and
  k_rope turned at the token's position (theta 800,000, no scaling,
  interleaved pairs (2i, 2i + 1) handed on de-interleaved as the family's
  `modeling_deepseek.py` does), k_rope one for all heads;
  k[s, h] = [ckv[s] Wuk[h] | k_rope[s]], v[s, h] = ckv[s] Wuv[h] (128);
  o_h = softmax over ALL s <= t of q . k x 192^-1/2, times v; y = x + concat_h
  (o_h) Wo; w = RMSNorm(y); layer 0 adds SwiGLU(11,264); a sparse layer adds,
  over the 6 experts of largest sigmoid(w Wr) + bias, 2.446 s_e / (sum_chosen
  s + 1e-20) x expert_e(w), plus ONE SwiGLU(2,816) (the two shared experts).
Final RMSNorm, untied head. A media row takes its plain position.

Tower over an image of h x w patches (h, w even; every size `assumed`, the
configuration file's `vision_config`): pixels / 255, then (. - 0.5) / 0.5;
e = patch (3 x 14 x 14, channel first) We + be + the learned table (64, 64,
1152) resized to (h, w) by torch's bicubic (`bicubic_matrix`: cubic
convolution with a = -0.75, align_corners false, no antialias, border taps
clamped); 27 blocks z = z + Wo Attn(LN0 z), z = z + W2 gelu_tanh(W1 LN1 z),
attention over the image's own patches, not causal, q and k turned by the
2-D rotary term (`rotary_2d_angles`); a final LayerNorm; projector: LN(1152) a patch,
the patches (2r..2r+1, 2c..2c+1) concatenated row-major to 4,608, W2 gelu(W1
.) with the exact GELU -> h w / 4 rows of 2,048, which REPLACE the embedding
rows at the prompt's `media_placeholder_token_id` positions, in order.

`m` is the configuration file's published keys plus `vision_config`,
`media_placeholder_token_id`, and `num_hidden_layers` = the depth of the cut
(`benchmark/costs_kimi_vl.sizes`). `m["fault"]` plants one departure (the
controls of `benchmark/controls_kimi_vl.py`).

The seeded weights are made on the device, leaf by leaf, and kept as the
bfloat16 values the system holds (the router's score correction float32):
every matrix and bias normal(0, initializer_range), norm gains 1 + that.
"""
import functools
import json

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks
from .glm5_lm import (_leaf_maker, routed_errors, routed_gap,  # noqa: F401
                      routed_part)
from .laguna_lm import rms_gap, rms_norm, swiglu, token_gaps  # noqa: F401

BF16, F32 = jnp.bfloat16, jnp.float32
QUERY_BLOCK = 128
FAULTS = ("resize_keys", "table_cropped", "no_rope_2d", "rope_2d_swapped",
          "rope_not_interleaved", "scale_one", "softmax_scores",
          "shared_narrow", "media_shifted")


def weight_shapes(m):
    """{name: (shape, dtype, how it is initialised)}."""
    h, heads = m["hidden_size"], m["num_attention_heads"]
    kvr = m["kv_lora_rank"]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    out = {"kimi.emb": ((m["vocab_size"], h), BF16, "normal"),
           "kimi.head.w": ((h, m["vocab_size"]), BF16, "normal"),
           "kimi.norm_f.w": ((h,), BF16, "gain")}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), BF16, "normal"),
                    name + ".w3.w": ((h, width), BF16, "normal"),
                    name + ".w2.w": ((width, h), BF16, "normal")})

    for i in range(m["num_hidden_layers"]):
        n = "kimi%d." % i
        out.update({
            n + "attn_norm.w": ((h,), BF16, "gain"),
            n + "mlp_norm.w": ((h,), BF16, "gain"),
            n + "mla.q.w": ((h, heads * (nope + rope)), BF16, "normal"),
            n + "mla.kv_a.w": ((h, kvr + rope), BF16, "normal"),
            n + "mla.kv_norm.w": ((kvr,), BF16, "gain"),
            n + "mla.uk.w": ((kvr, heads * nope), BF16, "normal"),
            n + "mla.uv.w": ((kvr, heads * vd), BF16, "normal"),
            n + "mla.o.w": ((heads * vd, h), BF16, "normal")})
        if i < m["first_k_dense_replace"]:
            ffn(n + "mlp", m["intermediate_size"])
            continue
        f, e = m["moe_intermediate_size"], m["n_routed_experts"]
        ffn(n + "moe.shared", f * m["n_shared_experts"])
        out.update({
            n + "moe.gate.w": ((h, e), BF16, "normal"),
            n + "moe.gate.bias": ((e,), F32, "normal"),
            n + "moe.experts.w1": ((e, h, f), BF16, "normal"),
            n + "moe.experts.w3": ((e, h, f), BF16, "normal"),
            n + "moe.experts.w2": ((e, f, h), BF16, "normal")})
    v = m["vision_config"]
    d, side, g = v["hidden_size"], v["init_pos_emb_height"], v["patch_size"]
    merged = d * v["merge_kernel_size"][0] * v["merge_kernel_size"][1]
    out.update({
        "kimi.vit.patch.w": ((3 * g * g, d), BF16, "normal"),
        "kimi.vit.patch.b": ((d,), BF16, "normal"),
        "kimi.vit.pos": ((side, v["init_pos_emb_width"], d), BF16, "normal"),
        "kimi.vit.ln_f.w": ((d,), BF16, "gain"),
        "kimi.vit.ln_f.b": ((d,), BF16, "normal"),
        "kimi.proj.ln.w": ((d,), BF16, "gain"),
        "kimi.proj.ln.b": ((d,), BF16, "normal"),
        "kimi.proj.fc1.w": ((merged, merged), BF16, "normal"),
        "kimi.proj.fc1.b": ((merged,), BF16, "normal"),
        "kimi.proj.fc2.w": ((merged, h), BF16, "normal"),
        "kimi.proj.fc2.b": ((h,), BF16, "normal")})
    for j in range(v["num_hidden_layers"]):
        n = "kimi.vit%d." % j
        out.update({
            n + "ln0.w": ((d,), BF16, "gain"), n + "ln0.b": ((d,), BF16, "normal"),
            n + "ln1.w": ((d,), BF16, "gain"), n + "ln1.b": ((d,), BF16, "normal"),
            n + "qkv.w": ((d, 3 * d), BF16, "normal"),
            n + "qkv.b": ((3 * d,), BF16, "normal"),
            n + "o.w": ((d, d), BF16, "normal"),
            n + "o.b": ((d,), BF16, "normal"),
            n + "fc1.w": ((d, v["intermediate_size"]), BF16, "normal"),
            n + "fc1.b": ((v["intermediate_size"],), BF16, "normal"),
            n + "fc2.w": ((v["intermediate_size"], d), BF16, "normal"),
            n + "fc2.b": ((d,), BF16, "normal")})
    return out


def make_weights(m, seed):
    """Every leaf from the seed, on the default device, one jitted draw per
    leaf (leaves of one shape share a program)."""
    key = blocks.mask_key(seed)
    std = float(m.get("initializer_range", 0.02))
    return {name: _leaf_maker(tuple(shape), dtype, how, std)(
                jax.random.fold_in(key, i))
            for i, (name, (shape, dtype, how)) in enumerate(
                sorted(weight_shapes(m).items()))}


# -- the tower ---------------------------------------------------------------
def cubic_taps(t, a=-0.75):
    """The four weights of a cubic convolution at offset t in [0, 1) from
    the second tap (torch's `get_cubic_upsample_coefficients`)."""

    def near(x):      # |x| <= 1
        return ((a + 2.0) * x - (a + 3.0)) * x * x + 1.0

    def far(x):       # 1 < |x| < 2
        return ((a * x - 5.0 * a) * x + 8.0 * a) * x - 4.0 * a

    return [far(t + 1.0), near(t), near(1.0 - t), far(2.0 - t)]


def bicubic_matrix(out_size, in_size):
    """(out_size, in_size) float64 numpy: row o holds the weights of torch's
    `F.interpolate(mode="bicubic", align_corners=False)` along one axis:
    source coordinate (o + 0.5) in/out - 0.5, four taps around its floor, a
    tap off the edge falls on the border sample."""
    mat = np.zeros((out_size, in_size))
    for o in range(out_size):
        src = (o + 0.5) * in_size / out_size - 0.5
        first = int(np.floor(src))
        for k, wk in enumerate(cubic_taps(src - first)):
            mat[o, min(max(first - 1 + k, 0), in_size - 1)] += wk
    return mat


def resized_table(table, h, w, fault=None):
    """The learned table (S, S', D) float32 -> (h, w, D), h <= S, w <= S'.
    The two matrices are padded with zero rows to the table's own sides, so
    that every grid goes through one product of one shape."""
    if fault == "table_cropped":
        return table[:h, :w]
    if fault == "resize_keys":
        return jax.image.resize(table, (h, w, table.shape[-1]), "bicubic")
    if (h, w) == table.shape[:2]:
        return table
    rows, cols = np.zeros(table.shape[:1] * 2), np.zeros(table.shape[1:2] * 2)
    rows[:h] = bicubic_matrix(h, table.shape[0])
    cols[:w] = bicubic_matrix(w, table.shape[1])
    return jnp.einsum("rs,stc,ut->ruc", jnp.asarray(rows, F32), table,
                      jnp.asarray(cols, F32), precision="highest")[:h, :w]


def rotary_2d_angles(h, w, dh, fault=None):
    """-> (cos, sin) float32 numpy (h w, dh / 2), row-major patches: a head's
    dh dimensions are dh / 2 adjacent pairs; pair 2j turns by col x
    10000^(-4j/dh) and pair 2j + 1 by row x 10000^(-4j/dh). Made in float64
    on the host."""
    rates = 10000.0 ** -(np.arange(0, dh, 4, dtype=np.float64) / dh)
    row = np.repeat(np.arange(h), w)[:, None]
    col = np.tile(np.arange(w), h)[:, None]
    if fault == "rope_2d_swapped":
        row, col = col, row
    ang = np.stack([col * rates, row * rates], -1).reshape(h * w, dh // 2)
    if fault == "no_rope_2d":
        ang = np.zeros_like(ang)
    return np.cos(ang).astype(np.float32), np.sin(ang).astype(np.float32)


def rotary_2d(x, cos, sin):
    """x (T, heads, dh) turned in adjacent pairs by (T, dh / 2) angles."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    cos, sin = cos[:, None, :], sin[:, None, :]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def patches_of(pixels, g):
    """uint8 (g h, g w, 3) -> float32 numpy (h w, 3 g g): normalised, a
    patch a row, row-major over the grid, channel first inside a patch."""
    x = (np.asarray(pixels, np.float32) / np.float32(255.0)
         - np.float32(0.5)) / np.float32(0.5)
    h, w = x.shape[0] // g, x.shape[1] // g
    x = x.reshape(h, g, w, g, 3).transpose(0, 2, 4, 1, 3)
    return x.reshape(h * w, 3 * g * g)


def merge_groups(h, w, kh, kw):
    """(h w / (kh kw), kh kw) int32: the patches (row-major indices) that a
    merged row is made of, row-major inside the block and over the blocks."""
    at = np.arange(h * w, dtype=np.int32).reshape(h // kh, kh, w // kw, kw)
    return at.transpose(0, 2, 1, 3).reshape(-1, kh * kw)


def _pad_rows(x, rows):
    x = np.asarray(x)
    return np.concatenate([x, np.zeros((rows - len(x),) + x.shape[1:],
                                       x.dtype)])


def _vit_attention(q, k, v, real, rnd):
    """q, k, v (T, heads, dh): every patch sees every REAL patch (`real`
    (T,) bool: the rows past an image's own are padding)."""
    t, heads, dh = q.shape
    pad = (-t) % QUERY_BLOCK

    def one(qb):
        s = jnp.einsum("qhd,khd->hqk", rnd(qb), rnd(k),
                       precision="highest") * dh ** -0.5
        s = jnp.where(real[None, None, :], s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", rnd(jax.nn.softmax(s, -1)), rnd(v),
                          precision="highest")

    out = jax.lax.map(one, jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(
        -1, QUERY_BLOCK, heads, dh))
    return out.reshape(-1, heads * dh)[:t]


def tower_attention(z, lw, cos, sin, real, heads, rnd):
    """What a tower block's attention adds to the stream z (T, D)."""
    t, d = z.shape
    u = blocks.layer_norm(z, lw["ln0.w"], lw["ln0.b"])
    qkv = blocks.dense(u, lw["qkv.w"], lw["qkv.b"], rnd).reshape(
        t, 3, heads, d // heads)
    a = _vit_attention(rotary_2d(qkv[:, 0], cos, sin),
                       rotary_2d(qkv[:, 1], cos, sin), qkv[:, 2], real, rnd)
    return blocks.dense(a, lw["o.w"], lw["o.b"], rnd)


def tower(f, patches, pos, cos, sin, real, groups, m, rnd):
    """One image's T rows (its h w patches row-major, then padding that no
    patch sees): `patches` (T, 3 g g) normalised, `pos` (T, D) the resized
    table's rows, `cos` / `sin` (T, dh / 2) the 2-D rotary angles, `real`
    (T,), `groups` (T / 4, 4) the patches of each merged row -> the
    (projector's rows (T / 4, hidden) float32, the image's own first; the
    stream before each block (blocks, T, D) rounded to bfloat16). All that
    depends on the grid is an ARGUMENT, so images of every grid share one
    compiled program."""
    v = m["vision_config"]
    heads = v["num_attention_heads"]
    z = blocks.dense(patches, f["kimi.vit.patch.w"], f["kimi.vit.patch.b"],
                     rnd) + pos

    def block(z, lw):       # the 27 blocks are alike: one traced, scanned
        y = z + tower_attention(z, lw, cos, sin, real, heads, rnd)
        u = blocks.layer_norm(y, lw["ln1.w"], lw["ln1.b"])
        return y + blocks.dense(
            jax.nn.gelu(blocks.dense(u, lw["fc1.w"], lw["fc1.b"], rnd),
                        approximate=True), lw["fc2.w"], lw["fc2.b"],
            rnd), z.astype(BF16)

    z, before = jax.lax.scan(block, z, blocks.stack_layers(
        f, "kimi.vit%d.", v["num_hidden_layers"]))
    z = blocks.layer_norm(z, f["kimi.vit.ln_f.w"], f["kimi.vit.ln_f.b"])
    z = blocks.layer_norm(z, f["kimi.proj.ln.w"], f["kimi.proj.ln.b"])
    z = jnp.take(z, groups, axis=0).reshape(groups.shape[0], -1)
    return blocks.dense(blocks.gelu(blocks.dense(
        z, f["kimi.proj.fc1.w"], f["kimi.proj.fc1.b"], rnd)),
        f["kimi.proj.fc2.w"], f["kimi.proj.fc2.b"], rnd), before


@functools.lru_cache(maxsize=None)
def _tower_fn(frozen_m, precision):
    m, rnd = _thaw(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(w, patches, pos, cos, sin, real, groups):
        f = {k: x.astype(F32) for k, x in w.items()}
        return tower(f, patches, pos, cos, sin, real, groups, m, rnd)

    return run


@functools.lru_cache(maxsize=None)
def _tower_attention_fn(heads, precision):
    rnd = blocks.rounder(precision)

    @jax.jit
    def run(z, lw, cos, sin, real):
        return tower_attention(z.astype(F32), _upcast(lw), cos, sin, real,
                               heads, rnd)

    return run


def _grid_feeds(h, wd, m, fault=None):
    """What the tower's programs are told of a grid, padded to the most
    patches an image may hold (`in_token_limit`): (cos, sin, real)."""
    v = m["vision_config"]
    rows = v["in_token_limit"]
    cos, sin = rotary_2d_angles(
        h, wd, v["hidden_size"] // v["num_attention_heads"], fault)
    return (_pad_rows(cos, rows), _pad_rows(sin, rows),
            np.arange(rows) < h * wd)


def _towers(w, images, m, precision):
    """Each image through the tower's one program -> [(the projector's rows
    (h w / 4, hidden), the resized table (h w, D), the streams before the
    blocks (blocks, T, D))]."""
    v, fault = m["vision_config"], m.get("fault")
    g, rows = v["patch_size"], v["in_token_limit"]
    kh, kw = v["merge_kernel_size"]
    tw = {k: x for k, x in w.items()
          if k.startswith("kimi.vit") or k.startswith("kimi.proj")}
    run = _tower_fn(_freeze({"vision_config": v}), precision)
    with jax.default_matmul_precision("highest"):
        for pixels in images:
            h, wd = pixels.shape[0] // g, pixels.shape[1] // g
            pos = resized_table(w["kimi.vit.pos"].astype(F32), h, wd,
                                fault).reshape(h * wd, -1)
            groups = merge_groups(h, wd, kh, kw)
            got, before = run(
                tw, _pad_rows(patches_of(pixels, g), rows),
                _pad_rows(pos, rows), *_grid_feeds(h, wd, m, fault),
                _pad_rows(groups, rows // (kh * kw)))
            yield got[:len(groups)], pos, before


def tower_rows(w, images, m, precision="float32"):
    """`images`: uint8 arrays (14 h, 14 w, 3) -> ([the projector's rows of
    each (h w / 4, hidden)], [its resized table (h w, D)])."""
    out = [(rows, pos) for rows, pos, _ in _towers(w, images, m, precision)]
    return [o[0] for o in out], [o[1] for o in out]


def tower_streams(w, images, m, at):
    """-> per image {block: the stream before it (h w, D) bfloat16, host}
    for the blocks `at`."""
    g = m["vision_config"]["patch_size"]
    return [{j: np.asarray(before[j][:px.shape[0] // g * (px.shape[1] // g)])
             for j in at}
            for px, (_, _, before) in zip(images,
                                          _towers(w, images, m, "float32"))]


def tower_attention_at(w, block, z, h, wd, m, precision="float32"):
    """Tower block `block`'s attention over the GIVEN stream z (h w, D),
    row-major patches -> what it adds (h w, D) float32."""
    v = m["vision_config"]
    n = "kimi.vit%d." % block
    lw = {k[len(n):]: x for k, x in w.items() if k.startswith(n)}
    with jax.default_matmul_precision("highest"):
        out = _tower_attention_fn(v["num_attention_heads"], precision)(
            jnp.asarray(_pad_rows(np.asarray(z, np.float32),
                                  v["in_token_limit"])),
            lw, *_grid_feeds(h, wd, m, m.get("fault")))
    return out[:h * wd]


# -- the decoder -------------------------------------------------------------
def rotary(x, positions, m, interleaved=True):
    """x (T, ..., rot) at `positions` (T,): interleaved pairs (x[2i], x[2i +
    1]), pair i turned by position x theta^(-2i/rot), handed on
    de-interleaved (pair i at i and i + rot/2); float32."""
    rot = x.shape[-1]
    rates = float(m["rope_theta"]) ** -(
        np.arange(0, rot, 2, dtype=np.float64) / rot)
    ang = positions.astype(F32)[:, None] * jnp.asarray(rates, F32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (rot // 2,)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    if interleaved:
        pairs = x.reshape(x.shape[:-1] + (rot // 2, 2))
        x1, x2 = pairs[..., 0], pairs[..., 1]
    else:
        x1, x2 = x[..., :rot // 2], x[..., rot // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_block(x, bw, m, rnd, at=None):
    """What the layer's attention block adds to the stream x (T, H) at the
    query rows `at` (default: every row) -> (n, H)."""
    heads, t = m["num_attention_heads"], x.shape[0]
    nope, rope, vd = (m["qk_nope_head_dim"], m["qk_rope_head_dim"],
                      m["v_head_dim"])
    rank, eps = m["kv_lora_rank"], m["rms_norm_eps"]
    paired = m.get("fault") != "rope_not_interleaved"
    u = rms_norm(x, bw["attn_norm.w"], eps)
    every = jnp.arange(t, dtype=jnp.int32)
    at = every if at is None else jnp.asarray(at, jnp.int32)
    n = at.shape[0]
    rows = jnp.pad(at, (0, (-n) % QUERY_BLOCK))
    q = blocks.matmul(jnp.take(u, rows, axis=0), bw["mla.q.w"],
                      rnd).reshape(-1, heads, nope + rope)
    q_rope = rotary(q[..., nope:], rows, m, paired)
    kv = blocks.matmul(u, bw["mla.kv_a.w"], rnd)
    ckv = rms_norm(kv[:, :rank], bw["mla.kv_norm.w"], eps)
    k_rope = rotary(kv[:, rank:], every, m, paired)             # (T, rope)
    k_nope = blocks.matmul(ckv, bw["mla.uk.w"], rnd).reshape(t, heads, nope)
    v = blocks.matmul(ckv, bw["mla.uv.w"], rnd).reshape(t, heads, vd)

    def one(args):
        qn, qr, ib = args
        seen = every[None, :] <= ib[:, None]                    # (QB, T)
        scores = (jnp.einsum("qhd,khd->hqk", rnd(qn), rnd(k_nope),
                             precision="highest")
                  + jnp.einsum("qhd,kd->hqk", rnd(qr), rnd(k_rope),
                               precision="highest")) * (nope + rope) ** -0.5
        probs = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", rnd(probs), rnd(v),
                          precision="highest").reshape(QUERY_BLOCK,
                                                       heads * vd)

    o = jax.lax.map(one, (q[..., :nope].reshape(-1, QUERY_BLOCK, heads, nope),
                          q_rope.reshape(-1, QUERY_BLOCK, heads, rope),
                          rows.reshape(-1, QUERY_BLOCK)))
    return blocks.matmul(o.reshape(-1, heads * vd), bw["mla.o.w"], rnd)[:n]


def route(h, bw, m, rnd):
    """-> (T, experts) float32: each token's weight on every expert (zero on
    those it did not choose): `noaux_tc` with one group."""
    logits = blocks.matmul(h, bw["moe.gate.w"], rnd)
    if m.get("fault") == "softmax_scores":
        s = jax.nn.softmax(logits, -1)
    else:
        s = jax.nn.sigmoid(logits)
    _, idx = jax.lax.top_k(s + bw["moe.gate.bias"], m["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, -1)
    scale = 1.0 if m.get("fault") == "scale_one" else m[
        "routed_scaling_factor"]
    w = w / (jnp.sum(w, -1, keepdims=True) + 1e-20) * scale
    return jnp.zeros_like(s).at[jnp.arange(h.shape[0])[:, None], idx].set(w)


def feed_forward(h, bw, m, sparse, rnd):
    """-> (what the layer's second half adds (T, H), the routed experts' part
    of it or None)."""
    if not sparse:
        return swiglu(h, bw["mlp.w1.w"], bw["mlp.w3.w"], bw["mlp.w2.w"],
                      rnd), None
    part = routed_part(h, route(h, bw, m, rnd), bw, rnd)
    s1, s3, s2 = (bw["moe.shared.w%d.w" % k] for k in (1, 3, 2))
    if m.get("fault") == "shared_narrow":
        f = m["moe_intermediate_size"]
        s1, s3, s2 = s1[:, :f], s3[:, :f], s2[:f]
    return part + swiglu(h, s1, s3, s2, rnd), part


def _freeze(m):
    return json.dumps(m, sort_keys=True)


def _thaw(frozen):
    return json.loads(frozen)


def _upcast(bw):
    return {k: v.astype(F32) for k, v in bw.items()}      # this layer alone


@functools.lru_cache(maxsize=None)
def _layer_fn(sparse, frozen_m, precision):
    m, rnd = _thaw(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, bw):
        bw = _upcast(bw)
        a = attention_block(x, bw, m, rnd)
        y = x + a
        out, part = feed_forward(
            rms_norm(y, bw["mlp_norm.w"], m["rms_norm_eps"]), bw, m, sparse,
            rnd)
        return y + out, a, part

    return run


@functools.lru_cache(maxsize=None)
def _attention_fn(frozen_m, precision):
    m, rnd = _thaw(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, bw):
        return attention_block(x.astype(F32), _upcast(bw), m, rnd, at)

    return run


@functools.lru_cache(maxsize=None)
def _ffn_fn(sparse, frozen_m, precision):
    m, rnd = _thaw(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(y, bw):
        bw = _upcast(bw)
        return feed_forward(rms_norm(y.astype(F32), bw["mlp_norm.w"],
                                     m["rms_norm_eps"]), bw, m, sparse,
                            rnd)[0]

    return run


@functools.lru_cache(maxsize=None)
def _head_fn(frozen_m, precision):
    m, rnd = _thaw(frozen_m), blocks.rounder(precision)

    @jax.jit
    def run(x, at, norm_w, head_w):
        x = rms_norm(jnp.take(x, at, axis=0), norm_w.astype(F32),
                     m["rms_norm_eps"])
        return blocks.matmul(x, head_w.astype(F32), rnd)

    return run


def layer_weights(w, i):
    n = "kimi%d." % i
    return {k[len(n):]: v for k, v in w.items() if k.startswith(n)}


def embed(w, ids, m, media=None):
    """ids (T,) -> the stream into layer 0 (T, H) float32: the embedding's
    rows, those at the placeholder id REPLACED by `media` (n, H), in
    order."""
    ids = np.asarray(ids)
    x = jnp.take(w["kimi.emb"], jnp.asarray(ids), axis=0).astype(F32)
    at = np.flatnonzero(ids == m["media_placeholder_token_id"])
    if media is None or not len(at):
        return x
    media = jnp.asarray(media, F32)[:len(at)]
    if m.get("fault") == "media_shifted":
        at = np.minimum(at + 1, len(ids) - 1)
    return x.at[jnp.asarray(at[:media.shape[0]])].set(media)


def forward(w, ids, m, precision="float32", media=None, keep_streams=False,
            on_part=None):
    """ids (T,) -> (the stream (T, H) before the final norm; with
    `keep_streams` the stream before each layer rounded to bfloat16 as host
    arrays, else None; the routed experts' part (T, H) of every sparse
    layer, or what `on_part(j, part)` makes of the j-th)."""
    fm = _freeze(m)
    x = embed(w, ids, m, media)
    streams, held = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(m["num_hidden_layers"]):
            if keep_streams:
                streams.append(np.asarray(x.astype(BF16)))
            x, _, part = _layer_fn(i >= m["first_k_dense_replace"], fm,
                                   precision)(x, layer_weights(w, i))
            if part is not None:
                held.append(on_part(len(held), part) if on_part else part)
    return x, streams if keep_streams else None, held


def head_logits(w, x, at, m, precision="float32"):
    """The stream x (T, H) -> float32 logits (len(at), vocab) at `at`."""
    with jax.default_matmul_precision("highest"):
        return _head_fn(_freeze(m), precision)(
            x, jnp.asarray(at), w["kimi.norm_f.w"], w["kimi.head.w"])


def logits_at(w, ids, at, m, precision="float32", media=None):
    return head_logits(w, forward(w, ids, m, precision, media)[0], at, m,
                       precision)


def attention_at(w, layer, x, at, m, precision="float32"):
    """Layer `layer`'s attention block over the GIVEN stream x (T, H) at the
    query rows `at` -> (len(at), H) float32. The rows are padded to whole
    query blocks, so that a few lengths share one compiled program."""
    at = np.asarray(at, np.int32)
    rows = np.pad(at, (0, (-len(at)) % QUERY_BLOCK), mode="edge")
    with jax.default_matmul_precision("highest"):
        out = _attention_fn(_freeze(m), precision)(
            jnp.asarray(x), jnp.asarray(rows), layer_weights(w, layer))
    return out[:len(at)]


def ffn_at(w, layer, y, m, precision="float32"):
    """Layer `layer`'s second half over the GIVEN rows y (n, H) of the stream
    after the layer's attention -> what it adds (n, H) float32."""
    with jax.default_matmul_precision("highest"):
        return _ffn_fn(layer >= m["first_k_dense_replace"], _freeze(m),
                       precision)(jnp.asarray(y), layer_weights(w, layer))
