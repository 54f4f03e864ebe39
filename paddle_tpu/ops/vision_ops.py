"""Lowerings for a native-resolution vision tower (a pre-norm ViT over the
patches of ONE image of any even grid ``h x w`` up to a bucket's patches):
the learned position table resized to the image's grid, the 2-D rotary term
and attention of every patch over the image's own patches.

The grid is a FEED (``Grid`` (1, 2) int64 ``[h, w]``), so one compiled
program serves every image of its patch bucket. A program's T rows hold the
image's ``h w`` patches in MERGE ORDER and padding after them: patch ``p`` is
the ``(p % 4) // 2``-th row and ``p % 2``-th column of the 2 x 2 group ``p //
4``, the groups row-major over ``(h / 2, w / 2)``; the four patches a merged
row is made of are then neighbours and the merge is a reshape (attention
does not mind the order; the table and the rotary term take each patch's own
row and column from its index).

Precision: the table's resize and the rotary angles are float32; attention
takes its operands as stored and accumulates in float32.
"""
import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .registry import register_op, single

F32 = jnp.float32


def patch_coords(t, grid, merge=(2, 2)):
    """Rows 0..t-1 of a program in merge order -> (row, col, real) int32 /
    bool (t,): the patch's place in the grid ``[h, w]`` (a (2,) int array)
    and whether the row holds a patch at all."""
    kh, kw = merge
    h, w = grid[0].astype(jnp.int32), grid[1].astype(jnp.int32)
    p = jnp.arange(t, dtype=jnp.int32)
    group, k = p // (kh * kw), p % (kh * kw)
    across = jnp.maximum(w // kw, 1)
    row = (group // across) * kh + k // kw
    col = (group % across) * kw + k % kw
    return row, col, p < h * w


def _cubic_taps(t, a=-0.75):
    """The four weights of a cubic convolution at offset t in [0, 1) from
    the second tap, ``a`` = -0.75 (torch's bicubic; Keys' kernel has -0.5)."""
    def near(x):
        return ((a + 2.0) * x - (a + 3.0)) * x * x + 1.0

    def far(x):
        return ((a * x - 5.0 * a) * x + 8.0 * a) * x - 4.0 * a

    return [far(t + 1.0), near(t), near(1.0 - t), far(2.0 - t)]


def bicubic_matrix(out_rows, out_size, in_size):
    """(out_rows, in_size) float32: row o < ``out_size`` (a traced int32)
    holds the weights along one axis of torch's ``F.interpolate(mode=
    "bicubic", align_corners=False)`` from ``in_size`` samples to
    ``out_size``: source coordinate ``(o + 0.5) in/out - 0.5``, four taps
    around its floor, a tap off the edge falls on the border sample."""
    o = jnp.arange(out_rows, dtype=jnp.int32)
    # ((2o + 1) in - out) / (2 out): whole numbers up to the one division
    src = ((2 * o + 1) * in_size - out_size).astype(F32) / (
        2 * out_size).astype(F32)
    first = jnp.floor(src)
    taps = _cubic_taps(src - first)
    at = jnp.arange(in_size, dtype=jnp.int32)[None, :]
    mat = jnp.zeros((out_rows, in_size), F32)
    for k, wk in enumerate(taps):
        idx = jnp.clip(first.astype(jnp.int32) - 1 + k, 0, in_size - 1)
        mat = mat + jnp.where(at == idx[:, None], wk[:, None], 0.0)
    return mat


@register_op("bicubic_table")
def _bicubic_table(ctx, ins, attrs):
    """A learned 2-D position table resized to an image's grid, torch's
    bicubic: Table (S, S', C), Grid (1, 2) ``[h, w]`` with ``h <= S``, ``w
    <= S'`` -> Out (T, C) float32, row p the resized table at patch p's row
    and column (merge order, :func:`patch_coords`), zeros where the row holds
    no patch. A grid equal to the table's takes it as it is (every tap's
    weight is then 0 or 1). Separable: ``A T B^T`` in float32 at the
    ``highest`` matmul precision."""
    table = ins["Table"][0].astype(F32)
    grid = ins["Grid"][0].reshape(-1)
    t = int(attrs["rows"])
    s0, s1 = table.shape[:2]
    a = bicubic_matrix(s0, grid[0].astype(jnp.int32), s0)
    b = bicubic_matrix(s1, grid[1].astype(jnp.int32), s1)
    resized = jnp.einsum("rs,stc,ut->ruc", a, table, b,
                         precision=lax.Precision.HIGHEST)
    row, col, real = patch_coords(t, grid)
    out = resized[jnp.minimum(row, s0 - 1), jnp.minimum(col, s1 - 1)]
    return single(jnp.where(real[:, None], out, 0.0))


@register_op("rotary_2d")
def _rotary_2d(ctx, ins, attrs):
    """The 2-D rotary term of a vision tower: X (B, T, heads, dh), row p a
    patch in merge order of the grid Grid (1, 2). A head's dh dimensions are
    dh / 2 adjacent pairs ``(x[2i], x[2i + 1])``; pair 2j turns by ``col x
    theta^(-4j/dh)`` and pair 2j + 1 by ``row x theta^(-4j/dh)``, in place.
    Float32, returned in X's dtype."""
    x = ins["X"][0]
    grid = ins["Grid"][0].reshape(-1)
    t, dh = x.shape[1], x.shape[-1]
    theta = float(attrs.get("theta", 10000.0))
    rates = jnp.asarray(
        theta ** -(np.arange(0, dh, 4, dtype=np.float64) / dh), F32)
    row, col, _ = patch_coords(t, grid)
    ang = jnp.stack([col.astype(F32)[:, None] * rates,
                     row.astype(F32)[:, None] * rates], -1).reshape(
                         t, dh // 2)
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    pairs = x.astype(F32).reshape(x.shape[:-1] + (dh // 2, 2))
    x1, x2 = pairs[..., 0], pairs[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return single(out.reshape(x.shape).astype(x.dtype))


TOWER_QUERY_BLOCK = 512


@register_op("tower_attention")
def _tower_attention(ctx, ins, attrs):
    """Attention of an image's patches over the image's own patches, not
    causal: Q, K, V (B, T, heads * dh), Grid (1, 2); the rows past ``h w``
    are padding and are seen by no query (what they compute is dropped by
    the caller). On an unsharded TPU program of at least FLASH_MIN_SEQ rows
    that FLASH_BLOCK divides, the flash forward kernel with the padding as
    its key mask, the heads padded with zeros to 128 lanes (72 -> 128: one
    pass of the MXU either way); everywhere else blocks of queries through
    XLA. Counted: ``ops.tower_attention.kernel`` / ``.blocks``."""
    from .. import observability as obs
    from .hybrid_ops import FLASH_BLOCK, FLASH_MIN_SEQ

    q, k, v = ins["Q"][0], ins["K"][0], ins["V"][0]
    grid = ins["Grid"][0].reshape(-1).astype(jnp.int32)
    heads = int(attrs["heads"])
    b, t, _ = q.shape
    dh = q.shape[-1] // heads
    scale = dh ** -0.5
    real = jnp.arange(t, dtype=jnp.int32) < grid[0] * grid[1]       # (T,)

    def split(x):
        return jnp.swapaxes(x.reshape(b, t, heads, dh), 1, 2)

    if (getattr(ctx, "platform", None) == "tpu"
            and not getattr(ctx, "mesh_axes", None)
            and t >= FLASH_MIN_SEQ and t % FLASH_BLOCK == 0):
        from .pallas_attention import flash_attention

        obs.inc("ops.tower_attention.kernel")
        pad = ((0, 0), (0, 0), (0, 0), (0, (-dh) % 128))
        out = flash_attention(
            *(jnp.pad(split(x), pad) for x in (q, k, v)),
            key_padding_mask=jnp.broadcast_to(
                jnp.where(real, 0.0, -1e30).astype(F32)[None, :], (b, t)),
            sm_scale=scale, causal=False, block_q=FLASH_BLOCK,
            block_k=FLASH_BLOCK)[..., :dh]
        return single(jnp.swapaxes(out, 1, 2).reshape(b, t, heads * dh))
    obs.inc("ops.tower_attention.blocks")
    blk = TOWER_QUERY_BLOCK if t % TOWER_QUERY_BLOCK == 0 else t
    kh, vh = split(k), split(v)

    def one(qb):                                        # (B, heads, blk, dh)
        s = jnp.einsum("bhqd,bhkd->bhqk", qb, kh,
                       preferred_element_type=F32) * scale
        p = jax.nn.softmax(jnp.where(real[None, None, None, :], s, -1e30), -1)
        return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), vh,
                          preferred_element_type=F32).astype(q.dtype)

    out = lax.map(one, jnp.moveaxis(
        split(q).reshape(b, heads, t // blk, blk, dh), 2, 0))
    out = jnp.moveaxis(out, 0, 2).reshape(b, heads, t, dh)
    return single(jnp.swapaxes(out, 1, 2).reshape(b, t, heads * dh))
