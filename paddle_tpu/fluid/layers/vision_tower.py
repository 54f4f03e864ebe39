"""Layers of a native-resolution vision tower over the patches of one image
of any even grid, the grid a feed: the learned position table resized to the
grid (torch's bicubic), the 2-D rotary term, attention of the image's patches
over themselves. The lowerings, and the order a program's rows hold the
patches in, are ``paddle_tpu/ops/vision_ops.py``.
"""
from ..layer_helper import LayerHelper
from .hybrid import _out, _param

__all__ = ["bicubic_table", "rotary_2d", "tower_attention"]


def bicubic_table(name, shape, grid, rows, dtype="bfloat16"):
    """The learned table ``<name>`` of ``shape`` (S, S', C) resized to the
    grid ``grid`` (1, 2) int64 ``[h, w]`` by torch's bicubic (cubic
    convolution with a = -0.75, ``align_corners`` false, no antialias) ->
    (rows, C) float32, row p the value at patch p's row and column (merge
    order), zeros past ``h w``."""
    helper = LayerHelper("bicubic_table")
    out = _out(helper, "float32", (int(rows), shape[-1]))
    helper.append_op(
        type="bicubic_table",
        inputs={"Table": [_param(helper, name, shape, dtype)],
                "Grid": [grid]},
        outputs={"Out": [out]}, attrs={"rows": int(rows)})
    return out


def rotary_2d(x, grid, theta=10000.0):
    """The 2-D rotary term over ``x`` (B, T, heads, dh), row p a patch in
    merge order of ``grid``: adjacent pairs, pair 2j turned by ``col x
    theta^(-4j/dh)``, pair 2j + 1 by ``row x theta^(-4j/dh)``. No
    parameter."""
    helper = LayerHelper("rotary_2d")
    out = _out(helper, x.dtype, x.shape)
    helper.append_op(type="rotary_2d", inputs={"X": [x], "Grid": [grid]},
                     outputs={"Out": [out]}, attrs={"theta": float(theta)})
    return out


def tower_attention(q, k, v, grid, heads):
    """Attention of an image's patches over the image's own, not causal:
    ``q``, ``k``, ``v`` (B, T, heads * dh); rows past the grid's ``h w`` are
    padding that no query sees. The flash kernel on an unsharded TPU program
    of 1,024 rows or more, blocks of queries through XLA elsewhere."""
    helper = LayerHelper("tower_attention")
    out = _out(helper, q.dtype, q.shape)
    helper.append_op(type="tower_attention",
                     inputs={"Q": [q], "K": [k], "V": [v], "Grid": [grid]},
                     outputs={"Out": [out]}, attrs={"heads": int(heads)})
    return out
