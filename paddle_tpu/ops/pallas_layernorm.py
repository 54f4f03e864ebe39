"""Fused LayerNorm as pallas TPU kernels.

TPU-native fused form of the reference's layer_norm op (ref:
paddle/fluid/operators/layer_norm_op.cc / .cu — a dedicated fused CUDA
kernel there too). One VMEM pass computes mean/var/normalize/affine per row
block; the backward kernel re-normalizes from saved (mean, rstd) and emits
per-block partial sums for d(scale)/d(bias) that the wrapper reduces — the
cross-row reduction is the only part XLA sees, so it fuses into neighbours.

Used by the layer_norm lowering when PADDLE_TPU_PALLAS_LN=1 on TPU
(default off: XLA's own LN fusion is already strong; flip after profiling
shows a win for your shape mix). Exact parity with the jnp lowering is
covered by tests in interpret mode.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["fused_layer_norm"]


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, mean_ref, rstd_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)                 # (bm, H)
    mean = jnp.mean(x, axis=1, keepdims=True)
    xc = x - mean
    var = jnp.mean(xc * xc, axis=1, keepdims=True)
    rstd = lax.rsqrt(var + eps)
    xhat = xc * rstd
    y = xhat * g_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)
    mean_ref[...] = mean
    rstd_ref[...] = rstd


def _bwd_kernel(x_ref, g_ref, mean_ref, rstd_ref, dy_ref, dx_ref, dg_ref,
                db_ref):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    rstd = rstd_ref[...]
    xhat = (x - mean_ref[...]) * rstd
    wdy = dy * g
    c1 = jnp.mean(wdy, axis=1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=1, keepdims=True)
    dx = (wdy - c1 - xhat * c2) * rstd
    dx_ref[...] = dx.astype(dx_ref.dtype)
    # per-block partials; wrapper sums over the grid axis
    dg_ref[0] = jnp.sum(dy * xhat, axis=0, keepdims=True)
    db_ref[0] = jnp.sum(dy, axis=0, keepdims=True)


# Mosaic wants the last two block dims to be multiples of the (8, 128)
# f32 tile or the whole array dim: rows ride in blocks of >= _SUBLANES
# (the wrapper pads n up to a multiple), gamma/beta are (1, H), the
# per-row stats (bm, 1), and the per-block dgamma/dbeta partials
# (1, 1, H) slices of a (grid, 1, H) array.
_SUBLANES = 8


def _row_block(n):
    for b in (256, 128, 64, 32, 16, _SUBLANES):
        if n % b == 0:
            return b
    raise ValueError("row count %d is not a multiple of %d" % (n, _SUBLANES))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x, gamma, beta, eps, interpret):
    """Returns (y, mean, rstd); mean/rstd are diagnostics — their
    cotangents are ignored in the backward (like the reference op's
    Mean/Variance outputs, which carry no gradient)."""
    return _ln_fwd(x, gamma, beta, eps, interpret)[0]


def _ln_fwd(x, gamma, beta, eps, interpret):
    n, h = x.shape
    bm = _row_block(n)
    grid = (n // bm,)
    rows = pl.BlockSpec((bm, h), lambda i: (i, 0))
    vec = pl.BlockSpec((1, h), lambda i: (0, 0))
    stat = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    y, mean, rstd = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[rows, vec, vec],
        out_specs=(rows, stat, stat),
        out_shape=(
            jax.ShapeDtypeStruct((n, h), x.dtype),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
            jax.ShapeDtypeStruct((n, 1), jnp.float32),
        ),
        interpret=interpret,
    )(x, gamma, beta)
    return (y, mean, rstd), (x, gamma, mean, rstd)


def _ln_bwd(eps, interpret, res, dys):
    dy = dys[0]  # stats cotangents (dys[1:]) are ignored by design
    x, gamma, mean, rstd = res
    n, h = x.shape
    bm = _row_block(n)
    grid = (n // bm,)
    rows = pl.BlockSpec((bm, h), lambda i: (i, 0))
    stat = pl.BlockSpec((bm, 1), lambda i: (i, 0))
    part = pl.BlockSpec((1, 1, h), lambda i: (i, 0, 0))
    dx, dg_part, db_part = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[rows, pl.BlockSpec((1, h), lambda i: (0, 0)), stat, stat,
                  rows],
        out_specs=(rows, part, part),
        out_shape=(
            jax.ShapeDtypeStruct((n, h), x.dtype),
            jax.ShapeDtypeStruct((grid[0], 1, h), jnp.float32),
            jax.ShapeDtypeStruct((grid[0], 1, h), jnp.float32),
        ),
        interpret=interpret,
    )(x, gamma, mean, rstd, dy)
    dg = jnp.sum(dg_part, axis=(0, 1)).reshape(gamma.shape)
    db = jnp.sum(db_part, axis=(0, 1)).reshape(gamma.shape)
    return dx, dg.astype(gamma.dtype), db.astype(gamma.dtype)


_ln.defvjp(_ln_fwd, _ln_bwd)


def fused_layer_norm(x, gamma=None, beta=None, eps=1e-5, interpret=False,
                     return_stats=False):
    """LayerNorm over the last axis of a 2D-reshapeable x.

    x: (..., H); gamma/beta: (H,) or None. With return_stats=True also
    returns (mean, rstd) shaped like x's leading axes — the kernel computed
    them anyway; callers must not recompute (that would double the memory
    passes this kernel exists to avoid).
    """
    shape = x.shape
    h = shape[-1]
    xf = x.reshape(-1, h)
    n = xf.shape[0]
    # pad/slice sit OUTSIDE the custom_vjp so autodiff zeroes the pad
    # rows' cotangents for free
    pad = -n % _SUBLANES
    if pad:
        xf = jnp.pad(xf, ((0, pad), (0, 0)))
    if gamma is None:
        gamma = jnp.ones((h,), jnp.float32)
    if beta is None:
        beta = jnp.zeros((h,), jnp.float32)
    y, mean, rstd = _ln(
        xf, gamma.reshape(1, h), beta.reshape(1, h), float(eps), interpret
    )
    y = y[:n].reshape(shape)
    if return_stats:
        return (
            y,
            mean[:n, 0].reshape(shape[:-1]),
            rstd[:n, 0].reshape(shape[:-1]),
        )
    return y
