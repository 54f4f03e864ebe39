"""Plain jax.numpy building blocks of the references: float32 arithmetic,
matrix products at precision "highest", no kernels, no cache, no batching
tricks. Imports nothing of paddle_tpu.

`precision` names how the operands of every matrix product are rounded
before the float32 product:

  float32   not at all: the reference proper
  bfloat16  what the configurations state (AMP compute for BERT, the TPU's
            default one-pass product for the served GPT)
  float8    e4m3, the nearest precision below bfloat16: the control that
            `correct` has to fail
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = {"float32": None, "bfloat16": jnp.bfloat16,
              "float8": jnp.float8_e4m3fn}


def rounder(precision):
    dtype = PRECISIONS[precision]
    if dtype is None:
        return lambda x: x
    return lambda x: x.astype(dtype).astype(jnp.float32)


def matmul(a, b, rnd):
    return jnp.matmul(rnd(a), rnd(b), precision="highest",
                      preferred_element_type=jnp.float32)


def dense(x, w, b, rnd):
    return matmul(x, w, rnd) + b


def layer_norm(x, w, b, eps=1e-5):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * w + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def dropout(x, key, p):
    """Inverted dropout (upscale in training): each element kept with
    probability 1 - p, by a Bernoulli mask of the reference's own."""
    if not p:
        return x
    keep = jax.random.bernoulli(key, 1.0 - p, x.shape)
    return jnp.where(keep, x / (1.0 - p), 0.0)


def attention(q, k, v, mask, heads, rnd, on_probs=None):
    """q (..., Tq, H), k/v (..., Tk, H), additive mask broadcastable to
    (..., heads, Tq, Tk) or None -> (..., Tq, H). `on_probs`, if given, is
    applied to the attention probabilities (training's dropout)."""
    dh = q.shape[-1] // heads

    def split(t):
        t = t.reshape(t.shape[:-1] + (heads, dh))
        return jnp.swapaxes(t, -2, -3)

    scores = matmul(split(q), jnp.swapaxes(split(k), -1, -2), rnd) * dh ** -0.5
    if mask is not None:
        scores = scores + mask
    probs = jax.nn.softmax(scores, -1)
    if on_probs is not None:
        probs = on_probs(probs)
    ctx = matmul(probs, split(v), rnd)
    ctx = jnp.swapaxes(ctx, -2, -3)
    return ctx.reshape(ctx.shape[:-2] + (heads * dh,))


def stack_layers(w, prefix, n):
    """{"<prefix % i><part>": leaf} for i < n -> {part: the n leaves
    stacked}, for a scan over layers that are alike."""
    first = prefix % 0
    parts = [k[len(first):] for k in w if k.startswith(first)]
    return {part: jnp.stack([w[prefix % i + part] for i in range(n)])
            for part in parts}


def seed_key(seed):
    """A PRNG key from any whole number, beyond 32 bits too."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def mask_key(seed):
    """A key for dropout masks from any whole number. Its bits come from
    XLA's own generator (rbg): threefry doubled the reference's time on
    the chip, and nothing here needs its guarantees."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF, impl="rbg")
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


@functools.lru_cache(maxsize=None)
def _maker(layout, std):
    """One jitted program per layout: a single normal draw, cut into the
    leaves (one draw, not one per leaf: it compiles in a second)."""
    total = sum(int(np.prod(shape)) for _, shape in layout)

    @jax.jit
    def make(key):
        flat = std * jax.random.normal(key, (total,), jnp.float32)
        out, at = {}, 0
        for name, shape in layout:
            n = int(np.prod(shape))
            x = flat[at:at + n].reshape(shape)
            at += n
            gain = name.endswith(".w") and "ln" in name[:-2]
            out[name] = 1.0 + x if gain else x
        return out

    return make


def seeded_weights(shapes, seed, std=0.02):
    """Every leaf from the seed in one jitted call, on the default device:
    normal(0, std), layer-norm gains around 1."""
    layout = tuple((n, tuple(shapes[n])) for n in sorted(shapes))
    return _maker(layout, float(std))(seed_key(seed))
