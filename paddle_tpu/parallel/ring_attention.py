"""Ring attention: sequence/context parallelism for long sequences.

Not present in the reference (its sequence scale was bounded by single-GPU
memory); required here as first-class long-context support. Each device in
the 'sp' mesh axis holds a sequence shard of Q/K/V; K/V blocks rotate around
the ICI ring via lax.ppermute while a flash-attention-style running
(max, sum, out) accumulator keeps the softmax exact — O(seq/n) memory per
chip, compute/communication overlapped by XLA.

Use inside shard_map over a Mesh with an 'sp' axis, or through
`ring_attention_sharded` which wraps the shard_map call.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .comms.allreduce import axis_size

__all__ = ["ring_attention", "ring_attention_sharded", "full_attention"]


def full_attention(q, k, v, causal=False, scale=None):
    """Reference single-device attention. q,k,v: (B, T, H, D)."""
    d = q.shape[-1]
    scale = scale or (d ** -0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), tk - tq)
        logits = jnp.where(mask, logits, -1e30)
    probs = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _block_attn(q, k, v, scale, mask):
    """One block's contribution: returns (m, l, o) partials.
    q: (B, Tq, H, D); k,v: (B, Tk, H, D); mask broadcastable (Tq, Tk)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    if mask is not None:
        logits = jnp.where(mask[None, None], logits, -1e30)
    m = jnp.max(logits, axis=-1)                      # (B, H, Tq)
    p = jnp.exp(logits - m[..., None])
    l = jnp.sum(p, axis=-1)                           # (B, H, Tq)
    o = jnp.einsum("bhqk,bkhd->bqhd", p, v)           # (B, Tq, H, D)
    return m, l, o


def ring_attention(q, k, v, axis_name, causal=False, scale=None):
    """Exact attention over a ring-sharded sequence. Call inside shard_map;
    q,k,v are the LOCAL shards (B, T_local, H, D)."""
    d = q.shape[-1]
    t_local = q.shape[1]
    scale = scale or (d ** -0.5)
    n = axis_size(axis_name)
    my_idx = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    neg_inf = jnp.float32(-1e30)
    b, _, h, _ = q.shape
    m_acc = jnp.full((b, h, t_local), neg_inf, jnp.float32)
    l_acc = jnp.zeros((b, h, t_local), jnp.float32)
    o_acc = jnp.zeros(q.shape, jnp.float32)

    def mask_for(block_owner):
        if not causal:
            return None
        # global positions: my queries [my_idx*T, ...), block keys likewise
        qpos = my_idx * t_local + jnp.arange(t_local)[:, None]
        kpos = block_owner * t_local + jnp.arange(t_local)[None, :]
        return qpos >= kpos

    def body(carry, step):
        m_acc, l_acc, o_acc, k_blk, v_blk = carry
        owner = (my_idx - step) % n  # whose K/V shard we hold this step
        m_b, l_b, o_b = _block_attn(
            q.astype(jnp.float32),
            k_blk.astype(jnp.float32),
            v_blk.astype(jnp.float32),
            scale,
            mask_for(owner),
        )
        m_new = jnp.maximum(m_acc, m_b)
        alpha = jnp.exp(m_acc - m_new)
        beta = jnp.exp(m_b - m_new)
        l_new = l_acc * alpha + l_b * beta
        o_new = (
            o_acc * jnp.moveaxis(alpha, 1, 2)[..., None]
            + o_b * jnp.moveaxis(beta, 1, 2)[..., None]
        )
        k_next = lax.ppermute(k_blk, axis_name, perm)
        v_next = lax.ppermute(v_blk, axis_name, perm)
        return (m_new, l_new, o_new, k_next, v_next), None

    (m_acc, l_acc, o_acc, _, _), _ = lax.scan(
        body, (m_acc, l_acc, o_acc, k, v), jnp.arange(n)
    )
    denom = jnp.moveaxis(l_acc, 1, 2)[..., None]
    out = o_acc / jnp.maximum(denom, 1e-20)
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, axis="sp", causal=False):
    """Convenience wrapper: q,k,v are GLOBAL (B, T, H, D) arrays; runs ring
    attention with the sequence dim sharded over `axis`."""
    spec = P(None, axis, None, None)
    fn = jax.shard_map(
        functools.partial(ring_attention, axis_name=axis, causal=causal),
        mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec,
        check_vma=False,
    )
    return fn(q, k, v)
