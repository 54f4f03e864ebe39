"""Plain reference of the served decoder: a post-LN, learned-position, GELU
transformer language model (Radford et al. 2018, GPT-1), one full causal
forward pass over prompt + served tokens, float32, no cache, no slots.

Departure from the published model, shared with the system under test: the
output head `gpt_out` is a matrix and bias of its own, not the tied
embedding (the configuration file lists it under `assumed`).
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks


def weight_shapes(m):
    h, f = m["n_embd"], m["n_inner"]
    shapes = {"gpt_tok_emb": (m["vocab_size"], h),
              "gpt_pos_emb": (m["n_positions"], h),
              "gpt_out.w": (h, m["vocab_size"]),
              "gpt_out.b": (m["vocab_size"],)}
    for i in range(m["n_layer"]):
        p = "gpt%d." % i
        for part in ("self.q", "self.k", "self.v", "self.o", "ffn.fc2"):
            shapes[p + part + ".w"] = (f if part == "ffn.fc2" else h, h)
            shapes[p + part + ".b"] = (h,)
        shapes.update({p + "ffn.fc1.w": (h, f), p + "ffn.fc1.b": (f,),
                       p + "ln1.w": (h,), p + "ln1.b": (h,),
                       p + "ln2.w": (h,), p + "ln2.b": (h,)})
    return shapes


def make_weights(m, seed):
    return blocks.seeded_weights(weight_shapes(m), seed)


def hidden_states(w, ids, m, rnd):
    """ids (T,) -> final hidden states (T, H), causal."""
    t = ids.shape[0]
    x = jnp.take(w["gpt_tok_emb"], ids, axis=0) + w["gpt_pos_emb"][:t]
    pos = jnp.arange(t)
    mask = jnp.where(pos[None, :] <= pos[:, None], 0.0, -1e9)[None]

    def layer(x, lw):
        q, k, v = (blocks.dense(x, lw["self.%s.w" % c], lw["self.%s.b" % c],
                                rnd) for c in "qkv")
        a = blocks.attention(q, k, v, mask, m["n_head"], rnd)
        a = blocks.dense(a, lw["self.o.w"], lw["self.o.b"], rnd)
        x = blocks.layer_norm(x + a, lw["ln1.w"], lw["ln1.b"])
        f = blocks.gelu(blocks.dense(x, lw["ffn.fc1.w"], lw["ffn.fc1.b"],
                                     rnd))
        f = blocks.dense(f, lw["ffn.fc2.w"], lw["ffn.fc2.b"], rnd)
        return blocks.layer_norm(x + f, lw["ln2.w"], lw["ln2.b"]), None

    # the layers are alike: one scan over their stacked weights
    x, _ = jax.lax.scan(layer, x, blocks.stack_layers(w, "gpt%d.",
                                                      m["n_layer"]))
    return x


def _logits_at(w, ids, at, m, precision):
    rnd = blocks.rounder(precision)
    x = hidden_states(w, ids, m, rnd)
    return blocks.dense(jnp.take(x, at, axis=0), w["gpt_out.w"],
                        w["gpt_out.b"], rnd)


def served_gaps(w, requests, m, seq_len, out_len, control=None):
    """For each request (prompt ids, served tokens): the reference's logits
    at every position that produced a served token, and from them, in units
    of that position's logit standard deviation, how far the served token
    lies below the reference's best. With `control` (a precision name) the
    token judged is not the served one but the one that precision puts
    first at the same position of the same sequence.

    Returns the list of per-token gaps, request by request."""
    fn = jax.jit(functools.partial(_logits_at, m=m, precision="float32"))
    low = control and jax.jit(
        functools.partial(_logits_at, m=m, precision=control))
    gaps = []
    for prompt, served in requests:
        n = len(served)
        seq = np.zeros((seq_len,), np.int32)
        full = list(prompt) + list(served)
        seq[:len(full)] = full
        at = np.minimum(len(prompt) - 1 + np.arange(out_len),
                        seq_len - 1).astype(np.int32)
        ref = np.asarray(fn(w, seq, at))[:n]
        tok = (np.asarray(low(w, seq, at))[:n].argmax(-1) if control
               else np.asarray(served))
        gap = ref.max(-1) - ref[np.arange(n), tok]
        gaps.append(gap / ref.std(-1))
    return gaps
