"""Plain reference of BERT masked-LM pretraining: post-LN encoder (Devlin et
al. 2018), tied output embedding, cross-entropy summed over the labelled
positions and divided by all positions of the batch, Adam (Kingma & Ba, with
the bias correction folded into the step size). float32 throughout.

Departures from the published model, shared with the system under test: no
token-type embedding and no next-sentence head (the pretraining graph has
neither), no pooler, no output bias on the MLM head, layer-norm epsilon 1e-5
(see the configuration file's `assumed`).

Dropout is on, as published, at the program's four places (the embeddings
after their layer norm, the attention probabilities, the attention output,
the second feed-forward output). The masks are the reference's own Bernoulli
draws from the seed: the program's masks cannot be had without the program,
so the two train under different masks of the same rate, and the limits of
`correct` stand above that noise (PERF.md has the readings).

Weights are keyed by parameter name, the format in which the system under
test takes a checkpoint; they are made here from the seed and handed to it,
never read back from it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks


def weight_shapes(m):
    h, f = m["hidden_size"], m["intermediate_size"]
    shapes = {"word_emb": (m["vocab_size"], h),
              "pos_emb": (m["max_position_embeddings"], h),
              "emb_ln.w": (h,), "emb_ln.b": (h,)}
    for i in range(m["num_hidden_layers"]):
        p = "enc_l%d_" % i
        shapes.update({
            p + "qkv.w": (h, 3 * h), p + "qkv.b": (3 * h,),
            p + "attnout.w": (h, h), p + "attnout.b": (h,),
            p + "ln1.w": (h,), p + "ln1.b": (h,),
            p + "ffn1.w": (h, f), p + "ffn1.b": (f,),
            p + "ffn2.w": (f, h), p + "ffn2.b": (h,),
            p + "ln2.w": (h,), p + "ln2.b": (h,)})
    return shapes


def make_weights(m, seed):
    return blocks.seeded_weights(weight_shapes(m), seed)


def loss_sum(w, ids, labels, key, m, rnd):
    """Sum of the cross-entropies of the labelled positions (label >= 0);
    `key` draws this block's dropout masks."""
    heads, t = m["num_attention_heads"], ids.shape[1]
    p_hid = m.get("hidden_dropout_prob", 0.0)
    p_att = m.get("attention_probs_dropout_prob", 0.0)
    n_layers = m["num_hidden_layers"]
    key_emb, key_layers = jax.random.split(key)
    x = jnp.take(w["word_emb"], ids, axis=0) + w["pos_emb"][:t][None]
    x = blocks.layer_norm(x, w["emb_ln.w"], w["emb_ln.b"])
    x = blocks.dropout(x, key_emb, p_hid)

    def layer(x, lw_key):
        lw, (k_att, k_out, k_ffn) = lw_key[0], jax.random.split(lw_key[1], 3)
        qkv = blocks.dense(x, lw["qkv.w"], lw["qkv.b"], rnd)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        a = blocks.attention(
            q, k, v, None, heads, rnd,
            on_probs=lambda p: blocks.dropout(p, k_att, p_att))
        a = blocks.dense(a, lw["attnout.w"], lw["attnout.b"], rnd)
        a = blocks.dropout(a, k_out, p_hid)
        x = blocks.layer_norm(x + a, lw["ln1.w"], lw["ln1.b"])
        f = blocks.gelu(blocks.dense(x, lw["ffn1.w"], lw["ffn1.b"], rnd))
        f = blocks.dense(f, lw["ffn2.w"], lw["ffn2.b"], rnd)
        f = blocks.dropout(f, k_ffn, p_hid)
        return blocks.layer_norm(x + f, lw["ln2.w"], lw["ln2.b"])

    # the layers are alike: one scan over their stacked weights and their
    # mask keys, each layer recomputed in the backward pass (from the same
    # key, so under the same masks), so that a block of rows fits and the
    # reference compiles in seconds
    stacked = blocks.stack_layers(w, "enc_l%d_", n_layers)
    x, _ = jax.lax.scan(
        lambda x, lw_key: (jax.checkpoint(layer)(x, lw_key), None), x,
        (stacked, jax.random.split(key_layers, n_layers)))
    logits = blocks.matmul(x, w["word_emb"].T, rnd)
    logp = jax.nn.log_softmax(logits, -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    return -jnp.sum(jnp.where(labels >= 0, picked, 0.0))


def adam(w, g, mom, vel, step, o):
    """One Adam step; `step` counts from 1."""
    b1, b2 = o["beta1"], o["beta2"]
    lr_t = o["learning_rate"] * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)

    def one(p, g, m_, v_):
        m_ = b1 * m_ + (1 - b1) * g
        v_ = b2 * v_ + (1 - b2) * g * g
        return p - lr_t * m_ / (jnp.sqrt(v_) + o["epsilon"]), m_, v_

    out = {n: one(w[n], g[n], mom[n], vel[n]) for n in w}
    return ({n: t[0] for n, t in out.items()},
            {n: t[1] for n, t in out.items()},
            {n: t[2] for n, t in out.items()})


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
            for n, x in tree.items()}


def follow(w0, batches, m, optimizer, precision="float32", block_rows=32,
           devices=None, mask_seed=0):
    """Train from `w0` over `batches` [(ids, labels), ...], the gradient of
    each batch accumulated over blocks of rows so that it fits beside
    nothing else on one chip (or, with `devices`, a block on each). Every
    block of every step draws dropout masks of its own from `mask_seed`.
    Returns the readings `correct` compares: each step's loss, the per-leaf
    norm of the first gradient, the per-leaf norm of the parameters'
    change."""
    rnd = blocks.rounder(precision)
    devices = list(devices or jax.devices()[:1])
    n_dev = len(devices)
    grad = jax.value_and_grad(
        functools.partial(loss_sum, m=m, rnd=rnd))

    if n_dev > 1:
        mesh = jax.sharding.Mesh(np.array(devices), ("d",))
        rows = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec("d"))
        whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        w0 = jax.device_put(w0, whole)
        place = lambda a: jax.device_put(a, rows)  # noqa: E731
    else:
        place = lambda a: jax.device_put(a, devices[0])  # noqa: E731

    @jax.jit
    def block_grad(w, ids, labels, key, acc_l, acc_g):
        l, g = grad(w, ids, labels, key)
        return acc_l + l, jax.tree_util.tree_map(jnp.add, acc_g, g)

    adam_step = jax.jit(functools.partial(adam, o=optimizer))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    norms = jax.jit(leaf_norms)
    scaled = jax.jit(lambda t, k: jax.tree_util.tree_map(
        lambda g: g * k, t))
    delta_norms = jax.jit(lambda a, b: leaf_norms(
        {n: a[n] - b[n] for n in a}))

    w, mom, vel = w0, zeros(w0), zeros(w0)
    losses, grad_norms = [], None
    mask_key = blocks.mask_key(mask_seed)
    for step, (ids, labels) in enumerate(batches, start=1):
        acc_l, acc_g = jnp.zeros((), jnp.float32), zeros(w)
        chunk = block_rows * n_dev
        if ids.shape[0] % chunk:
            raise ValueError("batch of %d rows is not a multiple of %d"
                             % (ids.shape[0], chunk))
        for r in range(0, ids.shape[0], chunk):
            acc_l, acc_g = block_grad(
                w, place(ids[r:r + chunk].astype(np.int32)),
                place(labels[r:r + chunk].astype(np.int32)),
                jax.random.fold_in(mask_key, step * 65536 + r), acc_l, acc_g)
        acc_g = scaled(acc_g, jnp.float32(1.0 / ids.size))
        losses.append(float(acc_l) / ids.size)
        if step == 1:
            grad_norms = {n: float(v) for n, v in norms(acc_g).items()}
        w, mom, vel = adam_step(w, acc_g, mom, vel, jnp.float32(step))
    change = {n: float(v) for n, v in delta_norms(w, w0).items()}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
