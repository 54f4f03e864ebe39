"""Settle BERT s512 MFU 0.34 (VERDICT r3 #2): is the phase-2 pretrain
shape at the XLA/v5e ceiling, or is the framework leaving throughput on
the table?

Mirrors the ResNet methodology (resnet_ablate.py): a MINIMAL pure-jax
BERT-base MLM train step — same compute recipe as the framework path
(bf16 matmul inputs, f32 softmax/layernorm, rbg dropout, tied MLM head,
plain Adam, donated state) — measured on the same chip, alongside
framework variants (batch sweep, dropout ablation). If the control
matches ~0.34, s512 is attention-bandwidth destiny; if not, the gap is
framework overhead worth chasing.

Self-exiting; banks to bench_experiments/bert_s512_ablate.json after
every variant.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


# ---------------------------------------------------------------------------
# minimal pure-jax BERT-base (control)
# ---------------------------------------------------------------------------
V, H, L, NH, FFN, MAXP = 30522, 768, 12, 12, 3072, 512


def _init_params(seed=0):
    import numpy as np

    rng = np.random.default_rng(seed)

    def n(*shape):
        return (rng.standard_normal(shape) * 0.02).astype("float32")

    p = {"word_emb": n(V, H), "pos": n(MAXP, H),
         "emb_ln_w": np.ones(H, "float32"),
         "emb_ln_b": np.zeros(H, "float32")}
    for i in range(L):
        p["l%d_qkv_w" % i] = n(H, 3 * H)
        p["l%d_qkv_b" % i] = n(3 * H)
        p["l%d_o_w" % i] = n(H, H)
        p["l%d_o_b" % i] = n(H)
        p["l%d_ln1_w" % i] = np.ones(H, "float32")
        p["l%d_ln1_b" % i] = np.zeros(H, "float32")
        p["l%d_f1_w" % i] = n(H, FFN)
        p["l%d_f1_b" % i] = n(FFN)
        p["l%d_f2_w" % i] = n(FFN, H)
        p["l%d_f2_b" % i] = n(H)
        p["l%d_ln2_w" % i] = np.ones(H, "float32")
        p["l%d_ln2_b" % i] = np.zeros(H, "float32")
    return p


def _purejax_step_fn(dropout):
    import jax
    import jax.numpy as jnp

    bf16 = jnp.bfloat16

    def ln(x, w, b):
        x = x.astype(jnp.float32)
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + 1e-5) * w + b

    def drop(x, key, i):
        if not dropout:
            return x
        keep = jax.random.bernoulli(
            jax.random.fold_in(key, i), 1.0 - dropout, x.shape)
        return jnp.where(keep, x / (1.0 - dropout), 0).astype(x.dtype)

    def fwd(p, ids, labels, key):
        B, T = ids.shape
        x = p["word_emb"][ids] + p["pos"][None, :T]
        x = ln(x, p["emb_ln_w"], p["emb_ln_b"])
        x = drop(x, key, 1000)
        dh = H // NH
        for i in range(L):
            xb = x.astype(bf16)
            qkv = xb @ p["l%d_qkv_w" % i].astype(bf16) \
                + p["l%d_qkv_b" % i].astype(bf16)
            qkv = qkv.reshape(B, T, 3, NH, dh).transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]          # (B,NH,T,dh)
            scores = (q @ k.transpose(0, 1, 3, 2)) * (dh ** -0.5)
            probs = jax.nn.softmax(
                scores.astype(jnp.float32), axis=-1)
            probs = drop(probs, key, 10 * i + 1).astype(bf16)
            ctx = (probs @ v).transpose(0, 2, 1, 3).reshape(B, T, H)
            attn = ctx @ p["l%d_o_w" % i].astype(bf16) \
                + p["l%d_o_b" % i].astype(bf16)
            attn = drop(attn, key, 10 * i + 2)
            x = ln(x + attn, p["l%d_ln1_w" % i], p["l%d_ln1_b" % i])
            xb = x.astype(bf16)
            f = jax.nn.gelu(
                xb @ p["l%d_f1_w" % i].astype(bf16)
                + p["l%d_f1_b" % i].astype(bf16))
            f = f @ p["l%d_f2_w" % i].astype(bf16) \
                + p["l%d_f2_b" % i].astype(bf16)
            f = drop(f, key, 10 * i + 3)
            x = ln(x + f, p["l%d_ln2_w" % i], p["l%d_ln2_b" % i])
        logits = (x.astype(bf16)
                  @ p["word_emb"].astype(bf16).T).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.clip(labels, 0)[..., None], axis=-1)[..., 0]
        mask = (labels >= 0).astype(jnp.float32)
        return jnp.sum((lse - gold) * mask) / jnp.maximum(mask.sum(), 1)

    def step(p, m, v, t, ids, labels, key):
        loss, g = jax.value_and_grad(fwd)(p, ids, labels, key)
        b1, b2, lr, eps = 0.9, 0.999, 1e-4, 1e-8
        t = t + 1
        new_p, new_m, new_v = {}, {}, {}
        for k2 in p:
            new_m[k2] = b1 * m[k2] + (1 - b1) * g[k2]
            new_v[k2] = b2 * v[k2] + (1 - b2) * g[k2] ** 2
            mhat = new_m[k2] / (1 - b1 ** t)
            vhat = new_v[k2] / (1 - b2 ** t)
            new_p[k2] = p[k2] - lr * mhat / (jnp.sqrt(vhat) + eps)
        return loss, new_p, new_m, new_v, t

    return step


def measure_purejax(tag, batch, seq, n_steps, dropout):
    import jax
    import jax.numpy as jnp
    import numpy as np

    import bench

    p = _init_params()
    p = jax.device_put(p)
    m = jax.tree.map(jnp.zeros_like, p)
    v = jax.tree.map(jnp.zeros_like, p)
    t = jnp.zeros((), jnp.int32)
    step = jax.jit(_purejax_step_fn(dropout),
                   donate_argnums=(0, 1, 2, 3))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, V, size=(batch, seq), dtype=np.int64)
    labels = ids.copy()
    mask = rng.random((batch, seq)) < 0.15
    ids[mask] = 0
    labels[~mask] = -1
    ids = jax.device_put(ids)
    labels = jax.device_put(labels)
    key = jax.device_put(jax.random.key(7, impl="rbg"))

    t0 = time.time()
    loss, p, m, v, t = step(p, m, v, t, ids, labels, key)
    loss0 = float(loss)
    compile_s = time.time() - t0
    loss, p, m, v, t = step(p, m, v, t, ids, labels, key)  # settle layouts
    t0 = time.time()
    for _ in range(n_steps):
        loss, p, m, v, t = step(p, m, v, t, ids, labels, key)
    last = float(loss)
    dt = time.time() - t0
    tps = n_steps * batch * seq / dt

    class _Cfg:
        hidden, num_layers, vocab_size = H, L, V

    flops = bench._flops_per_token_train(_Cfg, seq)
    return {
        "tag": tag, "tokens_per_sec": round(tps, 1), "batch": batch,
        "seq_len": seq, "steps": n_steps,
        "step_ms": round(1000 * dt / n_steps, 2),
        "compile_s": round(compile_s, 1),
        "loss_first": round(loss0, 4), "loss_last": round(last, 4),
        "dropout": dropout,
        "mfu": round(tps * flops / 197e12, 4),
    }


def measure_framework(tag, batch, seq, n_steps, dropout=0.1):
    """Framework path, optionally with dropout ablated (isolates the
    RNG + mask-apply cost at this shape)."""
    import bench
    from paddle_tpu.models import bert

    orig = bert.bert_base

    def patched():
        cfg = orig()
        cfg.dropout = dropout
        return cfg

    bert.bert_base = patched
    try:
        variant, cfg = bench._measure(tag, True, False, batch, seq,
                                      n_steps)
    finally:
        bert.bert_base = orig
    variant["dropout"] = dropout
    variant["mfu"] = round(
        variant["tokens_per_sec"]
        * bench._flops_per_token_train(cfg, seq) / 197e12, 4)
    return variant


def main():
    bank = Bank(__file__)
    plan = [
        ("fw_b16", lambda: measure_framework("fw_b16", 16, 512, 12)),
        ("fw_b24", lambda: measure_framework("fw_b24", 24, 512, 12)),
        ("fw_b32", lambda: measure_framework("fw_b32", 32, 512, 12)),
        ("fw_b16_nodrop",
         lambda: measure_framework("fw_b16_nodrop", 16, 512, 12,
                                   dropout=0.0)),
        ("purejax_b16",
         lambda: measure_purejax("purejax_b16", 16, 512, 12, 0.1)),
        ("purejax_b16_nodrop",
         lambda: measure_purejax("purejax_b16_nodrop", 16, 512, 12,
                                 0.0)),
        ("purejax_b32",
         lambda: measure_purejax("purejax_b32", 32, 512, 12, 0.1)),
    ]
    for tag, fn in plan:
        bank.run(tag, fn)
    bank.done()


if __name__ == "__main__":
    enable_compile_cache()
    main()
