"""Flash-attention pallas kernels: numeric parity with the plain-jax oracle
(fwd + grads, causal/padding-mask/dropout), and graph-level equivalence of
the fused_multihead_attention op against the unfused matmul/softmax graph.

Kernels run in pallas interpret mode on the CPU test mesh; on real TPU the
same code path compiles via Mosaic (exercised by chip_smoke.py).

Which kernel a call takes is decided by what the call shows (platform, mesh,
shape: ops/hybrid_ops.py), never by the environment: the last two tests hold
`paddle_tpu/ops/` to that."""
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import pallas_attention as pa


def _qkv(b=2, h=3, t=64, d=16, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda: jnp.asarray(rng.normal(size=(b, h, t, d)), jnp.float32)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("use_kpm", [False, True])
def test_forward_matches_reference(causal, use_kpm):
    q, k, v = _qkv()
    kpm = None
    if use_kpm:
        rng = np.random.default_rng(3)
        kpm = jnp.where(
            jnp.asarray(rng.random((q.shape[0], q.shape[2]))) < 0.2,
            -1e30, 0.0,
        ).astype(jnp.float32)
    out = pa.flash_attention(
        q, k, v, kpm, causal=causal, block_q=32, block_k=16, interpret=True
    )
    ref = pa.reference_attention(q, k, v, kpm, causal=causal)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_grads_match_reference():
    q, k, v = _qkv()
    rng = np.random.default_rng(3)
    kpm = jnp.where(
        jnp.asarray(rng.random((q.shape[0], q.shape[2]))) < 0.2, -1e30, 0.0
    ).astype(jnp.float32)

    def lf(q, k, v, kpm):
        return jnp.sum(pa.flash_attention(
            q, k, v, kpm, causal=True, block_q=32, block_k=16, interpret=True
        ) ** 2)

    def lr(q, k, v, kpm):
        return jnp.sum(pa.reference_attention(q, k, v, kpm, causal=True) ** 2)

    gf = jax.grad(lf, argnums=(0, 1, 2, 3))(q, k, v, kpm)
    gr = jax.grad(lr, argnums=(0, 1, 2, 3))(q, k, v, kpm)
    for a, b in zip(gf, gr):    # includes d(key_padding_mask)
        assert float(jnp.max(jnp.abs(a - b))) < 5e-4


def test_uneven_blocks():
    # T not a multiple of the requested block → _pick_block divides it down
    q, k, v = _qkv(t=48)
    out = pa.flash_attention(q, k, v, block_q=32, block_k=32, interpret=True)
    ref = pa.reference_attention(q, k, v)
    assert float(jnp.max(jnp.abs(out - ref))) < 2e-5


def test_dropout_exact_mask_fwd_and_grads():
    """Rebuild the kernel's dropout mask from its own hash (pure jnp) and
    check fwd + all grads against a reference using those exact bits."""
    B, H, T, D = 2, 2, 32, 8
    bq = bk = 16
    p, seed = 0.3, 7
    q, k, v = _qkv(B, H, T, D, seed=1)

    m = np.zeros((B * H, T, T), bool)
    for bh in range(B * H):
        s = pa.fold_bh_seed(jnp.int32(seed), jnp.int32(bh))
        for qi in range(T // bq):
            for kj in range(T // bk):
                tile = pa._keep_mask(
                    s, jnp.int32(qi), jnp.int32(kj), bq, bk, p
                )
                m[bh, qi * bq:(qi + 1) * bq, kj * bk:(kj + 1) * bk] = (
                    np.asarray(tile)
                )
    keep = jnp.asarray(m.reshape(B, H, T, T))
    assert 0.6 < float(keep.mean()) < 0.8       # ~1-p kept
    assert not bool((keep[0, 0] == keep[0, 1]).all())   # heads independent

    def ref(q, k, v):
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * (D ** -0.5)
        pr = jax.nn.softmax(s, -1)
        pr = jnp.where(keep, pr, 0.0) / (1.0 - p)
        return jnp.einsum("bhqk,bhkd->bhqd", pr, v)

    def fl(q, k, v):
        return pa.flash_attention(
            q, k, v, seed=seed, dropout_p=p, block_q=bq, block_k=bk,
            interpret=True,
        )

    assert float(jnp.max(jnp.abs(fl(q, k, v) - ref(q, k, v)))) < 2e-5
    gf = jax.grad(lambda *a: jnp.sum(fl(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lambda *a: jnp.sum(ref(*a) ** 2), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        assert float(jnp.max(jnp.abs(a - b))) < 5e-4


def test_dropout_deterministic_per_seed():
    q, k, v = _qkv(1, 2, 32, 8)
    f = lambda s: pa.flash_attention(
        q, k, v, seed=s, dropout_p=0.4, block_q=16, block_k=16,
        interpret=True,
    )
    assert bool((f(5) == f(5)).all())
    assert not bool((f(5) == f(6)).all())


def test_fused_op_graph_matches_unfused_bert():
    """Same bert-tiny program with and without the fused op → same loss."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import bert

    losses = []
    for fused in (False, True):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        fluid.default_startup_program().random_seed = 11
        cfg = bert.bert_tiny(seq=32)
        cfg.use_fused_attention = fused
        vs = bert.build_bert_pretrain(cfg, 32)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        ids, labels = bert.synthetic_batch(cfg, 4, 32)
        out = exe.run(
            feed={"input_ids": ids, "mlm_labels": labels},
            fetch_list=[vs["loss"]],
        )
        losses.append(float(out[0]))
    assert abs(losses[0] - losses[1]) < 1e-4, losses


def test_prime_length_pads_not_degrades():
    """T=61 (prime): block must not shrink to 1; pad+mask path stays exact."""
    q, k, v = _qkv(t=61, d=8)
    for causal in (False, True):
        out = pa.flash_attention(
            q, k, v, causal=causal, block_q=32, block_k=32, interpret=True
        )
        ref = pa.reference_attention(q, k, v, causal=causal)
        assert float(jnp.max(jnp.abs(out - ref))) < 2e-5
    # grads flow through the pad/slice wrapper
    g = jax.grad(lambda a: jnp.sum(pa.flash_attention(
        a, k, v, block_q=32, block_k=32, interpret=True) ** 2))(q)
    gr = jax.grad(lambda a: jnp.sum(
        pa.reference_attention(a, k, v) ** 2))(q)
    assert float(jnp.max(jnp.abs(g - gr))) < 5e-4


# ---------------------------------------------------------------------------
# no lowering asks the environment which kernel to take
# ---------------------------------------------------------------------------
PACKAGE = pathlib.Path(pa.__file__).resolve().parents[1]


def _sources(root):
    return {str(p.relative_to(PACKAGE)): p.read_text(encoding="utf-8")
            for p in sorted(root.rglob("*.py"))}


def test_no_module_under_ops_reads_the_environment():
    readers = sorted(name for name, text in _sources(PACKAGE / "ops").items()
                     if "os.environ" in text or "getenv" in text)
    assert readers == []


@pytest.mark.parametrize("option", [
    "PADDLE_TPU_FLASH_MIN_SEQ", "PADDLE_TPU_DISABLE_PALLAS",
    "PADDLE_TPU_PALLAS_LN", "PADDLE_TPU_ASYNC_DEPTH",
    "PADDLE_TPU_MAX_DECODE_LEN"])
def test_an_option_nobody_set_is_gone_from_the_package(option):
    """Each was a user-set switch that no cell, test, example or script
    set (PR 46): two chose Pallas paths that lost on the chip, one turned
    every Pallas path off, two were the defaults of arguments."""
    assert sorted(name for name, text in _sources(PACKAGE).items()
                  if option in text) == []
    assert not (PACKAGE / "ops" / "pallas_layernorm.py").exists()
