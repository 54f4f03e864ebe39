"""Gradient-communication subsystem (ISSUE 10, parallel/comms):
block-scaled quantization round-trip bounds, error feedback, bucket-plan
determinism, the two-shot quantized allreduce inside shard_map, the
Fleet grad_sync_mode='comms' path (fp32 parity, quantized convergence,
overlap-vs-sync bit-equivalence), telemetry, the cost-model interconnect
leg, the quantized_collectives shim, and FleetGuard fault drills."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from jax import shard_map

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid import resilience as R
from paddle_tpu.parallel import fleet as fleet_mod
from paddle_tpu.parallel.comms import allreduce as ar
from paddle_tpu.parallel.comms import bucketing as bk
from paddle_tpu.parallel.comms import quantize as qz
from paddle_tpu.parallel.fleet import DistributedStrategy

NDP = 8


def _mesh():
    return Mesh(np.array(jax.devices()[:NDP]), ("dp",))


def _shard_map(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# -- quantize.py ------------------------------------------------------------

@pytest.mark.parametrize("block", [32, 64, 256])
@pytest.mark.parametrize("wire", ["int8"])
def test_roundtrip_error_bound_per_block(block, wire):
    """|x - dq(q(x))| <= s/2 per element, s the block's symmetric
    scale — the bound the error-feedback telescoping relies on."""
    rng = np.random.default_rng(3)
    flat = jnp.asarray(
        rng.standard_normal(block * 16).astype(np.float32) * 5.0)
    payload, scales = qz.quantize_blocks(flat, block, wire)
    dec = np.asarray(qz.dequantize_blocks(payload, scales, block))
    err = np.abs(np.asarray(flat) - dec).reshape(-1, block)
    bound = np.asarray(scales).reshape(-1, 1) / 2.0 + 1e-7
    assert (err <= bound).all()


def test_smaller_blocks_tighten_the_bound():
    """Scales are per-block maxima: splitting blocks can only lower (or
    keep) each element's scale, so the worst-case error shrinks."""
    rng = np.random.default_rng(4)
    flat = jnp.asarray(rng.standard_normal(1024).astype(np.float32))
    errs = {}
    for block in (256, 32):
        p, s = qz.quantize_blocks(flat, block, "int8")
        errs[block] = float(np.max(np.abs(
            np.asarray(flat) - np.asarray(
                qz.dequantize_blocks(p, s, block)))))
    assert errs[32] <= errs[256] + 1e-7


def test_error_feedback_residual_bounded():
    """The residual after one compensated round stays within the
    quantization bound — it never accumulates past one step's error."""
    rng = np.random.default_rng(5)
    flat = jnp.asarray(rng.standard_normal(512).astype(np.float32))
    residual = jnp.zeros_like(flat)
    for _ in range(4):
        send = qz.error_feedback_apply(flat, residual)
        p, s = qz.quantize_blocks(send, 64, "int8")
        decoded = qz.dequantize_blocks(p, s, 64)
        residual = qz.error_feedback_update(send, decoded)
        bound = float(np.max(np.asarray(s))) / 2.0 + 1e-6
        assert float(np.max(np.abs(np.asarray(residual)))) <= bound


def test_wire_bytes_and_compression_ratio():
    n = 4096
    fp32 = 4.0 * n
    for block in (32, 64, 256):
        ratio = fp32 / qz.wire_bytes(n, block, "int8")
        assert ratio == pytest.approx(4.0 / (1.0 + 4.0 / block))
        assert ratio >= 3.5
    assert qz.compression_ratio(n, 256, "int8") == pytest.approx(
        fp32 / qz.wire_bytes(n, 256, "int8"))


# -- bucketing.py -----------------------------------------------------------

def test_bucket_plan_deterministic_reverse_backward_order():
    named = [("w0", (64, 64)), ("b0", (64,)), ("w1", (64, 64)),
             ("b1", (64,)), ("w2", (512, 512)), ("b2", (512,))]
    a = bk.plan_buckets(named, 64 * 64 * 4)
    b = bk.plan_buckets(named, 64 * 64 * 4)
    assert a.to_dict() == b.to_dict()
    flat_names = [n for bucket in a.buckets for n in bucket.names]
    assert flat_names == [n for n, _ in reversed(named)]
    # the oversized w2 closes its bucket on its own
    assert any(bucket.names[-1] == "w2" for bucket in a.buckets)


def test_overlap_ratio_semantics():
    one = bk.plan_buckets([("w", (8, 8))], 1 << 20)
    assert len(one.buckets) == 1
    assert one.overlap_ratio() == 0.0
    many = bk.plan_buckets(
        [("a", (64, 64)), ("b", (64, 64)), ("c", (64, 64))], 64 * 64 * 4)
    assert len(many.buckets) >= 2
    assert many.overlap_ratio() > 0.0
    assert many.overlap_ratio(overlap=False) == 0.0
    # everything-but-last-bucket fraction, by elements
    last = many.buckets[-1].n_elements
    assert many.overlap_ratio() == pytest.approx(
        1.0 - last / many.total_elements)


def test_pack_unpack_roundtrip():
    named = [("p", (3, 5)), ("q", (7,))]
    plan = bk.plan_buckets(named, 1 << 20)
    bucket = plan.buckets[0]
    rng = np.random.default_rng(0)
    grads = {"p": jnp.asarray(rng.standard_normal((3, 5)), jnp.float32),
             "q": jnp.asarray(rng.standard_normal((7,)), jnp.float32)}
    padded = bk.bucket_padded_len(bucket, NDP, 16)
    flat = bk.pack_bucket(bucket, grads, padded)
    assert flat.shape == (padded,)
    out = bk.unpack_bucket(bucket, flat, grads)
    for n in ("p", "q"):
        np.testing.assert_array_equal(np.asarray(out[n]),
                                      np.asarray(grads[n]))


# -- allreduce.py (direct, inside shard_map) --------------------------------

def test_quantized_allreduce_matches_mean_within_bound():
    block = 16
    per = NDP * block * 2              # per-shard flat length
    rng = np.random.default_rng(11)
    x = rng.standard_normal((NDP, per)).astype(np.float32)

    def f(xs):
        reduced, _ = ar.quantized_allreduce_flat(
            xs.reshape(-1), "dp", block_size=block, mean=True)
        return reduced[None]

    out = np.asarray(_shard_map(f, _mesh(), P("dp"), P("dp"))(x))
    want = x.mean(axis=0)
    # phase-1 error (averaged per-shard roundings) + phase-2 rounding
    tol = np.abs(x).max() / 127.0 + 1e-6
    assert np.max(np.abs(out[0] - want)) <= tol
    # phase 2 re-quantizes the reduced chunk: all shards decode the
    # same bytes, so replicated state stays bit-identical
    for i in range(1, NDP):
        np.testing.assert_array_equal(out[i], out[0])


def test_exact_allreduce_flat_is_psum_mean():
    per = 32
    x = np.random.default_rng(1).standard_normal(
        (NDP, per)).astype(np.float32)

    def f(xs):
        reduced, local = ar.exact_allreduce_flat(xs.reshape(-1), "dp")
        return (reduced + 0.0 * local.sum())[None]

    out = np.asarray(_shard_map(f, _mesh(), P("dp"), P("dp"))(x))
    np.testing.assert_allclose(out[0], x.mean(axis=0), rtol=1e-5,
                               atol=1e-6)


def test_allreduce_wire_bytes_accounting():
    n, shards = 8192, 8
    frac = 2.0 * (shards - 1) / shards
    assert ar.allreduce_wire_bytes(n, shards) == pytest.approx(
        frac * 4.0 * n)
    q = ar.allreduce_wire_bytes(n, shards, quantized=True, block_size=256)
    assert q == pytest.approx(frac * qz.wire_bytes(n, 256, "int8"))
    assert ar.allreduce_wire_bytes(n, 1) == 0.0


def test_c_allreduce_quant_op_registered():
    from paddle_tpu.ops import registry

    assert registry.has_lowering("c_allreduce_quant")


# -- the Fleet grad_sync_mode='comms' path ----------------------------------

def _build_loss(seed=11):
    fluid.default_startup_program().random_seed = seed
    fluid.default_main_program().random_seed = seed
    x = fluid.data("cx", shape=[None, 6], dtype="float32")
    y = fluid.data("cy", shape=[None, 1], dtype="float32")
    h = fluid.layers.fc(x, 12, act="tanh")
    p = fluid.layers.fc(h, 1)
    return fluid.layers.reduce_mean(fluid.layers.square_error_cost(p, y))


def _data():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 6)).astype("float32")
    y = (x @ rng.standard_normal((6, 1))).astype("float32")
    return x, y


def _run(strategy, steps=6, lr=0.1):
    from paddle_tpu.fluid import executor as executor_mod
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    fl = fleet_mod.Fleet().init()
    loss = _build_loss()
    opt = fl.distributed_optimizer(fluid.optimizer.SGD(lr),
                                   strategy=strategy)
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    x, y = _data()
    losses = []
    for _ in range(steps):
        out = exe.run(fl.main_program, feed={"cx": x, "cy": y},
                      fetch_list=[loss])
        losses.append(float(np.asarray(out[0])))
    return losses, fl, exe, loss


def _comms_strategy(quantized=False, overlap=True, bucket_bytes=None,
                    block=64):
    s = DistributedStrategy()
    s.grad_sync_mode = "comms"
    s.grad_quantize = quantized
    s.grad_quantize_block = block
    s.grad_overlap = overlap
    if bucket_bytes is not None:
        s.grad_bucket_bytes = bucket_bytes
    return s


def test_comms_fp32_matches_gspmd_dp():
    plain, _, _, _ = _run(DistributedStrategy())
    exact, fl, _, _ = _run(_comms_strategy())
    np.testing.assert_allclose(exact, plain, rtol=2e-4, atol=2e-5)
    assert fl._distributed_program._plans


def test_comms_quantized_ef_converges_to_fp32():
    plain, _, _, _ = _run(DistributedStrategy(), steps=8)
    quant, _, _, _ = _run(_comms_strategy(quantized=True), steps=8)
    assert quant[-1] < quant[0] * 0.5          # it actually trains
    # documented tolerance: error feedback keeps the quantized run
    # within a few 1e-3 of the fp32 trajectory on this model
    assert abs(quant[-1] - plain[-1]) < 5e-3


def test_overlap_vs_sync_bit_identical():
    # small bucket target so the model splits into >1 bucket and the
    # optimization_barrier fence actually has something to fence
    kw = dict(quantized=True, bucket_bytes=64)
    lap, fl, _, _ = _run(_comms_strategy(overlap=True, **kw))
    sync, _, _, _ = _run(_comms_strategy(overlap=False, **kw))
    assert lap == sync
    plans = fl._distributed_program._plans
    assert sum(len(p.buckets) for p in plans) > 1


def test_quantized_comms_without_error_feedback_still_runs():
    s = _comms_strategy(quantized=True)
    s.grad_error_feedback = False
    losses, fl, _, _ = _run(s)
    assert losses[-1] < losses[0]
    assert not fl._distributed_program._residual_names


def test_dp8_comm_metrics_and_predicted_seconds(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "on")
    monkeypatch.setenv("PADDLE_TPU_ICI_BW", "1e9")
    obs.reset()
    _, fl, _, _ = _run(_comms_strategy(quantized=True, bucket_bytes=64))
    ratio = obs.gauge("comm.compression_ratio")
    assert ratio is not None and ratio >= 3.5
    assert obs.gauge("comm.overlap_ratio") > 0.0
    assert obs.counter("comm.bytes_sent") > 0
    assert obs.counter("comm.bytes_saved") > 0
    h = obs.histogram("comm.allreduce_seconds")
    assert h and h["count"] >= 1
    assert obs.counter("collective.dispatch.grad_sync") >= 1
    # the program's own prediction agrees with the wire accounting
    prog = fl._distributed_program
    t = prog.predicted_comm_seconds()
    assert t == pytest.approx(
        prog._wire_stats["bytes_sent"] / NDP / 1e9)
    obs.reset()


def test_wire_stats_compression_matches_theory():
    _, fl, _, _ = _run(_comms_strategy(quantized=True, block=64), steps=1)
    stats = fl._distributed_program._wire_stats
    assert stats["bytes_fp32"] / stats["bytes_sent"] == pytest.approx(
        4.0 / (1.0 + 4.0 / 64))


def test_residuals_persist_in_scope():
    _, fl, _, _ = _run(_comms_strategy(quantized=True, bucket_bytes=64))
    prog = fl._distributed_program
    assert prog._residual_names
    from paddle_tpu.fluid import executor as executor_mod

    scope = executor_mod.global_scope()
    for n in prog._residual_names:
        v = scope.find_value(n)
        assert v is not None
        # stacked per-shard state: one residual per dp shard
        assert v.shape[0] == NDP
        assert np.any(np.asarray(v) != 0.0)


# -- cost model interconnect leg --------------------------------------------

def test_cost_report_scaling_efficiency(monkeypatch):
    from paddle_tpu.analysis import costs

    loss = _build_loss()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    monkeypatch.setenv(costs.PEAK_FLOPS_ENV, "1e12")
    monkeypatch.setenv(costs.HBM_BW_ENV, "1e12")
    monkeypatch.setenv(costs.ICI_BW_ENV, "1e8")
    rep = costs.analyze_cost(
        prog, feed_names=["cx", "cy"], fetch_names=[loss.name],
        default_dim=8, dp_shards=8, comm_overlap_ratio=0.5)
    assert rep.grad_bytes > 0
    t = rep.predicted_comm_seconds
    assert t == pytest.approx(costs.ring_allreduce_seconds(
        rep.grad_bytes, 8, 1e8))
    eff = rep.scaling_efficiency
    assert eff is not None and 0.0 < eff < 1.0
    d = rep.to_dict()
    assert d["comm"]["dp_shards"] == 8
    assert d["comm"]["scaling_efficiency"] == pytest.approx(eff, abs=1e-4)
    # overlap hides half the comm leg: efficiency must beat the
    # fully-exposed prediction
    rep0 = costs.analyze_cost(
        prog, feed_names=["cx", "cy"], fetch_names=[loss.name],
        default_dim=8, dp_shards=8, comm_overlap_ratio=0.0)
    assert eff > rep0.scaling_efficiency


def test_cli_cost_mesh_dp8_reports_comm_leg(tmp_path, capsys):
    """`--cost --mesh dp=8` over a saved TRAINING program prints the
    interconnect leg: predicted allreduce seconds and scaling
    efficiency."""
    import json

    from paddle_tpu.analysis import cli

    loss = _build_loss()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    path = tmp_path / "train.json"
    path.write_text(fluid.default_main_program().to_json())
    rc = cli.main([str(path), "--cost", "--device", "v5e", "--mesh",
                   "dp=8", "--batch", "8", "--fail-on", "never"])
    assert rc == 0
    comm = json.loads(capsys.readouterr().out)["cost"]["comm"]
    assert comm["dp_shards"] == 8
    assert comm["predicted_allreduce_seconds"] > 0
    assert 0.0 < comm["scaling_efficiency"] <= 1.0


def test_device_table_carries_ici_bw(monkeypatch):
    from paddle_tpu.analysis.costs import (DEVICE_TABLE, ICI_BW_ENV,
                                           device_profile)

    monkeypatch.delenv(ICI_BW_ENV, raising=False)
    for _, p in DEVICE_TABLE:
        assert p.ici_bw and p.ici_bw > 0
    assert device_profile("TPU v4").ici_bw == 300e9
    assert "ici_bw" in device_profile("TPU v4").to_dict()
    monkeypatch.setenv(ICI_BW_ENV, "7e9")
    assert device_profile("TPU v4").ici_bw == 7e9


def test_lint_flags_quantizable_allreduce():
    from paddle_tpu.analysis.tpu_lint import lint
    from paddle_tpu.fluid import framework

    prog = framework.Program()
    with framework.program_guard(prog):
        g = fluid.data("g", shape=[512, 512], dtype="float32")
        blk = prog.global_block()
        out = blk.create_var(name="g_red", shape=[512, 512],
                             dtype="float32")
        blk.append_op(type="c_allreduce_sum", inputs={"X": [g.name]},
                      outputs={"Out": [out.name]}, attrs={"ring_id": 0})
        small = blk.create_var(name="g_small", shape=[4, 4],
                               dtype="float32")
        blk.append_op(type="c_allreduce_sum", inputs={"X": [small.name]},
                      outputs={"Out": [small.name]},
                      attrs={"ring_id": 0})
    rep = lint(prog, feed_names=["g"])
    hits = [d for d in rep.diagnostics
            if d.check == "quantizable-allreduce"]
    assert len(hits) == 1 and hits[0].var == "g"
    assert "c_allreduce_quant" in hits[0].message


# -- shim + LocalSGD regression ---------------------------------------------

def test_quantized_collectives_shim_reexports():
    from paddle_tpu.parallel import quantized_collectives as shim

    assert shim.pmean_int8 is ar.pmean_int8
    assert shim.__all__ == ["pmean_int8"]


def test_local_sgd_quantized_sync_still_works():
    s = DistributedStrategy()
    s.use_local_sgd = True
    s.local_sgd_k_steps = 2
    s.local_sgd_quantized_sync = True
    losses, _, _, _ = _run(s, steps=6)
    assert losses[-1] < losses[0]


def test_local_sgd_plus_comms_mode_rejected():
    s = DistributedStrategy()
    s.use_local_sgd = True
    s.grad_sync_mode = "comms"
    with pytest.raises(NotImplementedError, match="comms"):
        _run(s, steps=1)


def test_unknown_grad_sync_mode_rejected():
    s = DistributedStrategy()
    s.grad_sync_mode = "carrier-pigeon"
    with pytest.raises(NotImplementedError):
        _run(s, steps=1)


# -- FleetGuard drills ------------------------------------------------------

@pytest.mark.faults
def test_grad_sync_respects_collective_deadline():
    losses, fl, exe, loss = _run(_comms_strategy(quantized=True), steps=2)
    x, y = _data()
    with R.collective_deadline(0):
        with pytest.raises(R.CollectiveTimeoutError, match="grad_sync"):
            exe.run(fl.main_program, feed={"cx": x, "cy": y},
                    fetch_list=[loss])
    # deadline released: the engine is usable again
    out = exe.run(fl.main_program, feed={"cx": x, "cy": y},
                  fetch_list=[loss])
    assert np.isfinite(float(np.asarray(out[0])))


@pytest.mark.faults
def test_grad_sync_fault_site_drill(monkeypatch):
    losses, fl, exe, loss = _run(_comms_strategy(), steps=1)
    x, y = _data()
    R.FaultInjector.install("collective:at=1:RuntimeError")
    try:
        with pytest.raises(RuntimeError, match="injected fault"):
            exe.run(fl.main_program, feed={"cx": x, "cy": y},
                    fetch_list=[loss])
    finally:
        R.FaultInjector.uninstall()
    out = exe.run(fl.main_program, feed={"cx": x, "cy": y},
                  fetch_list=[loss])
    assert np.isfinite(float(np.asarray(out[0])))
