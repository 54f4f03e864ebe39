"""Static cost & memory analyzer tests: the shared device table (bench
dedupe), exact FLOP counting, roofline MFU prediction, liveness
peak-HBM with backward residuals, the executor predicted-OOM gate,
serving bucket admission, the intensity-ranked lint upgrade, the CLI
``--cost``/``--json-out`` surface, and the ``apply_gradients``
grad_clip fix. See ``paddle_tpu/analysis/costs.py`` / ``memory.py``."""
import io
import json
from contextlib import redirect_stdout

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis
from paddle_tpu import observability as obs
from paddle_tpu.analysis import costs, memory, shapes, walker
from paddle_tpu.analysis.diagnostics import ProgramVerifyError

pytestmark = pytest.mark.analysis


def _exe():
    return fluid.Executor(fluid.CPUPlace())


def _fc_chain(widths=(16, 32, 1), batch=None):
    """x -> fc -> ... -> mean(loss); returns (x, loss)."""
    x = fluid.data(name="x", shape=[batch, widths[0]], dtype="float32")
    h = x
    for w in widths[1:]:
        h = fluid.layers.fc(h, size=w)
    loss = fluid.layers.mean(h)
    return x, loss


# ---------------------------------------------------------------------------
# device table + bench dedupe (satellite 1)
# ---------------------------------------------------------------------------
def test_device_table_lookup_and_precedence():
    p = costs.device_profile("TPU v5e chip")
    assert (p.name, p.peak_flops, p.hbm_bytes) == ("v5e", 197e12, 16e9)
    assert costs.device_profile("TPU v5p").peak_flops == 459e12
    # the string a v5e chip reports (chip run, PR 21) has its own row
    p = costs.device_profile("TPU v5 lite")
    assert (p.name, p.peak_flops, p.hbm_bw) == ("v5e", 197e12, 819e9)
    # no "v5" catch-all: an unknown v5 part gets no peaks, not v5e's
    assert costs.device_profile("TPU v5") is None
    assert costs.device_profile("Threadripper") is None
    assert costs.peak_flops("TPU v4") == 275e12
    assert costs.peak_flops("unknown") is None


def test_require_device_profile_errors_on_unknown_device():
    assert costs.require_device_profile("TPU v5 lite").peak_flops == 197e12
    for dk in ("cpu", "TPU v5", None):
        with pytest.raises(LookupError, match="DEVICE_TABLE"):
            costs.require_device_profile(dk)


def test_device_profile_env_overrides(monkeypatch):
    monkeypatch.setenv(costs.PEAK_FLOPS_ENV, "1e12")
    monkeypatch.setenv(costs.HBM_BYTES_ENV, "2e9")
    # unknown device + overrides -> synthesized profile
    p = costs.device_profile("cpu")
    assert p.peak_flops == 1e12 and p.hbm_bytes == 2e9
    assert p.hbm_bw is None
    # known device: overrides win over the table entry
    p = costs.device_profile("TPU v5e")
    assert p.peak_flops == 1e12 and p.hbm_bytes == 2e9
    assert p.hbm_bw == 819e9  # un-overridden field keeps the table value


def test_peak_flops_is_table_backed():
    for dk in ("TPU v6e", "TPU v5p", "TPU v5 lite", "TPU v4"):
        assert costs.peak_flops(dk) \
            == costs.require_device_profile(dk).peak_flops > 0
    # the analyzer makes no prediction for a device the table does not
    # know; a measurement path errors on it
    assert costs.peak_flops("nope") is None
    with pytest.raises(LookupError):
        costs.require_device_profile("nope")


# ---------------------------------------------------------------------------
# exact FLOP / byte counting
# ---------------------------------------------------------------------------
def test_matmul_flops_and_bytes_exact():
    x = fluid.data(name="x", shape=[4, 16], dtype="float32")
    h = fluid.layers.fc(x, size=32)   # mul [4,16]x[16,32] + bias add
    rep = costs.analyze_cost(
        fluid.default_main_program(), feed_names=["x"],
        fetch_names=[h.name])
    by_type = {c.op_type: c for c in rep.per_op}
    mm = by_type["mul"]
    assert mm.flops == 2 * 4 * 32 * 16
    # bytes = inputs (x + w) + output footprints
    assert mm.bytes == (4 * 16 + 16 * 32 + 4 * 32) * 4
    assert mm.intensity == mm.flops / mm.bytes
    add = by_type["elementwise_add"]
    assert add.flops == 4 * 32  # one per output element


def test_backward_op_costed_as_2x_forward():
    x, loss = _fc_chain()
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    rep = costs.analyze_cost(
        fluid.default_main_program(), feed_names=["x"],
        fetch_names=[loss.name], default_dim=8)
    bwd = [c for c in rep.per_op if c.op_type == "backward"]
    assert len(bwd) == 1
    fwd_flops = sum(c.flops for c in rep.per_op
                    if c.op_index < bwd[0].op_index)
    assert bwd[0].flops == 2.0 * fwd_flops
    assert bwd[0].bytes > 2.0 * sum(
        c.bytes for c in rep.per_op if c.op_index < bwd[0].op_index) - 1


def test_roofline_prediction_and_bound(monkeypatch):
    monkeypatch.setenv(costs.PEAK_FLOPS_ENV, "1e9")
    monkeypatch.setenv(costs.HBM_BW_ENV, "1e8")
    x, loss = _fc_chain()
    rep = costs.analyze_cost(
        fluid.default_main_program(), feed_names=["x"],
        fetch_names=[loss.name], default_dim=8, device_kind="cpu")
    p = rep.profile
    expect = sum(max(c.flops / p.peak_flops, c.bytes / p.hbm_bw)
                 for c in rep.per_op)
    assert rep.predicted_step_seconds == pytest.approx(expect)
    assert rep.predicted_mfu == pytest.approx(
        rep.total_flops / (expect * p.peak_flops))
    assert 0.0 < rep.predicted_mfu <= 1.0
    assert rep.bound == ("compute" if rep.total_flops / p.peak_flops
                         >= rep.total_bytes / p.hbm_bw else "memory")
    # hottest() is FLOPs-descending and stable
    hot = rep.hottest(3)
    assert [c.flops for c in hot] == sorted(
        [c.flops for c in hot], reverse=True)
    d = rep.to_dict(top=2)
    assert len(d["hottest_ops"]) == 2
    assert d["memory"]["peak_bytes"] == rep.memory.peak_bytes


# ---------------------------------------------------------------------------
# liveness peak-HBM
# ---------------------------------------------------------------------------
def test_memory_intermediates_die_after_last_use():
    # x -> a -> b -> c(fetch): a must NOT be resident once c is computed
    x = fluid.data(name="x", shape=[64, 64], dtype="float32")
    a = fluid.layers.relu(x)
    b = fluid.layers.relu(a)
    c = fluid.layers.reduce_sum(b)
    rep = memory.estimate(
        fluid.default_main_program(), fetch_names=[c.name],
        default_dim=64)
    each = 64 * 64 * 4
    # peak: two big tensors live at once (producer + consumer), never 3
    assert rep.peak_bytes < 3 * each
    assert rep.peak_bytes >= 2 * each
    assert rep.peak_op_index is not None
    assert rep.peak_op_type in ("relu", "reduce_sum")
    assert rep.param_bytes == 0
    names = [n for n, _ in rep.top]
    assert any(n == x.name or n == a.name or n == b.name for n in names)


def test_memory_resident_names_pin_kv_buffers():
    # a decode engine round-trips its KV buffer device-to-device every
    # step: resident_names must hold the fed copy live across the WHOLE
    # program even though def-use liveness would let it die at its only
    # reader (first op)
    x = fluid.data(name="x", shape=[64, 64], dtype="float32")
    a = fluid.layers.relu(x)
    b = fluid.layers.relu(a)
    c = fluid.layers.reduce_sum(b)
    prog = fluid.default_main_program()
    each = 64 * 64 * 4
    plain = memory.estimate(prog, fetch_names=[c.name], default_dim=64)
    pinned = memory.estimate(prog, fetch_names=[c.name], default_dim=64,
                             resident_names=[x.name])
    assert plain.peak_bytes < 3 * each
    assert pinned.peak_bytes >= 3 * each
    assert pinned.peak_bytes > plain.peak_bytes


def test_lint_decode_ladder_budget():
    from paddle_tpu.analysis import tpu_lint

    # a sane engine ladder is clean
    ok = tpu_lint.lint_decode_ladder((8, 16, 32), slot_counts=(8,),
                                     cache_lens=(64,))
    assert ok.findings == []
    assert ok.meta["decode_ladder_programs"] == 4
    # a per-token "ladder" re-creates the unbounded-shape-vocab hazard
    # with every rung declared static
    bad = tpu_lint.lint_decode_ladder(
        range(1, 3001), slot_counts=(8,), cache_lens=(4096,))
    assert len(bad.findings) == 1
    assert bad.findings[0].check == "unbounded-shape-vocab"
    # non-pow2 rungs are flagged info (advice), never a finding
    odd = tpu_lint.lint_decode_ladder((8, 24, 32))
    assert odd.findings == []
    assert any(d.check == "decode-ladder-rungs" for d in odd.diagnostics)


def test_memory_backward_residuals_and_persistables():
    x, loss = _fc_chain(widths=(32, 64, 1))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    rep = memory.estimate(prog, fetch_names=[loss.name], default_dim=8)
    # params (w0 32x64 + b0 + w1 64x1 + b1) always resident, plus
    # whatever scalar state the optimizer declares (lr var)
    expect_params = (32 * 64 + 64 + 64 * 1 + 1) * 4
    assert expect_params <= rep.param_bytes <= expect_params + 64
    # the backward op holds every forward residual -> it is the peak
    assert rep.peak_op_type == "backward"
    assert rep.peak_bytes > expect_params
    assert rep.act_bytes_at_peak == rep.peak_bytes - rep.param_bytes


def test_shard_divisors_and_sharded_estimate():
    assert memory.shard_divisors({"dp": 8, "mp": 2}) == (2, 8)
    assert memory.shard_divisors({"data": 4}) == (1, 4)
    assert memory.shard_divisors({"model": 4}) == (4, 1)
    assert memory.shard_divisors(None) == (1, 1)
    x, loss = _fc_chain(widths=(32, 64, 1))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    prog = fluid.default_main_program()
    r1 = memory.estimate(prog, fetch_names=[loss.name], default_dim=8)
    r4 = memory.estimate(prog, fetch_names=[loss.name], default_dim=8,
                         param_shards=4, act_shards=2)
    assert r4.param_bytes == pytest.approx(r1.param_bytes / 4, abs=64)
    assert r4.act_bytes_at_peak <= r1.act_bytes_at_peak / 2 + 64
    assert r4.peak_bytes < r1.peak_bytes


def test_propagate_minus_one_batch_feeds_liveness():
    # satellite: -1 batch dims resolved at two default_dims -> the
    # inferred env feeds liveness and the activation peak scales ~4x
    x, loss = _fc_chain(widths=(16, 32, 1), batch=None)
    prog = fluid.default_main_program()
    reps = {}
    for dd in (8, 32):
        feed = shapes.feed_specs_from_program(
            prog, feed_names=["x"], default_dim=dd)
        env, _ = shapes.propagate(prog, feed_specs=feed, default_dim=dd,
                                  check_declared=False)
        assert env["x"].shape[0] == dd
        reps[dd] = memory.estimate(prog, env=env, feed_specs=feed,
                                   fetch_names=[loss.name])
    r8, r32 = reps[8], reps[32]
    assert r8.param_bytes == r32.param_bytes  # params batch-independent
    assert r32.act_bytes_at_peak == pytest.approx(
        4 * r8.act_bytes_at_peak, rel=0.05)


def test_live_report_nested_while_cond_closure_reads():
    # satellite: a global var read ONLY two sub-block levels down
    # (while -> cond branch) must be seen by the liveness walk
    deep = fluid.layers.fill_constant([1], "float32", 3.0)
    i = fluid.layers.fill_constant([1], "float32", 0.0)
    n = fluid.layers.fill_constant([1], "float32", 5.0)
    acc = fluid.layers.fill_constant([1], "float32", 0.0)
    junk = fluid.layers.elementwise_mul(n, n)  # nothing reads this
    c = fluid.layers.less_than(i, n)
    w = fluid.layers.While(c)
    with w.block():
        t = fluid.layers.fill_constant([1], "float32", 2.0)
        c2 = fluid.layers.less_than(i, t)
        r = fluid.layers.cond(
            c2, lambda: fluid.layers.elementwise_add(acc, deep),
            lambda: fluid.layers.elementwise_sub(acc, deep))
        fluid.layers.assign(r, acc)
        fluid.layers.increment(i, value=1.0)
        fluid.layers.less_than(i, n, cond=c)
    prog = fluid.default_main_program()
    gb = prog.global_block()
    live, dead_ops, dead_vars = walker.live_report(
        prog, fetch_names=[acc.name, i.name])
    while_idx = [k for k, op in enumerate(gb.ops) if op.type == "while"]
    assert while_idx and while_idx[0] in live
    # the nested closure read keeps `deep`'s producer live
    deep_idx = [k for k, op in enumerate(gb.ops)
                if deep.name in [m for ns in op.outputs.values()
                                 for m in ns]]
    assert deep_idx[0] in live
    assert deep.name not in dead_vars
    # the untouched global op IS dead
    assert any(op.type == "elementwise_mul" for _k, op in dead_ops)
    assert junk.name in dead_vars
    # _op_reads on the while op surfaces the two-level-deep read
    assert deep.name in walker._op_reads(prog, gb.ops[while_idx[0]])
    # and the memory estimate keeps `deep` resident through the while
    rep = memory.estimate(prog, fetch_names=[acc.name, i.name],
                          default_dim=4)
    assert rep.peak_bytes > 0 and rep.n_ops == len(gb.ops)


# ---------------------------------------------------------------------------
# executor gate: predicted-OOM before compile_start + gauges
# ---------------------------------------------------------------------------
def test_executor_gate_rejects_predicted_oom(monkeypatch):
    x, loss = _fc_chain(widths=(64, 128, 1))
    exe = _exe()
    exe.run(fluid.default_startup_program())
    monkeypatch.setenv(costs.HBM_BYTES_ENV, "1000")  # ~1 KB "device"
    before = len(obs.get_recorder().of("compile_start"))
    with pytest.raises(ProgramVerifyError) as ei:
        exe.run(feed={"x": np.ones((16, 64), np.float32)},
                fetch_list=[loss])
    msg = str(ei.value)
    assert "predicted-oom" in msg
    assert "exceeds device HBM" in msg
    assert "op" in msg  # op attribution present
    # the gate fired BEFORE any compile started
    assert len(obs.get_recorder().of("compile_start")) == before


def test_executor_publishes_analysis_gauges(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_ANALYSIS", "full")
    monkeypatch.setenv(costs.PEAK_FLOPS_ENV, "1e12")
    monkeypatch.setenv(costs.HBM_BW_ENV, "1e11")
    x, loss = _fc_chain()
    exe = _exe()
    exe.run(fluid.default_startup_program())
    exe.run(feed={"x": np.ones((4, 16), np.float32)}, fetch_list=[loss])
    g = obs.snapshot()["gauges"]
    assert g.get("analysis.predicted_peak_hbm", 0) > 0
    assert 0 < g.get("analysis.predicted_mfu", 0) <= 1.0


# ---------------------------------------------------------------------------
# serving admission
# ---------------------------------------------------------------------------
def _save_infer_model(tmp_path, width=6):
    x = fluid.data(name="x", shape=[None, width], dtype="float32")
    out = fluid.layers.fc(x, size=4, act="softmax")
    exe = _exe()
    exe.run(fluid.default_startup_program())
    d = str(tmp_path / "model")
    fluid.io.save_inference_model(d, ["x"], [out], exe)
    return d


def test_serving_bucket_ladder_admission(tmp_path):
    from paddle_tpu.fluid.inference import Predictor
    from paddle_tpu.serving import BucketSpec, ServingEngine

    spec = BucketSpec({"x": (6,)}, batch_sizes=(1, 2, 8))
    assert spec.max_batch_size == 8
    fs = spec.feed_specs(8)
    assert fs["x"].shape == (8, 6) and fs["x"].dtype == np.float32

    pred = Predictor.from_model(_save_infer_model(tmp_path))
    eng = ServingEngine(pred, buckets=[spec], name="adm",
                        auto_start=False)
    results = eng.check_hbm_budget(budget_bytes=10**9)
    assert len(results) == 1  # one ladder, priced at its worst bucket
    assert results[0][1] == 8
    g = obs.snapshot()["gauges"]
    assert g.get("serving.predicted_peak_hbm.adm", 0) > 0
    with pytest.raises(ProgramVerifyError) as ei:
        eng.check_hbm_budget(budget_bytes=64)
    assert "predicted-oom" in str(ei.value)
    assert "batch 8" in str(ei.value)
    assert obs.get_recorder().of("bucket_rejected")
    # warmup runs the check first: same tiny budget via env
    # (no device profile on CPU otherwise -> check would no-op)
    import os
    os.environ[costs.HBM_BYTES_ENV] = "64"
    try:
        with pytest.raises(ProgramVerifyError):
            eng.warmup()
    finally:
        del os.environ[costs.HBM_BYTES_ENV]
    # ample budget: warmup compiles the ladder
    rep = eng.warmup()
    assert [r["batch_size"] for r in rep] == [1, 2, 8]


# ---------------------------------------------------------------------------
# lint upgrade: intensity-ranked hottest ops
# ---------------------------------------------------------------------------
def test_lint_hot_unpadded_matmul_ranked():
    x = fluid.data(name="x", shape=[4, 5], dtype="float32")
    h = fluid.layers.fc(x, size=3)  # 5x3 weight: badly unaligned
    report = analysis.analyze(
        fluid.default_main_program(), feed_names=["x"],
        fetch_names=[h.name], platform="tpu", level="full")
    perf = report.by_severity("perf")
    names = {f.check for f in perf}
    assert "hot-unpadded-matmul" in names
    assert not report.findings  # perf hints never fail 'lint clean'
    f = next(f for f in perf if f.check == "hot-unpadded-matmul")
    assert "rank #" in f.message and "% of program FLOPs" in f.message
    hot = report.meta["hottest_ops"]
    assert hot and hot[0]["rank"] == 1
    assert all(h0["flops"] >= h1["flops"]
               for h0, h1 in zip(hot, hot[1:]))


# ---------------------------------------------------------------------------
# CLI --cost / --json-out / exit codes
# ---------------------------------------------------------------------------
def test_cli_cost_json_roundtrip(tmp_path):
    from paddle_tpu.analysis import cli

    model_dir = _save_infer_model(tmp_path)
    out_path = tmp_path / "report.json"
    argv = [model_dir, "--platform", "cpu", "--cost", "--device", "v5e",
            "--json-out", str(out_path)]
    bufs = []
    for _ in range(2):
        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = cli.main(argv)
        assert rc == 0
        bufs.append(buf.getvalue())
    assert bufs[0] == bufs[1]  # stable across runs
    doc = json.loads(bufs[0])
    assert json.loads(out_path.read_text()) == doc  # file == stdout
    c = doc["cost"]
    assert c["total_flops"] > 0
    assert c["device"]["name"] == "v5e"
    assert 0 < c["predicted_mfu"] <= 1.0
    assert c["memory"]["peak_bytes"] > 0
    assert c["hottest_ops"]


def test_cli_cost_oom_exits_1(tmp_path, monkeypatch):
    from paddle_tpu.analysis import cli

    model_dir = _save_infer_model(tmp_path)
    monkeypatch.setenv(costs.HBM_BYTES_ENV, "64")
    buf = io.StringIO()
    with redirect_stdout(buf):
        rc = cli.main([model_dir, "--platform", "cpu", "--cost"])
    assert rc == 1
    assert "predicted-oom" in buf.getvalue()
    # usage errors stay exit 2
    assert cli.main([str(tmp_path / "missing"), "--cost"]) == 2


def test_cli_mesh_divides_footprints(tmp_path):
    from paddle_tpu.analysis import cli

    model_dir = _save_infer_model(tmp_path)

    def run(argv):
        buf = io.StringIO()
        with redirect_stdout(buf):
            assert cli.main(argv) == 0
        return json.loads(buf.getvalue())

    base = run([model_dir, "--platform", "cpu", "--cost"])
    sharded = run([model_dir, "--platform", "cpu", "--cost",
                   "--mesh", "dp=4,mp=2"])
    assert (sharded["cost"]["memory"]["peak_bytes"]
            < base["cost"]["memory"]["peak_bytes"])
    assert cli.main([model_dir, "--mesh", "garbage"]) == 2


# ---------------------------------------------------------------------------
# apply_gradients grad_clip (satellite 2)
# ---------------------------------------------------------------------------
def _train_once(clip):
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    x = fluid.data(name="x", shape=[None, 8], dtype="float32")
    y = fluid.data(name="y", shape=[None, 1], dtype="float32")
    p = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(p, y))
    opt = fluid.optimizer.SGD(learning_rate=1.0)
    params_grads = opt.backward(loss)
    opt.apply_gradients(params_grads, grad_clip=clip)
    exe = _exe()
    exe.run(fluid.default_startup_program())
    w0 = np.array(fluid.global_scope().find_var("fc_0.w_0").get_tensor())
    exe.run(feed={"x": np.full((4, 8), 5.0, np.float32),
                  "y": np.zeros((4, 1), np.float32)},
            fetch_list=[loss])
    w1 = np.array(fluid.global_scope().find_var("fc_0.w_0").get_tensor())
    return float(np.linalg.norm(w1 - w0))


def test_apply_gradients_honors_grad_clip():
    from paddle_tpu.fluid.dygraph_grad_clip import GradClipByGlobalNorm

    unclipped = _train_once(None)
    clipped = _train_once(GradClipByGlobalNorm(0.01))
    assert unclipped > 1.0          # huge inputs -> huge raw update
    assert clipped <= 0.01 + 1e-4   # update norm bounded by the clip
    assert clipped < unclipped / 10


def test_apply_gradients_rejects_non_gradclip():
    opt = fluid.optimizer.SGD(learning_rate=1.0)
    with pytest.raises(TypeError, match="GradClipBase"):
        opt.apply_gradients([], grad_clip=42)
