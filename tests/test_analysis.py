"""Static analyzer tests: zoo cleanliness, seeded defects with op
attribution, the CLI, the executor/predictor/guard gates, and the scope
sanitizer. See ``paddle_tpu/analysis/``."""
import json
import threading

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import analysis
from paddle_tpu.analysis import diagnostics, sanitizer, tpu_lint, verifier

pytestmark = pytest.mark.analysis


def _exe():
    return fluid.Executor(fluid.CPUPlace())


def _analyze_current(fetch, feed_names=None, platform="cpu", **kw):
    prog = fluid.default_main_program()
    fetch_names = [f.name if hasattr(f, "name") else f for f in fetch]
    if feed_names is None:
        gb = prog.global_block()
        feed_names = [n for n, v in gb.vars.items() if v.is_data]
    return analysis.analyze(prog, feed_names=feed_names,
                            fetch_names=fetch_names, platform=platform,
                            **kw)


# ---------------------------------------------------------------------------
# zoo cleanliness: full analyzer, zero findings on real model programs
# ---------------------------------------------------------------------------
def _assert_clean(report):
    assert not report.findings, "\n" + str(report)


def test_clean_fit_a_line():
    x = fluid.layers.data(name="x", shape=[13], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
    _assert_clean(_analyze_current([loss]))


def test_clean_conv_classifier():
    img = fluid.layers.data(name="img", shape=[1, 28, 28], dtype="float32")
    label = fluid.layers.data(name="label", shape=[1], dtype="int64")
    conv = fluid.layers.conv2d(img, num_filters=8, filter_size=3, act="relu")
    pool = fluid.layers.pool2d(conv, pool_size=2, pool_stride=2)
    logits = fluid.layers.fc(pool, size=10)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, label))
    fluid.optimizer.Adam(learning_rate=1e-3).minimize(loss)
    _assert_clean(_analyze_current([loss]))


def test_clean_static_rnn():
    t, b, d = 4, 3, 5
    x = fluid.data(name="x", shape=[t, b, d], dtype="float32")
    h0 = fluid.layers.fill_constant([b, d], "float32", 0.0)
    rnn = fluid.layers.StaticRNN()
    with rnn.step():
        xt = rnn.step_input(x)
        h_prev = rnn.memory(init=h0)
        h = fluid.layers.elementwise_add(xt, h_prev)
        rnn.update_memory(h_prev, h)
        rnn.step_output(h)
    out = rnn()
    _assert_clean(_analyze_current([out], feed_names=["x"]))


def test_clean_while_loop():
    i = fluid.layers.fill_constant([1], "float32", 0.0)
    n = fluid.layers.fill_constant([1], "float32", 5.0)
    acc = fluid.layers.fill_constant([1], "float32", 0.0)
    cond = fluid.layers.less_than(i, n)
    w = fluid.layers.While(cond)
    with w.block():
        fluid.layers.increment(acc, value=2.0)
        fluid.layers.increment(i, value=1.0)
        fluid.layers.less_than(i, n, cond=cond)
    _assert_clean(_analyze_current([acc, i], feed_names=[]))


def test_clean_cond():
    x = fluid.data(name="x", shape=[1], dtype="float32")
    t = fluid.layers.fill_constant([1], "float32", 1.0)
    c = fluid.layers.less_than(x, t)
    out = fluid.layers.cond(
        c, lambda: fluid.layers.elementwise_add(x, t),
        lambda: fluid.layers.elementwise_sub(x, t))
    _assert_clean(_analyze_current([out]))


def test_clean_bert_tiny():
    from paddle_tpu.models import bert

    cfg = bert.bert_tiny(seq=32)
    vs = bert.build_bert_pretrain(cfg, 32)
    fluid.optimizer.Adam(1e-3).minimize(vs["loss"])
    _assert_clean(_analyze_current([vs["loss"]]))


def test_clean_inference_clone():
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, size=8, act="relu")
    pred = fluid.layers.fc(h, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    test_prog = fluid.default_main_program().clone(for_test=True)
    # the clone keeps the loss ops (the executor lowers the whole block,
    # so 'y' must still be fed); they are merely dead w.r.t. the fetch
    report = analysis.analyze(
        test_prog, feed_names=["x", "y"], fetch_names=[pred.name],
        platform="cpu", is_test=True)
    _assert_clean(report)


# ---------------------------------------------------------------------------
# seeded defects: each class caught, with op attribution
# ---------------------------------------------------------------------------
def _checks(report, severity=None):
    return {d.check for d in report.diagnostics
            if severity is None or d.severity == severity}


def test_seeded_dangling_input():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    block = fluid.default_main_program().global_block()
    out = block.create_var(name="r", shape=(4,), dtype="float32")
    block.append_op(type="relu", inputs={"X": ["nope"]},
                    outputs={"Out": ["r"]})
    report = verifier.verify(fluid.default_main_program(),
                             feed_names=["x"], fetch_names=["r"])
    errs = [d for d in report.errors if d.check == "dangling-input"]
    assert errs and errs[0].var == "nope"
    assert errs[0].op_type == "relu"
    # attribution: the callstack points at THIS file
    assert any("test_analysis" in ln for ln in errs[0].callstack)


def test_seeded_use_before_def():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    h = fluid.layers.relu(x)
    z = fluid.layers.relu(h)
    block = fluid.default_main_program().global_block()
    # swap producer and consumer: classic op-ordering bug
    block.ops[-1], block.ops[-2] = block.ops[-2], block.ops[-1]
    report = verifier.verify(fluid.default_main_program(),
                             feed_names=["x"], fetch_names=[z.name])
    errs = [d for d in report.errors if d.check == "use-before-def"]
    assert errs and errs[0].var == h.name
    assert errs[0].op_index is not None


def test_seeded_fetch_unreachable_gates_executor():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    fluid.layers.relu(x)
    block = fluid.default_main_program().global_block()
    block.create_var(name="ghost", shape=(1,), dtype="float32")
    exe = _exe()
    exe.run(fluid.default_startup_program())
    with pytest.raises(diagnostics.ProgramVerifyError) as ei:
        exe.run(feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=["ghost"])
    assert "fetch-unreachable" in str(ei.value)
    # ProgramVerifyError IS an OpLoweringError (never retried, old
    # pytest.raises sites keep passing)
    from paddle_tpu.fluid.lowering import OpLoweringError

    assert isinstance(ei.value, OpLoweringError)


def test_seeded_dtype_mismatch():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    out = fluid.layers.cast(x, "int32")
    block = fluid.default_main_program().global_block()
    block.var(out.name).dtype = "float32"  # drifted declaration
    report = _analyze_current([out])
    bad = [d for d in report.findings if d.check == "dtype-mismatch"]
    assert bad and bad[0].var == out.name
    assert bad[0].op_type == "cast"
    assert any("test_analysis" in ln for ln in bad[0].callstack)


def test_seeded_shape_infer_failure_attributed():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    block = fluid.default_main_program().global_block()
    w = block.create_var(name="w_bad", shape=(9, 3), dtype="float32")
    block.create_var(name="mm", shape=(8, 3), dtype="float32")
    block.append_op(type="mul", inputs={"X": [x.name], "Y": ["w_bad"]},
                    outputs={"Out": ["mm"]})
    report = analysis.analyze(
        fluid.default_main_program(), feed_names=["x", "w_bad"],
        fetch_names=["mm"], platform="cpu")
    errs = [d for d in report.errors if d.check == "shape-infer-failed"]
    assert errs and errs[0].op_type == "mul"
    assert errs[0].callstack  # attributed before any XLA compile


def test_seeded_donated_and_fetched():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    pred = fluid.layers.fc(x, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    report = _analyze_current([loss, "fc_0.w_0"])
    bad = [d for d in report.findings if d.check == "donated-and-fetched"]
    assert bad and bad[0].var == "fc_0.w_0"


def test_seeded_float64_creep():
    fluid.layers.data(name="x64", shape=[4], dtype="float64")
    prog = fluid.default_main_program()
    on_tpu = tpu_lint.lint(prog, platform="tpu")
    on_cpu = tpu_lint.lint(prog, platform="cpu")
    assert "float64-creep" in _checks(on_tpu, "warning")
    # on cpu it is an observation, not a finding (zoo stays clean)
    assert "float64-creep" in _checks(on_cpu, "info")
    assert not [d for d in on_cpu.findings if d.check == "float64-creep"]


def test_seeded_unbounded_shape_vocab():
    fluid.layers.data(name="seq", shape=[-1, -1, -1], dtype="float32")
    prog = fluid.default_main_program()
    report = tpu_lint.lint(prog, feed_names=["seq"])
    assert "unbounded-shape-vocab" in _checks(report, "warning")
    assert report.meta["shape_vocab_estimate"] > tpu_lint.SHAPE_VOCAB_THRESHOLD


def test_seeded_host_sync_in_scan():
    i = fluid.layers.fill_constant([1], "float32", 0.0)
    n = fluid.layers.fill_constant([1], "float32", 3.0)
    cond = fluid.layers.less_than(i, n)
    w = fluid.layers.While(cond)
    with w.block():
        fluid.layers.increment(i, value=1.0)
        blk = fluid.default_main_program().current_block()
        blk.append_op(type="py_func", inputs={"X": [i.name]},
                      outputs={"Out": [i.name]})
        fluid.layers.less_than(i, n, cond=cond)
    report = tpu_lint.lint(fluid.default_main_program())
    bad = [d for d in report.findings if d.check == "host-sync-in-scan"]
    assert bad and bad[0].block_idx != 0


def test_seeded_conflicting_write():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    h = fluid.layers.relu(x)
    block = fluid.default_main_program().global_block()
    # second op writes the same name before anything reads the first
    block.append_op(type="relu", inputs={"X": [x.name]},
                    outputs={"Out": [h.name]})
    report = verifier.verify(fluid.default_main_program(),
                             feed_names=["x"], fetch_names=[h.name])
    assert "conflicting-write" in _checks(report, "warning")


def test_seeded_uninitialized_persistable():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    out = fluid.layers.fc(x, size=2)
    report = verifier.verify(fluid.default_main_program(),
                             feed_names=["x"], fetch_names=[out.name],
                             state_names=set())  # startup never ran
    errs = [d for d in report.errors
            if d.check == "uninitialized-persistable"]
    assert errs and errs[0].op_type in ("mul", "matmul")


def test_seeded_bad_sub_block():
    x = fluid.layers.data(name="x", shape=[1], dtype="float32")
    block = fluid.default_main_program().global_block()
    block.append_op(type="while", inputs={"X": [x.name]},
                    outputs={"Out": [x.name]}, attrs={"sub_block": 99})
    report = verifier.verify(fluid.default_main_program(),
                             feed_names=["x"])
    assert "bad-sub-block" in _checks(report, "error")


# ---------------------------------------------------------------------------
# executor / predictor / guard wiring
# ---------------------------------------------------------------------------
def test_executor_verify_memoized_per_signature(monkeypatch):
    calls = []
    from paddle_tpu.analysis import analyzer as analyzer_mod

    real = analyzer_mod.analyze

    def counting(*a, **kw):
        calls.append(1)
        return real(*a, **kw)

    monkeypatch.setattr(analyzer_mod, "analyze", counting)
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    out = fluid.layers.fc(x, size=2)
    exe = _exe()
    exe.run(fluid.default_startup_program())
    from paddle_tpu import observability as obs

    def timed():  # the gate records what each verify cost
        return (obs.histogram("analysis.verify_seconds") or {"count": 0}
                )["count"]

    n0, t0 = len(calls), timed()
    feed = {"x": np.ones((2, 4), np.float32)}
    exe.run(feed=feed, fetch_list=[out])
    assert (len(calls), timed()) == (n0 + 1, t0 + 1)
    exe.run(feed=feed, fetch_list=[out])  # cached signature: no re-verify
    assert (len(calls), timed()) == (n0 + 1, t0 + 1)


def test_executor_analysis_off(monkeypatch):
    monkeypatch.setenv(analysis.ANALYSIS_ENV, "off")
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    fluid.layers.relu(x)
    block = fluid.default_main_program().global_block()
    block.create_var(name="ghost", shape=(1,), dtype="float32")
    exe = _exe()
    # gate off: the ghost fetch dies inside lowering instead (proves the
    # analyzer is the thing that moved the failure earlier)
    from paddle_tpu.fluid.lowering import OpLoweringError

    with pytest.raises(OpLoweringError) as ei:
        exe.run(feed={"x": np.ones((2, 4), np.float32)},
                fetch_list=["ghost"])
    assert not isinstance(ei.value, diagnostics.ProgramVerifyError)


def test_guarded_retry_attaches_analysis(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_FAULT_SPEC", "run:at=2:RuntimeError")
    from paddle_tpu.fluid.resilience import GuardedExecutor

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    out = fluid.layers.fc(x, size=2)
    exe = _exe()
    exe.run(fluid.default_startup_program())
    events = []
    g = GuardedExecutor(exe, max_retries=2, backoff_base=0.0,
                        on_event=events.append)
    g.run(feed={"x": np.ones((2, 4), np.float32)}, fetch_list=[out])
    retries = [e for e in events if e["kind"] == "retry"]
    assert retries and "analysis" in retries[0]
    assert isinstance(retries[0]["analysis"], str)


def test_predictor_gate_and_cli(tmp_path):
    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    h = fluid.layers.fc(x, size=8, act="relu")
    pred = fluid.layers.fc(h, size=1)
    loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = _exe()
    exe.run(fluid.default_startup_program())
    model_dir = str(tmp_path / "model")
    fluid.io.save_inference_model(model_dir, ["x"], [pred], exe)

    # predictor verifies at construction without findings
    from paddle_tpu.fluid.inference import Predictor

    p = Predictor.from_model(model_dir)
    out, = p.run({"x": np.ones((2, 16), np.float32)})
    assert out.shape == (2, 1)

    # CLI: clean model exits 0, JSON is stable across runs
    from paddle_tpu.analysis import cli

    rc = cli.main([model_dir, "--platform", "cpu"])
    assert rc == 0
    import io as _io
    from contextlib import redirect_stdout

    bufs = []
    for _ in range(2):
        buf = _io.StringIO()
        with redirect_stdout(buf):
            cli.main([model_dir, "--platform", "cpu"])
        bufs.append(buf.getvalue())
    assert bufs[0] == bufs[1]
    doc = json.loads(bufs[0])
    assert doc["report"]["counts"]["error"] == 0

    # CLI: seeded defect (raw program JSON with a dangling read) exits 1
    prog = fluid.default_main_program()
    block = prog.global_block()
    block.append_op(type="relu", inputs={"X": ["never_defined"]},
                    outputs={"Out": [h.name]})
    bad_path = tmp_path / "bad_program.json"
    bad_path.write_text(prog.to_json())
    assert cli.main([str(bad_path), "--platform", "cpu"]) == 1
    assert cli.main([str(bad_path), "--fail-on", "never"]) == 0
    assert cli.main([str(tmp_path / "missing"), "--platform", "cpu"]) == 2


# ---------------------------------------------------------------------------
# scope sanitizer
# ---------------------------------------------------------------------------
def test_sanitizer_off_by_default():
    from paddle_tpu.fluid.executor import Scope

    assert not sanitizer.armed()
    sanitizer.reset()
    s = Scope()
    t = threading.Thread(target=lambda: s.set("w", 1))
    t.start()
    t.join()
    s.set("w", 2)
    assert sanitizer.violations() == []


def test_sanitizer_detects_cross_thread_write():
    from paddle_tpu.fluid.executor import Scope

    sanitizer.arm()
    sanitizer.reset()
    try:
        s = Scope()
        s.set("w", 1)
        gate = threading.Barrier(2)

        def writer():
            gate.wait()
            s.update("w", 2)  # second LIVE thread writes the same var

        t = threading.Thread(target=writer, name="racer")
        t.start()
        gate.wait()
        t.join()
        v = sanitizer.violations()
        assert len(v) == 1
        assert v[0]["var"] == "w"
        assert "racer" in v[0]["threads"]
        assert v[0]["stacks"]  # both write sites recorded
    finally:
        sanitizer.disarm()
        sanitizer.reset()


def test_sanitizer_dead_writer_handoff_is_clean():
    from paddle_tpu.fluid.executor import Scope

    sanitizer.arm()
    sanitizer.reset()
    try:
        s = Scope()
        t = threading.Thread(target=lambda: s.set("q", 1), name="w0")
        t.start()
        t.join()  # writer exited: sequential handoff, not a race
        s.set("q", 2)
        assert sanitizer.violations() == []
    finally:
        sanitizer.disarm()
        sanitizer.reset()


# ---------------------------------------------------------------------------
# graph_wrapper.infer_shape rides on the shape pass
# ---------------------------------------------------------------------------
def test_graph_wrapper_infer_shape_repropagates():
    from paddle_tpu.fluid.contrib.slim.graph import GraphWrapper

    x = fluid.layers.data(name="x", shape=[16], dtype="float32")
    h = fluid.layers.fc(x, size=32)
    out = fluid.layers.fc(h, size=10)
    g = GraphWrapper(fluid.default_main_program(), [("x", "x")],
                     [("out", out.name)])
    # prune fc_0: downstream declared shapes go stale
    g.var("fc_0.w_0").set_shape((16, 24))
    g.var("fc_0.w_1").set_shape((24,))
    assert g.var(h.name).shape() == (-1, 32)
    g.infer_shape()
    assert g.var(h.name).shape() == (-1, 24)  # batch stays dynamic


# ---------------------------------------------------------------------------
# debugger/graphviz routed through the walker
# ---------------------------------------------------------------------------
def test_debugger_renders_control_flow(tmp_path):
    from paddle_tpu.fluid import debugger

    x = fluid.data(name="x", shape=[1], dtype="float32")
    t = fluid.layers.fill_constant([1], "float32", 1.0)
    c = fluid.layers.less_than(x, t)
    out = fluid.layers.cond(
        c, lambda: fluid.layers.elementwise_add(x, t),
        lambda: fluid.layers.elementwise_sub(x, t))
    prog = fluid.default_main_program()
    txt = debugger.pprint_program_codes(prog, fetch_names=[out.name])
    assert "body of 'cond'" in txt
    dot = tmp_path / "g.dot"
    debugger.draw_block_graphviz(prog.global_block(), path=str(dot),
                                 fetch_names=[out.name])
    src = dot.read_text()
    assert src.count("subgraph cluster") == 2  # true + false bodies


def test_debugger_marks_dead_code():
    from paddle_tpu.fluid import debugger

    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    live = fluid.layers.relu(x)
    fluid.layers.sigmoid(x)  # off the fetch slice
    prog = fluid.default_main_program()
    txt = debugger.pprint_program_codes(prog, fetch_names=[live.name])
    assert "# dead: " in txt


def test_analysis_report_json_stable():
    x = fluid.layers.data(name="x", shape=[4], dtype="float32")
    out = fluid.layers.fc(x, size=2)
    prog = fluid.default_main_program()
    r1 = analysis.analyze(prog, feed_names=["x"], fetch_names=[out.name])
    r2 = analysis.analyze(prog, feed_names=["x"], fetch_names=[out.name])
    assert r1.to_json() == r2.to_json()
    doc = json.loads(r1.to_json())
    assert set(doc) == {"checks", "counts", "findings", "meta",
                       "diagnostics"}
