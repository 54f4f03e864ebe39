"""Observability spine: telemetry hub, spans, flight recorder, crash
dumps, and the end-to-end instrumented executor/resilience session
(ISSUE 3 acceptance)."""
import json
import os
import re
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid.resilience import (
    EventLog, FaultInjector, GuardedExecutor, TrainGuard,
)


@pytest.fixture(autouse=True)
def _fresh_hub(monkeypatch):
    """Every test gets an empty hub/ring and a clean env switch."""
    monkeypatch.delenv(obs.TELEMETRY_ENV, raising=False)
    monkeypatch.delenv(obs.CRASH_DUMP_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()
    FaultInjector.uninstall()


def _build_sgd_program():
    x = fluid.data("ox", shape=[None, 4], dtype="float32")
    y = fluid.data("oy", shape=[None, 1], dtype="float32")
    p = fluid.layers.fc(x, 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(p, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    return loss


def _feed(n=4):
    rng = np.random.default_rng(0)
    xv = rng.standard_normal((n, 4)).astype("float32")
    return {"ox": xv, "oy": xv.sum(1, keepdims=True).astype("float32")}


# ---------------------------------------------------------------------------
# hub primitives
# ---------------------------------------------------------------------------


class TestHub:
    def test_counters_gauges_histograms(self):
        obs.inc("a.b")
        obs.inc("a.b", 2)
        obs.set_gauge("g", 1.5)
        for v in (0.1, 0.2, 0.3):
            obs.observe("h", v)
        snap = obs.snapshot()
        assert snap["counters"]["a.b"] == 3
        assert snap["gauges"]["g"] == 1.5
        h = snap["histograms"]["h"]
        assert h["count"] == 3
        assert h["min"] == pytest.approx(0.1)
        assert h["max"] == pytest.approx(0.3)
        assert h["mean"] == pytest.approx(0.2)

    def test_histogram_reservoir_bounded(self):
        hist = obs.Histogram(cap=16)
        for i in range(1000):
            hist.observe(float(i))
        assert hist.count == 1000
        assert len(hist._reservoir) == 16
        s = hist.summary()
        assert s["max"] == 999.0 and s["min"] == 0.0
        # reservoir keeps the newest observations
        assert s["p50"] >= 984.0

    def test_off_mode_writes_nothing(self, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
        obs.inc("x")
        obs.observe("y", 1.0)
        obs.set_gauge("z", 2.0)
        obs.event("boom", source="test")
        with obs.span("dead"):
            pass
        snap = obs.snapshot()
        assert snap["counters"] == {}
        assert snap["gauges"] == {}
        assert snap["histograms"] == {}
        assert obs.get_recorder().tail() == []
        assert snap["mode"] == "off"

    def test_mode_parsing(self, monkeypatch):
        assert obs.mode() == obs.ON
        for v in ("off", "OFF", "0", "false", "none"):
            monkeypatch.setenv(obs.TELEMETRY_ENV, v)
            assert obs.mode() == obs.OFF
        monkeypatch.setenv(obs.TELEMETRY_ENV, "trace")
        assert obs.mode() == obs.TRACE
        monkeypatch.setenv(obs.TELEMETRY_ENV, "on")
        assert obs.mode() == obs.ON

    def test_event_counts_and_records(self):
        obs.event("retry", source="guard", attempt=1)
        assert obs.get_telemetry().counter("guard.retry") == 1
        evs = obs.get_recorder().of("retry")
        assert len(evs) == 1
        assert evs[0]["source"] == "guard"
        assert evs[0]["attempt"] == 1


# ---------------------------------------------------------------------------
# prom exposition
# ---------------------------------------------------------------------------

_PROM_LINE = re.compile(
    r"^(?:# (?:TYPE|HELP) [a-zA-Z_][a-zA-Z0-9_]*(?: \w+)?$"
    r"|[a-zA-Z_][a-zA-Z0-9_]*(?:\{[^}]*\})? -?[0-9.eE+-]+$)")


class TestProm:
    def test_render_prom_parses_line_by_line(self):
        obs.inc("executor.cache_hit", 3)
        obs.set_gauge("reader.queue_depth", 4)
        obs.observe("checkpoint.save_seconds", 0.25)
        obs.observe("checkpoint.save_seconds", 0.75)
        text = obs.render_prom()
        lines = text.strip().split("\n")
        assert lines
        for line in lines:
            assert _PROM_LINE.match(line), "bad prom line: %r" % line
        assert "paddle_tpu_executor_cache_hit 3" in lines
        assert "paddle_tpu_reader_queue_depth 4" in lines
        assert "paddle_tpu_checkpoint_save_seconds_count 2" in lines
        # default exposition is a proper Prometheus histogram
        assert "# TYPE paddle_tpu_checkpoint_save_seconds histogram" in lines
        buckets = [
            l for l in lines
            if l.startswith('paddle_tpu_checkpoint_save_seconds_bucket{le=')]
        assert buckets
        # the +Inf bucket closes the series and equals the count
        assert any('le="+Inf"} 2' in l for l in buckets)
        # cumulative: bucket counts never decrease
        counts = [float(l.rsplit(" ", 1)[1]) for l in buckets]
        assert counts == sorted(counts)
        assert any(
            l.startswith("paddle_tpu_checkpoint_save_seconds_sum ")
            for l in lines)

    def test_render_prom_summary_fallback(self, monkeypatch):
        obs.observe("checkpoint.save_seconds", 0.25)
        obs.observe("checkpoint.save_seconds", 0.75)
        # explicit style argument restores the legacy quantile lines
        text = obs.render_prom(style="summary")
        lines = text.strip().split("\n")
        for line in lines:
            assert _PROM_LINE.match(line), "bad prom line: %r" % line
        assert any(
            l.startswith('paddle_tpu_checkpoint_save_seconds{quantile=')
            for l in lines)
        assert not any("_bucket{le=" in l for l in lines)
        # ... and so does the env flag with no argument
        monkeypatch.setenv(obs.PROM_STYLE_ENV, "summary")
        env_lines = obs.render_prom().strip().split("\n")
        assert any(
            l.startswith('paddle_tpu_checkpoint_save_seconds{quantile=')
            for l in env_lines)

    def test_render_prom_empty_hub(self):
        assert obs.render_prom() == ""


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


class TestSpans:
    def test_nesting_and_histograms(self):
        with obs.span("outer"):
            time.sleep(0.01)
            with obs.span("inner"):
                time.sleep(0.01)
                active = obs.active_spans()
        frames = active["MainThread"]
        assert [n for n, _ in frames] == ["outer", "inner"]
        snap = obs.snapshot()
        outer = snap["histograms"]["span.outer.seconds"]
        inner = snap["histograms"]["span.inner.seconds"]
        assert outer["count"] == inner["count"] == 1
        assert outer["sum"] >= inner["sum"] >= 0.01
        # everything popped: no active spans remain
        assert obs.active_spans() == {}

    def test_span_pops_on_exception(self):
        with pytest.raises(ValueError):
            with obs.span("boom"):
                raise ValueError("x")
        assert obs.active_spans() == {}
        assert obs.snapshot()["histograms"]["span.boom.seconds"]["count"] \
            == 1

    def test_trace_mode_records_span_events(self, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "trace")
        with obs.span("outer"):
            with obs.span("inner"):
                pass
        evs = obs.get_recorder().of("span")
        names = [(e["name"], e["parent"]) for e in evs]
        assert ("inner", "outer") in names
        assert ("outer", None) in names

    def test_on_mode_records_no_span_events(self):
        with obs.span("outer"):
            pass
        assert obs.get_recorder().of("span") == []


# ---------------------------------------------------------------------------
# flight recorder + crash dumps
# ---------------------------------------------------------------------------


class TestRecorder:
    def test_ring_bounded_and_ordered(self):
        rec = obs.FlightRecorder(maxlen=8)
        for i in range(20):
            rec.record("tick", i=i)
        evs = rec.tail()
        assert len(evs) == 8
        assert [e["i"] for e in evs] == list(range(12, 20))
        assert all(evs[j]["ts"] <= evs[j + 1]["ts"]
                   for j in range(len(evs) - 1))

    def test_dump_jsonl(self, tmp_path):
        rec = obs.FlightRecorder()
        rec.record("a", value=np.float32(1.5))
        rec.record("b", arr=np.arange(3))
        path = rec.dump_jsonl(str(tmp_path / "flight.jsonl"))
        lines = [json.loads(l) for l in open(path)]
        assert [l["kind"] for l in lines] == ["a", "b"]
        assert lines[0]["value"] == 1.5
        assert lines[1]["arr"] == [0, 1, 2]

    def test_eventlog_interleaves_into_one_stream(self, tmp_path):
        rec = obs.FlightRecorder()
        res_log = EventLog(recorder=rec, source="resilience")
        fleet_log = EventLog(recorder=rec, source="fleet")
        res_log.emit("step", step=1)
        fleet_log.emit("worker_dead", worker=2)
        res_log.emit("save", step=1)
        path = rec.dump_jsonl(str(tmp_path / "joint.jsonl"))
        lines = [json.loads(l) for l in open(path)]
        assert [(l["kind"], l["source"]) for l in lines] == [
            ("step", "resilience"), ("worker_dead", "fleet"),
            ("save", "resilience")]
        ts = [l["ts"] for l in lines]
        assert ts == sorted(ts)

    def test_crash_dump_contents(self, tmp_path):
        obs.inc("executor.cache_miss")
        obs.get_recorder().record("compile_done", seconds=1.0)
        target = str(tmp_path / "crash.json")
        with obs.span("executor.run"):
            try:
                raise RuntimeError("chip fell over")
            except RuntimeError as e:
                path = obs.get_recorder().crash_dump(target, exc=e)
        assert path == target
        doc = json.load(open(path))
        assert doc["exception"]["type"] == "RuntimeError"
        assert "chip fell over" in doc["exception"]["message"]
        assert "RuntimeError" in doc["exception"]["traceback"]
        assert [e["kind"] for e in doc["events"]] == ["compile_done"]
        spans = doc["active_spans"]["MainThread"]
        assert spans[0][0] == "executor.run"
        assert doc["telemetry"]["counters"]["executor.cache_miss"] == 1

    def test_uncaught_fault_leaves_crash_dump(self, tmp_path):
        """A process killed by an uncaught (injected) fault leaves the
        black box behind through the auto-installed excepthook: the
        exception, the events before it, the telemetry snapshot."""
        import subprocess
        import sys

        dump = str(tmp_path / "crash.json")
        src = (
            "import numpy as np\n"
            "import paddle_tpu.fluid as fluid\n"
            "x = fluid.data('dx', shape=[None, 4], dtype='float32')\n"
            "loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 1))\n"
            "fluid.optimizer.SGD(0.05).minimize(loss)\n"
            "exe = fluid.Executor()\n"
            "exe.run(fluid.default_startup_program())\n"
            "feed = {'dx': np.ones((4, 4), 'float32')}\n"
            "for _ in range(2):\n"
            "    exe.run(feed=feed, fetch_list=[loss])\n")
        # run-site checks: 1 = startup, 2 = first step, 3 = the fault
        env = dict(os.environ, JAX_PLATFORMS="cpu",
                   PADDLE_TPU_FAULT_SPEC="run:at=3:RuntimeError",
                   PADDLE_TPU_CRASH_DUMP=dump)
        env.pop("PADDLE_TPU_TELEMETRY", None)
        r = subprocess.run([sys.executable, "-c", src], env=env,
                           capture_output=True, text=True, timeout=240,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        assert r.returncode != 0, r.stdout[-500:]
        doc = json.load(open(dump))
        assert doc["exception"]["type"] == "RuntimeError"
        assert "injected fault" in doc["exception"]["message"]
        assert "compile_done" in [e["kind"] for e in doc["events"]]
        assert "counters" in doc["telemetry"]

    def test_crash_dump_env_path(self, monkeypatch, tmp_path):
        target = str(tmp_path / "env_crash.json")
        monkeypatch.setenv(obs.CRASH_DUMP_ENV, target)
        assert obs.crash_dump_path() == target
        assert obs.get_recorder().crash_dump() == target
        assert os.path.exists(target)

    def test_explicit_recorder_ignores_off_mode(self, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
        rec = obs.FlightRecorder()
        rec.record("still_here")
        assert len(rec.tail()) == 1
        # ...but the GLOBAL recorder follows the switch
        obs.get_recorder().record("dropped")
        assert obs.get_recorder().tail() == []


# ---------------------------------------------------------------------------
# instrumented executor
# ---------------------------------------------------------------------------


class TestExecutorInstrumentation:
    def test_cache_hit_miss_two_run_session(self):
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        obs.reset()  # scope to the scripted session
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss])
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss])
        snap = obs.snapshot()
        assert snap["counters"]["executor.cache_miss"] == 1
        assert snap["counters"]["executor.cache_hit"] == 1
        hist = snap["histograms"]
        assert hist["executor.compile_seconds"]["count"] == 1
        # phase spans: one per run
        for name in ("executor.run", "executor.feed_convert",
                     "executor.device_compute", "executor.fetch"):
            assert hist["span.%s.seconds" % name]["count"] == 2, name
        kinds = [e["kind"] for e in obs.get_recorder().tail()]
        assert kinds.count("compile_start") == 1
        assert kinds.count("compile_done") == 1

    def test_cache_evict_counted(self, monkeypatch):
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe._cache_cap = 1
        exe.run(fluid.default_startup_program())
        obs.reset()
        exe.run(fluid.default_main_program(), feed=_feed(4),
                fetch_list=[loss])
        # different batch size -> new signature -> evicts the first
        exe.run(fluid.default_main_program(), feed=_feed(8),
                fetch_list=[loss])
        snap = obs.snapshot()
        assert snap["counters"]["executor.cache_miss"] == 2
        assert snap["counters"]["executor.cache_evict"] >= 1

    def test_disabled_mode_overhead(self, monkeypatch):
        """The off path must stay cheap: a cached executor.run traverses
        ~10 guarded sites (4 span enter/exits, the cache-hit counter,
        the trace check); their total off-mode cost must stay under 5%
        of the per-step time of a tight run loop."""
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        feed = _feed()
        monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
        exe.run(fluid.default_main_program(), feed=feed,
                fetch_list=[loss])  # warm the executable cache
        steps = 30
        t0 = time.perf_counter()
        for _ in range(steps):
            exe.run(fluid.default_main_program(), feed=feed,
                    fetch_list=[loss])
        per_step = (time.perf_counter() - t0) / steps
        calls = 50000
        t0 = time.perf_counter()
        for _ in range(calls):
            obs.inc("off.overhead")
        per_call = (time.perf_counter() - t0) / calls
        assert obs.get_telemetry().counter("off.overhead") == 0
        sites = 15  # upper bound on mode checks in one cached run()
        assert sites * per_call < 0.05 * per_step, (
            "off-mode guards cost %.1fus/step (%.0fns/site) vs "
            "%.1fus/step run loop"
            % (1e6 * sites * per_call, 1e9 * per_call, 1e6 * per_step))

    def test_trace_mode_blocks_and_spans(self, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "trace")
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        exe.run(fluid.default_main_program(), feed=_feed(),
                fetch_list=[loss])
        span_names = {e["name"] for e in obs.get_recorder().of("span")}
        assert {"executor.run", "executor.feed_convert",
                "executor.device_compute",
                "executor.fetch"} <= span_names


# ---------------------------------------------------------------------------
# the acceptance session (ISSUE 3)
# ---------------------------------------------------------------------------


@pytest.mark.faults
class TestAcceptanceSession:
    def _scripted_session(self, tmp_path):
        """2 executor.run calls, one injected run fault, one checkpoint
        save — the canonical flight-recorder session."""
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        obs.reset()  # the session starts AFTER startup
        FaultInjector.install("run:at=1:RuntimeError")
        guard = TrainGuard(
            exe, program=fluid.default_main_program(),
            ckpt_dir=str(tmp_path / "ck"), fetch_list=[loss],
            feed_fn=lambda step: _feed(), save_every=2, final_save=False,
            backoff_base=0.001)
        guard.train(num_steps=2)
        FaultInjector.uninstall()

    def test_snapshot_counts(self, tmp_path):
        self._scripted_session(tmp_path)
        snap = obs.snapshot()
        c = snap["counters"]
        assert c["executor.cache_miss"] == 1, c
        assert c["executor.cache_hit"] == 1, c
        assert c["guard.retry"] == 1, c
        assert c["resilience.save"] == 1, c
        hist = snap["histograms"]
        assert hist["checkpoint.save_seconds"]["count"] == 1
        assert hist["checkpoint.save_seconds"]["sum"] > 0
        # the ring interleaves guard + resilience + executor streams
        evs = obs.get_recorder().tail()
        kinds = [e["kind"] for e in evs]
        assert "retry" in kinds and "save" in kinds \
            and "compile_done" in kinds
        ts = [e["ts"] for e in evs]
        assert ts == sorted(ts)
        # exactly once each: no double-count through the relay
        assert kinds.count("retry") == 1
        assert kinds.count("save") == 1

    def test_off_mode_produces_none(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
        self._scripted_session(tmp_path)
        snap = obs.snapshot()
        assert snap["counters"] == {}
        assert snap["histograms"] == {}
        assert obs.get_recorder().tail() == []


# ---------------------------------------------------------------------------
# shared-recorder wiring (satellite)
# ---------------------------------------------------------------------------


class TestSharedRecorder:
    def test_trainguard_custom_recorder_stream(self, tmp_path):
        rec = obs.FlightRecorder()
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        guard = TrainGuard(
            exe, program=fluid.default_main_program(),
            ckpt_dir=str(tmp_path / "ck"), fetch_list=[loss],
            feed_fn=lambda step: _feed(), save_every=2, final_save=False,
            recorder=rec)
        guard.train(num_steps=2)
        kinds = [e["kind"] for e in rec.tail()]
        assert "step" in kinds and "save" in kinds
        path = rec.dump_jsonl(str(tmp_path / "stream.jsonl"))
        lines = [json.loads(l) for l in open(path)]
        assert all("ts" in l and "kind" in l for l in lines)

    def test_guarded_executor_recorder_param(self):
        rec = obs.FlightRecorder()
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        FaultInjector.install("run:at=1:RuntimeError")
        guard = GuardedExecutor(exe, backoff_base=0.001, recorder=rec)
        guard.run(fluid.default_main_program(), feed=_feed(),
                  fetch_list=[loss])
        FaultInjector.uninstall()
        assert [e["kind"] for e in rec.tail()] == ["retry"]

    def test_fleetguard_recorder_param(self):
        from paddle_tpu.parallel.elastic import FleetGuard

        rec = obs.FlightRecorder()
        loss = _build_sgd_program()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        guard = FleetGuard(
            exe, program=fluid.default_main_program(), worker_index=0,
            world_size=1, fetch_list=[loss],
            feed_fn=lambda step, g: _feed(), recorder=rec)
        guard.train(num_steps=2)
        kinds = [e["kind"] for e in rec.tail()]
        assert kinds.count("step") == 2
        assert "final" in kinds


# ---------------------------------------------------------------------------
# reader + profiler instrumentation
# ---------------------------------------------------------------------------


class TestPeripheralInstrumentation:
    def test_reader_gauges(self):
        reader = fluid.layers.py_reader(
            capacity=4, shapes=[(2, 3)], dtypes=["float32"])

        def gen():
            for _ in range(3):
                yield [np.ones((2, 3), "float32")]

        reader.decorate_tensor_provider(gen)
        reader.start()
        for _ in range(3):
            assert reader._next_feed() is not None
        snap = obs.snapshot()
        assert "reader.queue_depth" in snap["gauges"]
        assert snap["histograms"]["reader.pop_wait_seconds"]["count"] == 3

    def test_profiler_creates_requested_dir(self, tmp_path):
        from paddle_tpu.fluid import profiler as P

        target = str(tmp_path / "not" / "yet" / "there")
        P.start_profiler("All", profile_path=target)
        try:
            assert os.path.isdir(target)
        finally:
            P.stop_profiler(profile_path=target)
        assert P._trace_dir is None and P._start_time is None
        c = obs.snapshot()["counters"]
        assert c.get("profiler.trace_start") == 1
        assert c.get("profiler.trace_stop") == 1

    def test_profiler_start_failure_is_loud_and_consistent(
            self, monkeypatch, tmp_path):
        import jax

        from paddle_tpu.fluid import profiler as P

        def _boom(path):
            raise RuntimeError("profiler backend unavailable")

        monkeypatch.setattr(jax.profiler, "start_trace", _boom)
        with pytest.warns(UserWarning, match="start_trace"):
            P.start_profiler("All", profile_path=str(tmp_path / "t"))
        assert P._trace_dir is None and P._start_time is None
        assert obs.snapshot()["counters"]["profiler.trace_error"] == 1
        # stop after a failed start: clean no-op
        P.stop_profiler()

    def test_collective_dispatch_counter(self):
        from paddle_tpu.ops import collective_ops as C

        C._guard("c_allreduce_sum")
        C._guard("c_allgather")
        c = obs.snapshot()["counters"]
        assert c["collective.dispatch"] == 2
        assert c["collective.dispatch.c_allreduce_sum"] == 1
        assert c["collective.dispatch.c_allgather"] == 1
