"""Executable performance observatory (ISSUE 15): the process-wide
ExecutableLedger, the perf drift CLI and device-profile
auto-calibration."""
import json
import os
import warnings

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.analysis import costs
from paddle_tpu.fluid import compile_cache
from paddle_tpu.observability import __main__ as obs_cli
from paddle_tpu.observability import perf


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(obs.TELEMETRY_ENV, raising=False)
    monkeypatch.delenv(costs.CALIBRATION_ENV, raising=False)
    obs.reset()
    yield
    obs.reset()


class _FakeCompiled:
    """Quacks like a jax compiled executable."""

    def __init__(self, flops=2e9, bytes_accessed=3e8, mem=True,
                 cost_shape="dict"):
        self._flops = flops
        self._bytes = bytes_accessed
        self._mem = mem
        self._cost_shape = cost_shape

    def cost_analysis(self):
        d = {"flops": self._flops, "bytes accessed": self._bytes,
             "utilization operand 0 {}": 1.0}
        if self._cost_shape == "list":
            return [d]
        if self._cost_shape == "raise":
            raise NotImplementedError("no cost analysis on this backend")
        return d

    def memory_analysis(self):
        if not self._mem:
            raise NotImplementedError
        class _MA:
            argument_size_in_bytes = 1000
            output_size_in_bytes = 500
            temp_size_in_bytes = 2000
            alias_size_in_bytes = 300
            generated_code_size_in_bytes = 100
        return _MA()


class _Bare:
    """No cost/memory APIs at all (a deserialized disk artifact)."""


# ---------------------------------------------------------------------------
# ledger unit
# ---------------------------------------------------------------------------


class TestLedger:
    def test_register_probes_cost_and_memory(self):
        led = obs.ExecutableLedger()
        e = led.register("executor", fingerprint="f" * 64,
                         compiled=_FakeCompiled(), source="compile",
                         compile_seconds=1.5, donated=["w", "b"])
        assert e["xla"]["flops"] == 2e9
        assert e["xla"]["bytes_accessed"] == 3e8
        assert "utilization_operand_0_{}" not in e["xla"]
        # arg + out + temp + gen - alias
        assert e["memory"]["total_bytes"] == 1000 + 500 + 2000 + 100 - 300
        assert e["partial"] is False
        assert e["donated"] == ["b", "w"]
        assert e["compile_seconds"] == 1.5

    def test_list_shaped_cost_analysis(self):
        led = obs.ExecutableLedger()
        e = led.register("x", compiled=_FakeCompiled(cost_shape="list"))
        assert e["xla"]["flops"] == 2e9

    def test_partial_degradation(self):
        led = obs.ExecutableLedger()
        e = led.register("executor", fingerprint="a" * 64,
                         compiled=_Bare(), source="disk")
        assert e["xla"] is None and e["memory"] is None
        assert e["partial"] is True
        e2 = led.register("x", compiled=_FakeCompiled(cost_shape="raise",
                                                      mem=False))
        assert e2["partial"] is True

    def test_prediction_backfill_and_forward(self):
        led = obs.ExecutableLedger()
        fp = "c" * 64
        e1 = led.register("executor", fingerprint=fp)
        assert e1["predicted"] is None
        led.note_prediction(fp, {"predicted_step_seconds": 0.002,
                                 "predicted_mfu": 0.4,
                                 "device": {"peak_flops": 1e12},
                                 "junk": object()})
        assert e1["predicted"]["predicted_step_seconds"] == 0.002
        assert e1["predicted"]["device"] == {"peak_flops": 1e12}
        assert "junk" not in e1["predicted"]
        # entries registered AFTER the note pick it up too
        e2 = led.register("executor", fingerprint=fp, source="disk")
        assert e2["predicted"]["predicted_mfu"] == 0.4

    def test_note_measured(self):
        led = obs.ExecutableLedger()
        fp = "d" * 64
        e = led.register("executor", fingerprint=fp)
        led.note_measured(fp, 0.01)
        assert e["measured_step_seconds"] == 0.01
        led.note_measured(fp, -1)  # rejected
        assert e["measured_step_seconds"] == 0.01
        led.note_measured(None, 0.5)  # no-op, must not raise

    def test_snapshot_json_safe_and_tail(self):
        led = obs.ExecutableLedger()
        fp = "e" * 64
        led.register("executor", fingerprint=fp,
                     compiled=_FakeCompiled(), compile_seconds=2.0)
        led.note_prediction(fp, {"predicted_step_seconds": 0.001})
        led.note_measured(fp, 0.02)
        snap = led.snapshot()
        json.dumps(snap)  # must be serializable
        assert len(snap["entries"]) == 1
        assert snap["measured"][fp] == 0.02
        (t,) = led.tail()
        assert t["fingerprint"] == "e" * 16
        assert t["hbm_total_bytes"] == 3300
        assert t["compile_seconds"] == 2.0

    def test_maxlen_bounds_entries(self):
        led = obs.ExecutableLedger(maxlen=4)
        for i in range(10):
            led.register("k%d" % i)
        assert len(led) == 4
        assert led.entries()[0]["kind"] == "k6"

    def test_telemetry_emission(self, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "on")
        obs.reset()
        led = obs.get_ledger()
        led.register("executor", fingerprint="f" * 64,
                     compiled=_FakeCompiled(), compile_seconds=1.0)
        led.register("executor", fingerprint="f" * 64, compiled=_Bare(),
                     source="disk")
        snap = obs.snapshot()
        assert snap["counters"]["ledger.registered"] == 2
        assert snap["counters"]["ledger.partial"] == 1
        assert snap["counters"]["ledger.disk_hits"] == 1
        assert snap["gauges"]["ledger.entries"] == 2
        kinds = [e["kind"] for e in obs.get_recorder().tail()]
        assert kinds.count("executable_registered") == 2

    def test_facade_reset_clears_global_ledger(self):
        obs.get_ledger().register("x")
        assert len(obs.get_ledger()) == 1
        obs.reset()
        assert len(obs.get_ledger()) == 0


# ---------------------------------------------------------------------------
# drift rows / table / CLI
# ---------------------------------------------------------------------------


def _populated_ledger():
    led = obs.ExecutableLedger()
    fp = "a1b2" * 16
    led.register("executor", fingerprint=fp, compiled=_FakeCompiled(),
                 source="compile", compile_seconds=3.0)
    led.note_prediction(fp, {"predicted_step_seconds": 0.011,
                             "predicted_mfu": 0.31,
                             "predicted_peak_hbm_bytes": 3600.0,
                             "total_flops": 2.2e9,
                             "total_bytes": 2.8e8})
    led.note_measured(fp, 0.010)
    led.register("predict", fingerprint="ff" * 32, compiled=_Bare(),
                 source="disk")
    return led


class TestDrift:
    def test_rows_and_summary(self):
        rows = perf.drift_rows(_populated_ledger())
        assert len(rows) == 2
        full, partial = rows
        assert full["step_drift_pct"] == pytest.approx(10.0)
        assert full["hbm_drift_pct"] == pytest.approx(
            100 * (3600 - 3300) / 3300)
        assert full["flops_drift_pct"] == pytest.approx(10.0)
        assert partial["partial"] and partial["xla_gflops"] is None
        s = perf.drift_summary(rows)
        assert s["entries"] == 2 and s["partial"] == 1
        assert s["with_measured"] == 1
        assert s["mean_abs_step_drift_pct"] == pytest.approx(10.0)

    def test_render_table(self):
        txt = perf.render_drift_table(perf.drift_rows(_populated_ledger()))
        lines = txt.splitlines()
        assert lines[0].split()[:3] == ["#", "kind", "src"]
        assert "executor" in txt and "predict" in txt
        assert "+10.0" in txt  # step drift column
        # partial row renders dashes, not crashes
        assert lines[-1].count("-") >= 4

    def test_render_empty(self):
        assert perf.render_drift_table([]).splitlines()[0].startswith("#")

    def test_load_snapshot_file_dir_and_cli(self, tmp_path, capsys):
        snap = _populated_ledger().snapshot()
        # telemetry-out shape ({"ledger": ...}) in a directory with junk
        d = tmp_path / "out"
        d.mkdir()
        (d / "tel.json").write_text(json.dumps({"counters": {},
                                                "ledger": snap}))
        (d / "junk.json").write_text("{not json")
        (d / "other.json").write_text(json.dumps({"unrelated": 1}))
        loaded = perf.load_snapshot(str(d))
        assert len(loaded["entries"]) == 2
        assert obs_cli.main(["perf", str(d)]) == 0
        out = capsys.readouterr().out
        assert "executable(s)" in out and "mean |step drift|" in out
        # bare snapshot file + --out
        f = tmp_path / "snap.json"
        f.write_text(json.dumps(snap))
        o = tmp_path / "report.json"
        assert obs_cli.main(["perf", str(f), "-o", str(o)]) == 0
        doc = json.loads(o.read_text())
        assert doc["summary"]["entries"] == 2

    def test_cli_no_entries_is_rc1(self, tmp_path, capsys):
        (tmp_path / "x.json").write_text(json.dumps({"nope": 1}))
        assert obs_cli.main(["perf", str(tmp_path)]) == 1
        assert "no ledger entries" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# auto-calibration
# ---------------------------------------------------------------------------


class TestCalibration:
    def _snap(self, predicted_s=0.001, measured_s=0.01):
        return {"entries": [{
            "fingerprint": "ab" * 32,
            "measured_step_seconds": measured_s,
            "predicted": {"predicted_step_seconds": predicted_s,
                          "device": {"peak_flops": 1e12, "hbm_bw": 1e11,
                                     "hbm_bytes": 2e9}},
            "xla": {"flops": 1e9, "bytes_accessed": 1e8},
        }], "measured": {}}

    def test_ratio_fit(self):
        prof = costs.DeviceProfile.calibrated_from(self._snap())
        # predicted 10x too fast -> constants scaled down 10x
        assert prof.peak_flops == pytest.approx(1e11)
        assert prof.hbm_bw == pytest.approx(1e10)
        assert prof.hbm_bytes == pytest.approx(2e9)

    def test_rate_fallback(self):
        snap = {"entries": [{"fingerprint": "x",
                             "measured_step_seconds": 0.01,
                             "xla": {"flops": 1e9,
                                     "bytes_accessed": 1e8}}]}
        prof = costs.DeviceProfile.calibrated_from(snap)
        assert prof.peak_flops == pytest.approx(1e11)
        assert prof.hbm_bw == pytest.approx(1e10)

    def test_no_measurement_returns_none(self):
        assert costs.DeviceProfile.calibrated_from(
            {"entries": [{"fingerprint": "x"}]}) is None
        assert costs.DeviceProfile.calibrated_from(None) is None

    def test_measured_steps_override(self):
        snap = self._snap(measured_s=None)
        snap["entries"][0]["measured_step_seconds"] = None
        prof = costs.DeviceProfile.calibrated_from(
            snap, measured_steps={"ab" * 32: 0.002})
        assert prof.peak_flops == pytest.approx(5e11)

    def test_write_and_layering(self, tmp_path, monkeypatch):
        path = str(tmp_path / "cal.json")
        costs.DeviceProfile.calibrated_from(self._snap(), path=path)
        doc = json.loads(open(path).read())
        assert doc["fit"]["method"] == "ratio"
        assert doc["peak_flops"] == pytest.approx(1e11)
        # no table match, no env: calibration alone creates the profile
        monkeypatch.setenv(costs.CALIBRATION_ENV, path)
        prof = costs.device_profile("TFRT_CPU_0")
        assert prof is not None
        assert prof.peak_flops == pytest.approx(1e11)
        assert prof.name.endswith("+cal")
        # operator env pin beats calibration
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "7e12")
        prof2 = costs.device_profile("TFRT_CPU_0")
        assert prof2.peak_flops == pytest.approx(7e12)
        assert prof2.hbm_bw == pytest.approx(1e10)  # cal still layered
        monkeypatch.delenv("PADDLE_TPU_PEAK_FLOPS")
        # calibration layers OVER a table match
        prof3 = costs.device_profile("TPU v4")
        assert prof3.peak_flops == pytest.approx(1e11)
        assert prof3.ici_bw == pytest.approx(300e9)  # table field kept

    def test_unreadable_calibration_degrades(self, tmp_path, monkeypatch):
        bad = tmp_path / "bad.json"
        bad.write_text("{torn")
        monkeypatch.setenv(costs.CALIBRATION_ENV, str(bad))
        with pytest.warns(RuntimeWarning, match="corrupt calibration"):
            assert costs.load_calibration() is None
        assert costs.device_profile("no-such-device") is None

    def test_corrupt_calibration_warns_once_and_falls_back(
            self, tmp_path, monkeypatch):
        """Seeded corruption sweep: every torn/ill-formed shape warns
        (once per mtime — never spamming a serving loop), resolves to
        None, and leaves table resolution intact."""
        bad = tmp_path / "cal.json"
        monkeypatch.setenv(costs.CALIBRATION_ENV, str(bad))
        corruptions = [
            '{"peak_flops": 1e11, "hbm',                # torn mid-write
            "\x00\x01 binary junk",
            "[1, 2, 3]",                                # not an object
            '{"peak_flops": true, "hbm_bw": "fast"}',   # bool/str schema
            '{"peak_flops": NaN, "hbm_bw": Infinity}',  # non-finite
            '{"name": "v9", "peak_flops": -1}',         # nothing usable
        ]
        for i, payload in enumerate(corruptions):
            bad.write_text(payload)
            os.utime(bad, (i + 1, i + 1))  # distinct mtime per shape
            with pytest.warns(RuntimeWarning,
                              match="corrupt calibration"):
                assert costs.load_calibration() is None
            with warnings.catch_warnings():  # same mtime: cached, quiet
                warnings.simplefilter("error")
                assert costs.load_calibration() is None
        # the table still resolves underneath the broken calibration
        prof = costs.device_profile("TPU v4")
        assert prof is not None and not prof.name.endswith("+cal")
        # a repaired file heals on the next mtime, no process restart
        bad.write_text(json.dumps({"peak_flops": 1e11, "hbm_bw": 1e10}))
        os.utime(bad, (999, 999))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            doc = costs.load_calibration()
        assert doc["peak_flops"] == pytest.approx(1e11)
        assert costs.device_profile("TPU v4").name.endswith("+cal")

    def test_prediction_carries_device_profile(self, monkeypatch):
        monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e13")
        monkeypatch.setenv("PADDLE_TPU_HBM_BW", "1e11")
        x = fluid.data("cx", shape=[8, 16], dtype="float32")
        y = fluid.layers.fc(x, 4)
        out = costs.predict_program(
            fluid.default_main_program(),
            feed_specs={"cx": np.zeros((8, 16), "float32")},
            fetch_names=[y.name], device_kind="cpu")
        assert out["device"]["peak_flops"] == pytest.approx(1e13)


# ---------------------------------------------------------------------------
# jax integration: executor / predictor registration + crash dump tail
# ---------------------------------------------------------------------------


def _sgd_net():
    x = fluid.data("px", shape=[None, 4], dtype="float32")
    y = fluid.data("py", shape=[None, 1], dtype="float32")
    p = fluid.layers.fc(x, 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(p, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    return loss


@pytest.mark.perf
class TestLedgerIntegration:
    def test_executor_compile_registers(self):
        loss = _sgd_net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.ones((4, 4), "float32")
        feed = {"px": xv, "py": xv.sum(1, keepdims=True)}
        exe.run(feed=feed, fetch_list=[loss])
        exe.run(feed=feed, fetch_list=[loss])  # cache hit: no new entry
        entries = [e for e in obs.get_ledger().entries()
                   if e["kind"] == "executor"]
        # startup program + main program compiles
        assert len(entries) == 2
        main = entries[-1]
        assert main["source"] == "compile"
        assert main["compile_seconds"] > 0
        assert main["fingerprint"] == compile_cache.program_fingerprint(
            fluid.default_main_program())
        assert any(d.startswith("fc_") for d in main["donated"])

    def test_predictor_registers_with_tag(self):
        x = fluid.data("ix", shape=[None, 4], dtype="float32")
        y = fluid.layers.fc(x, 2)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        pred = fluid.inference.Predictor(
            fluid.default_main_program(), ["ix"], [y])
        pred.run({"ix": np.ones((2, 4), "float32")})
        kinds = [e["kind"] for e in obs.get_ledger().entries()]
        assert "predict" in kinds

    def test_crash_dump_carries_ledger_tail(self, tmp_path, monkeypatch):
        monkeypatch.setenv(obs.TELEMETRY_ENV, "on")
        obs.reset()
        loss = _sgd_net()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        xv = np.ones((4, 4), "float32")
        exe.run(feed={"px": xv, "py": xv.sum(1, keepdims=True)},
                fetch_list=[loss])
        target = str(tmp_path / "crash.json")
        obs.get_recorder().crash_dump(
            path=target, exc=RuntimeError("boom"))
        doc = json.loads(open(target).read())
        assert doc["executables"], "ledger tail missing from crash dump"
        assert doc["executables"][-1]["kind"] == "executor"
        assert set(doc["compile_cache"]) == {
            "disk_hit", "disk_miss", "corrupt", "corrupt_digest",
            "corrupt_deserialize", "store", "store_error"}
