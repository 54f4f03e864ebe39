"""Self-healing performance autopilot (ISSUE 16): typed actions +
append-only decision journal, the flap-proof ActionGate (hysteresis /
cooldown / exponential quarantine), the three control-loop legs
(calibrate, SLO burn, drift re-plan with gated apply + rollback), and
the end-to-end chaos drill: a seeded decode-replica slowdown detected
from SLO burn + ledger drift, remediated with zero failed streams and
bit-exact stream continuations, the full decision trail in one merged
Perfetto trace, and a seeded-bad proposal auto-rolled-back with its
trigger quarantined."""
import json
import os
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import autopilot as ap
from paddle_tpu import observability as obs
from paddle_tpu.fluid import resilience as R
from paddle_tpu.models import gpt
from paddle_tpu.serving.disagg import TenantSpec, TenantTable, disagg_fleet


@pytest.fixture(autouse=True)
def _fresh(monkeypatch):
    monkeypatch.delenv(obs.TELEMETRY_ENV, raising=False)
    monkeypatch.delenv(ap.AUTOPILOT_ENV, raising=False)
    monkeypatch.delenv("PADDLE_TPU_CALIBRATION_FILE", raising=False)
    obs.reset()
    R.FaultInjector.uninstall()
    yield
    R.FaultInjector.uninstall()
    obs.reset()


# ---------------------------------------------------------------------------
# actions + journal
# ---------------------------------------------------------------------------


class TestAutopilotAction:
    def test_lifecycle_and_dict(self):
        a = ap.AutopilotAction("replan", "drift:abc", "apply",
                               detail={"drift_pct": 120.0})
        assert a.outcome == "proposed" and a.seq is None
        a.resolve("applied").resolve("rolled_back", reason="regressed")
        d = a.to_dict()
        assert d["outcome"] == "rolled_back"
        assert d["detail"]["reason"] == "regressed"
        assert d["detail"]["drift_pct"] == 120.0
        assert d["trigger"] == "drift:abc" and d["wall"] > 0

    def test_unknown_outcome_rejected(self):
        with pytest.raises(ValueError):
            ap.AutopilotAction("replan", "t", "apply", outcome="maybe")
        a = ap.AutopilotAction("replan", "t", "apply")
        with pytest.raises(ValueError):
            a.resolve("undone")

    def test_mode_env_parsing(self, monkeypatch):
        assert ap.autopilot_mode() == "propose"
        for v in ("off", "propose", "apply"):
            monkeypatch.setenv(ap.AUTOPILOT_ENV, v.upper() + " ")
            assert ap.autopilot_mode() == v
        monkeypatch.setenv(ap.AUTOPILOT_ENV, "yolo")
        assert ap.autopilot_mode() == "off"  # a typo parks the loop


class TestDecisionJournal:
    def test_ring_and_seq(self):
        j = ap.DecisionJournal(capacity=3)
        for i in range(5):
            j.append(ap.AutopilotAction("calibrate", "cadence", "propose"))
        assert len(j) == 3
        assert [e["seq"] for e in j.entries()] == [3, 4, 5]
        assert [e["seq"] for e in j.tail(2)] == [4, 5]

    def test_jsonl_persistence_and_torn_line(self, tmp_path):
        path = str(tmp_path / "journal.jsonl")
        j = ap.DecisionJournal(path=path)
        j.append(ap.AutopilotAction("scale_up", "slo:gold:ttft", "apply",
                                    outcome="applied"))
        j.append(ap.AutopilotAction("replan", "drift:ff", "apply",
                                    detail={"bad": object()}))
        with open(path, "a") as fh:  # crash mid-append
            fh.write('{"seq": 3, "kind": "torn')
        back = ap.DecisionJournal.read_jsonl(path)
        assert [e["seq"] for e in back] == [1, 2]
        assert back[0]["kind"] == "scale_up"
        # undumpable detail journals as an envelope, never raises
        assert back[1]["detail"] == {"unserializable": True}
        assert ap.DecisionJournal.read_jsonl(
            str(tmp_path / "missing.jsonl")) == []


# ---------------------------------------------------------------------------
# the gate: hysteresis + cooldown + quarantine
# ---------------------------------------------------------------------------


class TestActionGate:
    def _gate(self, **kw):
        self.now = [0.0]
        kw.setdefault("clock", lambda: self.now[0])
        return ap.ActionGate(**kw)

    def test_hysteresis_requires_consecutive_fires(self):
        g = self._gate(confirm_n=3)
        assert [g.confirm("t", True) for _ in range(2)] == [False, False]
        g.confirm("t", False)  # reset: sustained, not cumulative
        assert not g.confirm("t", True)
        assert not g.confirm("t", True)
        assert g.confirm("t", True)
        g.clear("t")
        assert not g.confirm("t", True)

    def test_cooldown_per_kind(self):
        g = self._gate(cooldown_s=10.0)
        assert g.ready("scale_up")
        g.stamp("scale_up")
        assert not g.ready("scale_up")
        assert g.ready("kill_replica")  # independent kinds
        self.now[0] = 10.0
        assert g.ready("scale_up")

    def test_quarantine_exponential_backoff(self):
        g = self._gate(quarantine_base_s=30.0, quarantine_max_s=100.0)
        assert g.quarantine("t") == 30.0
        assert g.quarantined("t")
        self.now[0] = 31.0
        assert not g.quarantined("t")
        # strikes persist past expiry: repeat offender doubles
        assert g.quarantine("t") == 60.0
        assert g.quarantine("t") == 100.0  # clamped at max
        st = g.state()["quarantine"]["t"]
        assert st["strikes"] == 3 and st["remaining_s"] > 0
        g.release("t")  # operator pardon forgets the strikes
        assert not g.quarantined("t")
        assert g.quarantine("t") == 30.0

    def test_verify_measurement_directions(self):
        v = ap.verify_measurement(1.0, 1.3, tolerance_pct=10.0)
        assert v["regressed"] and v["delta_pct"] == pytest.approx(30.0)
        assert not ap.verify_measurement(1.0, 1.05)["regressed"]
        assert not ap.verify_measurement(1.0, 0.5)["regressed"]
        up = ap.verify_measurement(100.0, 80.0, higher_is_better=True)
        assert up["regressed"]
        # unknown sides never regress (the gate judges only what was
        # measured) and never raise
        for b, a in ((None, 1.0), (1.0, None), (0.0, 1.0), ("x", 1.0)):
            v = ap.verify_measurement(b, a)
            assert not v["regressed"] and v["delta_pct"] is None


# ---------------------------------------------------------------------------
# the loop legs, driven synchronously against fakes
# ---------------------------------------------------------------------------

_FP = "ab" * 32


def _seed_ledger(pred_s=0.001, meas_s=0.001):
    led = obs.get_ledger()
    led.register("decode.step:t", fingerprint=_FP, source="compile")
    led.note_prediction(_FP, {
        "predicted_step_seconds": pred_s,
        "device": {"name": "fake", "peak_flops": 1e12,
                   "hbm_bytes": 2e9, "hbm_bw": 1e11}})
    led.note_measured(_FP, meas_s)
    return led


class _FakeDisagg:
    def __init__(self, lat):
        self.lat = dict(lat)
        self.killed = []
        self.failed = 0

    def decode_latencies(self):
        return dict(self.lat)

    def stats(self):
        return {"failed_streams": self.failed}

    def kill_replica(self, rid):
        self.killed.append(rid)
        self.lat.pop(rid)


class _FakeRouter:
    def __init__(self, standby=1):
        self.standby = standby
        self.reasons = []

    def scale_up(self, reason="manual"):
        self.reasons.append(reason)
        if self.standby <= 0:
            return None
        self.standby -= 1
        return type("Rep", (), {"rid": 9})()


def _burning_tenants(name="gold"):
    tenants = TenantTable([
        TenantSpec(name, per_token_slo_ms=10.0),
        TenantSpec("batch", priority=1)])
    for _ in range(8):  # every observation blows the 10ms target
        obs.observe("serving.disagg.per_token_seconds.%s" % name, 0.5)
    return tenants


class TestAutopilotLegs:
    def test_calibrate_leg_fits_profile_and_ratio(self, tmp_path):
        _seed_ledger(pred_s=0.002, meas_s=0.001)
        cal = str(tmp_path / "cal.json")
        pilot = ap.Autopilot(mode="propose", calibration_path=cal,
                             gate=ap.ActionGate(cooldown_s=0.0))
        acts = pilot.tick()
        assert [a.kind for a in acts] == ["calibrate"]
        assert acts[0].outcome == "applied" and acts[0].seq == 1
        assert pilot._cal_ratio == pytest.approx(2.0)
        # prediction over-estimated 2x -> effective constants halve...
        assert pilot.profile.peak_flops == pytest.approx(2e12)
        assert os.path.exists(cal)
        # ...and an unchanged ledger does not refit next tick
        assert pilot.tick() == []

    def test_off_mode_parks_the_loop(self, monkeypatch):
        _seed_ledger()
        monkeypatch.setenv(ap.AUTOPILOT_ENV, "off")
        pilot = ap.Autopilot()
        assert pilot.tick() == []
        assert obs.gauge("autopilot.mode") == 0

    def test_drift_leg_proposes_after_hysteresis(self):
        led = _seed_ledger(pred_s=0.001, meas_s=0.001)
        seen = []
        pilot = ap.Autopilot(
            mode="propose", drift_tolerance_pct=50.0,
            replan=lambda prof: seen.append(prof) or {"plan": "v2"},
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=2))
        assert [a.kind for a in pilot.tick()] == ["calibrate"]
        led.note_measured(_FP, 0.004)  # 300% off the calibrated pred
        assert pilot.tick() == []      # hysteresis: 1st firing tick
        acts = pilot.tick()            # 2nd consecutive -> confirmed
        assert [a.kind for a in acts] == ["replan"]
        a = acts[0]
        assert a.outcome == "proposed" and a.trigger.startswith("drift:")
        assert a.detail["proposal"] == {"plan": "v2"}
        assert a.trace_id and len(a.trace_id) == 32
        assert seen[0] is pilot.profile  # re-planned under calibration
        assert obs.gauge("autopilot.worst_drift_pct") > 250.0

    def test_drift_apply_rollback_and_quarantine(self):
        led = _seed_ledger()
        state = {"applied": 0, "rolled_back": 0}
        pilot = ap.Autopilot(
            mode="apply", drift_tolerance_pct=50.0,
            replan=lambda prof: {"plan": "bad"},
            measure=lambda: 2.0 if state["applied"] >
            state["rolled_back"] else 1.0,
            apply=lambda p: state.__setitem__(
                "applied", state["applied"] + 1),
            rollback=lambda: state.__setitem__(
                "rolled_back", state["rolled_back"] + 1),
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1,
                               quarantine_base_s=60.0))
        pilot.tick()
        led.note_measured(_FP, 0.004)
        acts = pilot.tick()
        assert [a.kind for a in acts] == ["replan", "quarantine"]
        assert acts[0].outcome == "rolled_back"
        assert acts[0].detail["verify"]["regressed"]
        assert acts[1].outcome == "quarantined"
        assert acts[1].trace_id == acts[0].trace_id  # one incident
        assert state == {"applied": 1, "rolled_back": 1}
        # the benched trigger is refused outright on the next incident
        led.note_measured(_FP, 0.0041)
        acts = pilot.tick()
        assert [a.outcome for a in acts] == ["rejected"]
        assert acts[0].detail["reason"] == "quarantined"
        assert state["applied"] == 1  # nothing re-applied

    def test_drift_apply_verified_when_measurement_holds(self):
        led = _seed_ledger()
        pilot = ap.Autopilot(
            mode="apply", drift_tolerance_pct=50.0,
            replan=lambda prof: {"plan": "good"},
            measure=lambda: 1.0, apply=lambda p: None,
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1))
        pilot.tick()
        led.note_measured(_FP, 0.004)
        acts = pilot.tick()
        assert [a.outcome for a in acts] == ["verified"]
        assert not pilot.gate.state()["quarantine"]

    def test_slo_leg_kills_degraded_decode_replica(self):
        fleet = _FakeDisagg({1: 0.1, 2: 0.1})
        pilot = ap.Autopilot(
            mode="apply", tenants=_burning_tenants(), disagg=fleet,
            degrade_factor=3.0,
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=2))
        pilot.tick()           # healthy baselines + burn streak 1
        fleet.lat[2] = 1.0     # replica 2 degrades 10x
        acts = pilot.tick()    # streak 2 -> confirmed -> kill
        kills = [a for a in acts if a.kind == "kill_replica"]
        assert fleet.killed == [2]
        assert kills and kills[0].outcome == "verified"
        assert kills[0].detail["replica"] == 2
        assert kills[0].detail["failed_streams"] == 0

    def test_never_kills_the_last_decode_replica(self):
        fleet = _FakeDisagg({1: 0.1})
        pilot = ap.Autopilot(
            mode="apply", tenants=_burning_tenants(), disagg=fleet,
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1))
        fleet.lat[1] = 5.0  # degraded, but it is all we have
        acts = pilot.tick()
        assert fleet.killed == []
        assert all(a.kind != "kill_replica" for a in acts)

    def test_slo_leg_scales_up_standby(self):
        router = _FakeRouter(standby=1)
        pilot = ap.Autopilot(
            mode="apply", tenants=_burning_tenants(), router=router,
            gate=ap.ActionGate(cooldown_s=1e9, confirm_n=1))
        acts = pilot.tick()
        ups = [a for a in acts if a.kind == "scale_up"]
        assert ups and ups[0].outcome == "applied"
        assert ups[0].detail["replica"] == 9
        assert router.reasons == ["autopilot"]
        # cooldown: the very next confirmed burn does not scale again
        acts = pilot.tick()
        assert not [a for a in acts if a.kind == "scale_up"]

    def test_slo_leg_reweights_when_nothing_else_available(self):
        tenants = _burning_tenants()
        pilot = ap.Autopilot(
            mode="apply", tenants=tenants,
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1))
        acts = pilot.tick()
        rw = [a for a in acts if a.kind == "reweight"]
        assert rw and rw[0].outcome == "applied"
        assert "batch" in rw[0].detail["demoted"]
        batch = {s.name: s for s in tenants.specs()}["batch"]
        assert batch.priority == 2  # demoted one class
        # propose mode only lists the demotions
        tenants2 = _burning_tenants()
        pilot2 = ap.Autopilot(
            mode="propose", tenants=tenants2,
            gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1))
        rw2 = [a for a in pilot2.tick() if a.kind == "reweight"]
        assert rw2 and rw2[0].outcome == "proposed"
        batch2 = {s.name: s for s in tenants2.specs()}["batch"]
        assert batch2.priority == 1  # untouched

    def test_every_action_journaled(self, tmp_path):
        _seed_ledger()
        path = str(tmp_path / "j.jsonl")
        pilot = ap.Autopilot(mode="propose",
                             journal=ap.DecisionJournal(path=path))
        pilot.tick()
        back = ap.DecisionJournal.read_jsonl(path)
        assert [e["kind"] for e in back] == ["calibrate"]
        assert back == pilot.journal.entries()

    def test_background_thread_lifecycle(self):
        pilot = ap.Autopilot(mode="propose", interval_s=0.01)
        pilot.start()
        deadline = time.monotonic() + 5.0
        while pilot._ticks == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        pilot.stop()
        assert pilot._ticks >= 1
        assert obs.counter("autopilot.ticks") >= 1


# ---------------------------------------------------------------------------
# the chaos drill (satellite: decision-trail coverage)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def m():
    """One trained tiny GPT shared by the module (see
    test_disagg_serving.py — same idiom)."""
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    cfg = gpt.gpt_tiny(vocab=97, max_len=256)
    vs = gpt.build_gpt_lm(cfg, 16)
    fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
    for _ in range(30):
        exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                fetch_list=[vs["loss"]])
    yield {"cfg": cfg, "exe": exe, "scope": fluid.global_scope(),
           "ref": {}}


def _solo(m, prompt, n_new):
    from paddle_tpu.fluid import unique_name

    key = (tuple(int(t) for t in prompt), int(n_new))
    if key in m["ref"]:
        return m["ref"][key]
    g_prog, g_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_prog, g_st), unique_name.guard():
        gen = gpt.build_gpt_generate(m["cfg"], len(prompt), n_new,
                                     mode="greedy")
    out = np.asarray(m["exe"].run(
        g_prog, feed={"gpt_prompt": np.asarray(prompt).reshape(1, -1)},
        fetch_list=[gen["ids"]], scope=m["scope"])[0])
    m["ref"][key] = [int(t) for t in out[0, len(prompt) - 1:]]
    return m["ref"][key]


def _prompt(n, seed=11):
    rng = np.random.default_rng(seed + n)
    return rng.integers(1, 97, n).astype("int64")


@pytest.mark.chaos
def test_autopilot_chaos_drill_detect_remediate_trace(
        m, tmp_path, monkeypatch):
    """The ISSUE-16 acceptance drill. A seeded decode-replica slowdown
    (the new ``dispatch:every=1:slow=S`` fault arm) is detected from
    SLO burn + calibrated ledger drift; the autopilot kills the worst
    decode replica (streams migrate, zero failed, bit-exact); a
    seeded-bad re-plan proposal regresses its verify measurement, is
    auto-rolled-back and its trigger quarantined; and the whole
    detect -> replan -> apply -> verify decision trail shares one
    trace_id in the merged Perfetto doc, with the journal matching the
    actions taken."""
    monkeypatch.setenv(obs.TRACE_DIR_ENV, str(tmp_path / "traces"))
    # deliberately-wrong nominal pins: calibration must repair them
    # before drift is judged (the drift leg stays quiet until then)
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e14")
    monkeypatch.setenv("PADDLE_TPU_HBM_BW", "1e12")
    # per-token SLO generous enough that clean CPU decode (plus the
    # occasional compile-boundary gap, on a CPU that six test workers
    # share) does not burn, while the seeded 2s stall blows it 4x on
    # every token: the healthy phase must not depend on the machine
    tenants = TenantTable(
        [TenantSpec("batch", priority=1)],
        default_spec=TenantSpec("default", per_token_slo_ms=500.0))
    router = disagg_fleet(
        m["cfg"], m["scope"], n_prefill=1, n_decode=2, slots=2,
        cache_len=64, kv_dtype="fp32", wire_dtype="fp32",
        tenants=tenants, name="autopilot-fleet")
    state = {"applied": 0, "rolled_back": 0}
    journal_path = str(tmp_path / "journal.jsonl")
    pilot = ap.Autopilot(
        tenants=tenants, disagg=router, mode="apply",
        journal=ap.DecisionJournal(path=journal_path, capacity=4096),
        gate=ap.ActionGate(cooldown_s=0.2, confirm_n=2,
                           quarantine_base_s=120.0),
        replan=lambda prof: {"plan": "seeded-bad",
                             "profile": prof.to_dict() if prof else None},
        measure=lambda: 2.0 if state["applied"] > state["rolled_back"]
        else 1.0,
        apply=lambda p: state.__setitem__("applied",
                                          state["applied"] + 1),
        rollback=lambda: state.__setitem__("rolled_back",
                                           state["rolled_back"] + 1),
        burn_threshold=1.0, slo_budget=0.2, drift_tolerance_pct=200.0,
        degrade_factor=3.0, calibrate_every_s=1e9)
    n_new = 24
    try:
        # --- phase A: clean traffic feeds the ledger + baselines ----
        clean = [(plen, router.submit(_prompt(plen), max_new=12))
                 for plen in (3, 4, 5, 6)]
        for plen, h in clean:
            assert h.result(120.0) == _solo(m, _prompt(plen), 12)
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            pilot.tick()
            if (pilot._cal_ratio and
                    len(pilot._lat_baseline) >= 2):
                break
            time.sleep(0.05)
        assert pilot._cal_ratio, "calibration never fit"
        assert len(pilot._lat_baseline) >= 2, "no healthy baselines"
        kinds = {a["kind"] for a in pilot.journal.entries()}
        assert "calibrate" in kinds
        assert "kill_replica" not in kinds  # healthy fleet untouched
        assert "replan" not in kinds
        # --- phase B: seeded slowdown via the new fault arm ---------
        # all four prompts land in the bucket-8 prefill program phase A
        # already compiled: adoption is instant, so the fault catches
        # every stream mid-flight instead of racing ahead of a compile
        prompts = [_prompt(7), _prompt(8), _prompt(7, seed=31),
                   _prompt(8, seed=31)]
        handles = [(p, router.submit(p, max_new=n_new,
                                     trace_ctx=obs.TraceContext.new()))
                   for p in prompts]
        dl = time.monotonic() + 60
        while time.monotonic() < dl:
            if all(len(h.so_far()) >= 1 for _, h in handles):
                break
            time.sleep(0.002)
        assert all(len(h.so_far()) >= 1 for _, h in handles)
        # a 2s stall per decode step: beacon latency (1/drain_rate)
        # climbs well past 3x the healthy baseline, per-token gaps blow
        # the 100ms SLO, and the step EMA drifts >>200% off the
        # calibrated prediction — all three detection legs light up
        R.FaultInjector.install("dispatch:every=1:slow=2.0")
        got = set()
        dl = time.monotonic() + 90
        while time.monotonic() < dl:
            for a in pilot.tick():
                got.add((a.kind, a.outcome))
            if ("kill_replica", "verified") in got and \
                    ("replan", "rolled_back") in got:
                break
            time.sleep(0.05)
        assert ("kill_replica", "verified") in got, got
        assert ("replan", "rolled_back") in got, got
        assert ("quarantine", "quarantined") in got, got
        # every seeded-bad apply was rolled back (one incident per
        # drifting program fingerprint — there may be more than one)
        assert state["applied"] >= 1
        assert state["applied"] == state["rolled_back"]
        # --- phase C: heal, drain, audit ----------------------------
        R.FaultInjector.uninstall()
        for p, h in handles:
            assert h.result(120.0) == _solo(m, p, n_new), len(p)
        st = router.stats()
        assert st["failed_streams"] == 0
        assert st["decode_live"] == 1 and st["replica_dead"] >= 1
        assert st["migrations"] >= 1
        # journal on disk == journal in memory == actions taken (the
        # ring keeps the newest `capacity`, the file keeps everything)
        back = ap.DecisionJournal.read_jsonl(journal_path)
        ring = pilot.journal.entries()
        assert back[-len(ring):] == ring
        by_kind = {}
        for e in back:
            by_kind.setdefault(e["kind"], []).append(e)
        assert {"calibrate", "kill_replica", "replan",
                "quarantine"} <= set(by_kind)
        rolled = [e for e in by_kind["replan"]
                  if e["outcome"] == "rolled_back"]
        assert rolled and rolled[0]["detail"]["verify"]["regressed"]
        # the drift incident's detect -> replan -> apply -> verify
        # spans share ONE trace_id, merged into one Perfetto doc
        incident_trace = rolled[0]["trace_id"]
        assert incident_trace
        assert by_kind["quarantine"][0]["trace_id"] == incident_trace
        spans = obs.read_spans(str(tmp_path / "traces"))
        names = {s["name"] for s in spans
                 if s["trace"] == incident_trace}
        assert {"autopilot.detect", "autopilot.replan",
                "autopilot.apply", "autopilot.verify"} <= names
        doc = obs.chrome_trace(spans, trace_id=incident_trace)
        assert any("autopilot" in p
                   for p in doc["otherData"]["processes"])
        # the kill incident traced detect -> act -> verify too
        kills = [e for e in back if e["kind"] == "kill_replica"
                 and e["outcome"] == "verified"]
        knames = {s["name"] for s in spans
                  if s["trace"] == kills[0]["trace_id"]}
        assert {"autopilot.detect", "autopilot.act",
                "autopilot.verify"} <= knames
        # the seeded slowdown itself fired through the injector arm
        assert json.dumps(back)  # the whole trail is JSON-clean
    finally:
        R.FaultInjector.uninstall()
        router.stop(drain=False, timeout=10.0)
