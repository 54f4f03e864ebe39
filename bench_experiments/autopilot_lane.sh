#!/usr/bin/env bash
# Autopilot lane: the smoke for the self-healing performance autopilot
# (ISSUE 16) — ledger -> planner -> fleet control loop with
# chaos-proven remediation.
#
#   bash bench_experiments/autopilot_lane.sh
#
# Lane 1 runs the autopilot pytest slice (typed actions + journal,
# the flap-proof ActionGate, all three control-loop legs, and the
# end-to-end chaos drill: a seeded decode-replica slowdown via the new
# `dispatch:every=1:slow=SECONDS` fault arm, detected from SLO burn +
# ledger drift, remediated with zero failed streams). Lane 2 drives a
# headless control-loop drill and audits the DECISION TRAIL artifacts:
# the append-only journal on disk must match the loop's in-memory
# record, a seeded-bad re-plan must be auto-rolled-back and its
# trigger quarantined, and the detect -> replan -> apply -> verify
# spans must share one trace id in the merged Perfetto doc. Lane 3
# smokes the per-clause `slow=SECONDS` fault-spec arm itself.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PADDLE_TPU_TELEMETRY=on

WORK_DIR="$(mktemp -d /tmp/paddle_tpu_autopilot_lane.XXXXXX)"
trap 'rm -rf "$WORK_DIR"' EXIT

echo "== lane 1: autopilot pytest slice (units + chaos drill) =="
python -m pytest -q -p no:cacheprovider tests/test_autopilot.py

echo "== lane 2: decision-trail audit (journal + one-trace incident) =="
PADDLE_TPU_TRACE_DIR="$WORK_DIR/traces" \
python - "$WORK_DIR/journal.jsonl" "$WORK_DIR/traces" <<'EOF'
import json, sys
from paddle_tpu import autopilot as ap
from paddle_tpu import observability as obs

journal_path, trace_dir = sys.argv[1], sys.argv[2]
obs.reset()

# seed the ledger: a prediction made under a known device profile plus
# a measured step time that first agrees (the calibration fit), then
# drifts far off it (the incident)
FP = "ab" * 32
led = obs.get_ledger()
led.register("decode.step:lane", fingerprint=FP, source="compile")
led.note_prediction(FP, {
    "predicted_step_seconds": 0.002,
    "device": {"name": "lane", "peak_flops": 1e12,
               "hbm_bytes": 2e9, "hbm_bw": 1e11}})
led.note_measured(FP, 0.001)

state = {"applied": 0, "rolled_back": 0}
pilot = ap.Autopilot(
    mode="apply",
    journal=ap.DecisionJournal(path=journal_path),
    gate=ap.ActionGate(cooldown_s=0.0, confirm_n=1,
                       quarantine_base_s=300.0),
    replan=lambda prof: {"plan": "seeded-bad",
                         "profile": prof.to_dict() if prof else None},
    measure=lambda: 2.0 if state["applied"] > state["rolled_back"]
    else 1.0,
    apply=lambda p: state.__setitem__("applied", state["applied"] + 1),
    rollback=lambda: state.__setitem__("rolled_back",
                                       state["rolled_back"] + 1),
    drift_tolerance_pct=100.0, calibrate_every_s=1e9)

acts = pilot.tick()                       # calibration fit
assert [a.kind for a in acts] == ["calibrate"], acts
assert pilot._cal_ratio and pilot.profile is not None
led.note_measured(FP, 0.01)               # 10x off the calibrated pred
acts = pilot.tick()                       # detect -> replan -> apply
kinds = [(a.kind, a.outcome) for a in acts]
assert ("replan", "rolled_back") in kinds, kinds
assert ("quarantine", "quarantined") in kinds, kinds
assert state == {"applied": 1, "rolled_back": 1}, state
led.note_measured(FP, 0.011)
acts = pilot.tick()                       # benched trigger refused
assert [(a.kind, a.outcome) for a in acts] == [("replan", "rejected")]
assert state["applied"] == 1, "quarantined trigger re-applied"

# the journal on disk is the loop's own record, line for line
back = ap.DecisionJournal.read_jsonl(journal_path)
assert back == pilot.journal.entries(), "disk journal != memory"
rolled = [e for e in back if e["outcome"] == "rolled_back"]
assert rolled and rolled[0]["detail"]["verify"]["regressed"]

# the incident's decision trail shares ONE trace id, and the merged
# Perfetto doc carries the autopilot process
tid = rolled[0]["trace_id"]
assert tid, "rolled-back action carries no trace id"
spans = obs.read_spans(trace_dir)
names = {s["name"] for s in spans if s["trace"] == tid}
want = {"autopilot.detect", "autopilot.replan", "autopilot.apply",
        "autopilot.verify"}
assert want <= names, "trail incomplete: %s" % sorted(names)
doc = obs.chrome_trace(spans, trace_id=tid)
assert any("autopilot" in p for p in doc["otherData"]["processes"])
print("decision trail OK: %d journal lines, incident trace %s..."
      % (len(back), tid[:12]))
EOF

echo "== lane 3: fault-spec slow=SECONDS arm smoke =="
python - <<'EOF'
import time
from paddle_tpu.fluid import resilience as R

R.FaultInjector.install("dispatch:every=1:slow=0.05")
try:
    t0 = time.monotonic()
    R.fault_check("dispatch")
    dt = time.monotonic() - t0
    assert 0.04 <= dt < 1.0, "clause duration not honored: %.3fs" % dt
finally:
    R.FaultInjector.uninstall()
try:
    R.FaultInjector.install("dispatch:every=1:fail=0.5")
    raise AssertionError("bad spec (arg on non-slow action) accepted")
except R.FaultSpecError:
    pass
finally:
    R.FaultInjector.uninstall()
print("slow=SECONDS arm OK")
EOF

echo "autopilot lane OK"
