#!/usr/bin/env bash
# Perf-observatory lane: the smoke for the executable ledger, the
# baseline regression gate, and device-profile auto-calibration
# (ISSUE 15).
#
#   bash bench_experiments/perf_lane.sh
#
# Lane 1 runs the perf-observatory pytest slice. Lane 2 banks a clean
# CPU bench run into a scratch baseline store and proves the gate
# passes on it, then re-runs the bench with a SEEDED slowdown
# (PADDLE_TPU_BENCH_SEED_SLOWDOWN drops the executor's executable LRU
# every timed step, forcing a cache-miss + recompile per step) and
# proves `bench.py --check-regressions` catches it with a non-zero
# exit. Lane 3 fits a calibration from the clean run's ledger
# (DeviceProfile.calibrated_from), re-runs the bench under
# PADDLE_TPU_CALIBRATION_FILE instead of the deliberately-wrong env
# pins, and asserts |mfu_model_err_pct| shrank on bert_tiny; then the
# perf CLI must render the drift table from the calibrated run's
# telemetry-out.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PADDLE_TPU_TELEMETRY=on

WORK_DIR="$(mktemp -d /tmp/paddle_tpu_perf_lane.XXXXXX)"
trap 'rm -rf "$WORK_DIR"' EXIT

echo "== lane 1: perf-observatory pytest slice =="
python -m pytest -q -p no:cacheprovider tests/test_perf_observatory.py

# deliberately-wrong operator pins: a "TPU-sized" peak on a CPU lane.
# The roofline prediction lands ~1000x off, which is exactly what lane
# 3's calibration must repair.
export PADDLE_TPU_PEAK_FLOPS=1e14
export PADDLE_TPU_HBM_BW=1e12

run_bench () {
    # $1: tag. Writes $WORK_DIR/result_<tag>.json + tel_<tag>.json.
    local tag="$1"
    python bench.py --telemetry-out "$WORK_DIR/tel_$tag.json" \
        > "$WORK_DIR/bench_$tag.out"
    python - "$WORK_DIR/bench_$tag.out" "$WORK_DIR/result_$tag.json" <<'EOF'
import json, sys
result = None
for line in open(sys.argv[1]):
    line = line.strip()
    if line.startswith("{"):
        result = json.loads(line)
assert result is not None, "bench printed no result JSON"
assert result["value"] > 0, "bench measured nothing: %r" % result
json.dump(result, open(sys.argv[2], "w"))
EOF
}

echo "== lane 2: baseline gate — clean pass, seeded slowdown fails =="
run_bench clean
BASELINE="$WORK_DIR/BASELINE.json"
python bench.py --update-baseline \
    --result "$WORK_DIR/result_clean.json" --baseline "$BASELINE"
python bench.py --check-regressions \
    --result "$WORK_DIR/result_clean.json" --baseline "$BASELINE"
echo "gate clean on the banked run"

PADDLE_TPU_BENCH_SEED_SLOWDOWN=cache-miss run_bench slow
if python bench.py --check-regressions \
    --result "$WORK_DIR/result_slow.json" --baseline "$BASELINE"; then
    echo "FAIL: gate did not flag the seeded cache-miss slowdown"
    exit 1
fi
echo "gate caught the seeded slowdown (non-zero exit, as required)"

echo "== lane 3: auto-calibration shrinks the MFU model error =="
python - "$WORK_DIR/tel_clean.json" "$WORK_DIR/cal.json" <<'EOF'
import json, sys
from paddle_tpu.analysis import costs
tel = json.load(open(sys.argv[1]))
prof = costs.DeviceProfile.calibrated_from(tel["ledger"],
                                           path=sys.argv[2])
assert prof is not None, "no usable measurement in the ledger"
print("calibrated: peak_flops=%.3g hbm_bw=%.3g"
      % (prof.peak_flops or 0, prof.hbm_bw or 0))
EOF
# calibration replaces the wrong pins (env would win over the file)
unset PADDLE_TPU_PEAK_FLOPS PADDLE_TPU_HBM_BW
export PADDLE_TPU_CALIBRATION_FILE="$WORK_DIR/cal.json"
run_bench cal
python - "$WORK_DIR/result_clean.json" "$WORK_DIR/result_cal.json" <<'EOF'
import json, sys
def err(path):
    doc = json.load(open(path))
    v = doc["detail"]["variants"][0]
    assert "mfu_model_err_pct" in v, \
        "no mfu_model_err_pct in variant: %r" % sorted(v)
    return abs(v["mfu_model_err_pct"])
uncal, cal = err(sys.argv[1]), err(sys.argv[2])
print("|mfu_model_err_pct|: uncalibrated %.1f -> calibrated %.1f"
      % (uncal, cal))
assert cal < uncal, (
    "calibration did not reduce the model error: %.1f -> %.1f"
    % (uncal, cal))
EOF

echo "== perf CLI drift table (calibrated run) =="
python -m paddle_tpu.observability perf "$WORK_DIR/tel_cal.json"

echo "perf lane OK"
