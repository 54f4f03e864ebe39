#!/usr/bin/env bash
# Planner lane: the smoke for the auto-parallelism planner (ISSUE 11).
#
#   bash bench_experiments/planner_lane.sh
#
# Lane 1 runs the `planner`-marked pytest slice (enumeration, pricing,
# search, CLI, strategy ingestion, suboptimal-plan lint) including the
# slow measured-vs-predicted dryrun-zoo ordering check. Lane 2 is the
# zero-dependency CLI round-trip: `--plan --devices 8` must emit a
# ranked plan (exit 0), write byte-identical JSON across two fresh
# processes, and the winning plan must load back through
# DistributedStrategy.from_plan into a runnable fleet step. Both run
# under the one jax this installation has.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
# 8 virtual CPU devices so the from_plan fleet step and the zoo
# measurements have a real dp axis (same trick as tests/conftest.py)
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"

echo "== lane 1: planner pytest slice =="
python -c 'import jax; print("jax", jax.__version__)'
python -m pytest -q -p no:cacheprovider -m planner tests/

echo "== lane 2: CLI plan round-trip =="
WORK_DIR="$(mktemp -d /tmp/paddle_tpu_planner_lane.XXXXXX)"
trap 'rm -rf "$WORK_DIR"' EXIT

python -m paddle_tpu.analysis --plan --devices 8 --device v5e \
    --json-out "$WORK_DIR/plan_a.json" > /dev/null
python -m paddle_tpu.analysis --plan --devices 8 --device v5e \
    --json-out "$WORK_DIR/plan_b.json" > /dev/null
if ! cmp -s "$WORK_DIR/plan_a.json" "$WORK_DIR/plan_b.json"; then
    echo "FAIL: plan JSON differs across processes"
    diff "$WORK_DIR/plan_a.json" "$WORK_DIR/plan_b.json" | head
    exit 1
fi
echo "plan JSON byte-identical across two processes"

# the human table must render too
python -m paddle_tpu.analysis --plan --devices 8 --device v5e --text \
    | sed -n '1,6p'

# the emitted winner applies end-to-end: from_plan -> fleet -> one step
python - "$WORK_DIR/plan_a.json" <<'EOF'
import json
import sys

import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu.parallel import fleet as fleet_mod

doc = json.load(open(sys.argv[1]))
ranked = doc["plan"]["ranked"]
best = next(p for p in ranked if p["plan"]["fleet_runnable"])
strategy = fleet_mod.DistributedStrategy.from_plan(best)
print("applying plan:", best["plan"]["name"],
      "predicted %.4gs/step" % best["predicted_step_seconds"])

x = fluid.data("x", [None, 64], dtype="float32")
y = fluid.data("y", [None, 1], dtype="float32")
h = fluid.layers.fc(x, size=64, act="relu")
p = fluid.layers.fc(h, size=1)
loss = fluid.layers.reduce_mean(fluid.layers.square_error_cost(p, y))
fl = fleet_mod.Fleet().init()
fl.distributed_optimizer(
    fluid.optimizer.Adam(learning_rate=1e-3), strategy).minimize(loss)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
rng = np.random.default_rng(0)
feed = {"x": rng.normal(size=(16, 64)).astype(np.float32),
        "y": rng.normal(size=(16, 1)).astype(np.float32)}
out = exe.run(fl.main_program, feed=feed, fetch_list=[loss])
assert np.isfinite(float(np.asarray(out[0])))
print("fleet step under the planned strategy: loss",
      float(np.asarray(out[0])))
EOF

echo "planner lane OK"
