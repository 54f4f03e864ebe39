#!/usr/bin/env bash
# Warm-start lane: the perf smoke for the persistent AOT compile cache
# + pipelined dispatch (ISSUE 4).
#
#   bash bench_experiments/warm_start_lane.sh
#
# Lane 1 runs the `perf`-marked pytest slice (two-process warm start
# acceptance). Lane 2 is the zero-dependency smoke: the same tiny
# program compiled twice on CPU in two processes sharing one
# PADDLE_TPU_COMPILE_CACHE_DIR — the second process's compile MUST be a
# disk hit (compile_cache.disk_hit >= 1, zero compile_start events) and
# its fetches must match the first run bit-for-bit. Prints cold vs warm
# executor wall time so regressions show up as a ratio, not a vibe.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PADDLE_TPU_TELEMETRY=on

echo "== lane 1: perf-marked pytest slice =="
python -m pytest -q -p no:cacheprovider -m perf tests/

echo "== lane 2: two-process warm start on a shared cache dir =="
CACHE_DIR="$(mktemp -d /tmp/paddle_tpu_warm_lane.XXXXXX)"
trap 'rm -rf "$CACHE_DIR"' EXIT
export PADDLE_TPU_COMPILE_CACHE_DIR="$CACHE_DIR"
# the XLA tier is placed from outside too: nothing in the program sets it
export JAX_COMPILATION_CACHE_DIR="$CACHE_DIR/xla"

run_once() {
python - <<'EOF'
import json, time
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs

t0 = time.monotonic()
x = fluid.data("x", [None, 16], dtype="float32")
y = fluid.layers.fc(
    x, size=8,
    param_attr=fluid.ParamAttr(
        name="w", initializer=fluid.initializer.Constant(0.125)),
    bias_attr=fluid.ParamAttr(
        name="b", initializer=fluid.initializer.Constant(0.5)))
loss = fluid.layers.reduce_mean(y)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
feed = {"x": (np.arange(32, dtype="float32") / 31.0).reshape(2, 16)}
out = exe.run(feed=feed, fetch_list=[loss])
print(json.dumps({
    "loss": float(np.asarray(out[0])),
    "disk_hit": obs.counter("compile_cache.disk_hit"),
    "disk_miss": obs.counter("compile_cache.disk_miss"),
    "compile_start": len(obs.get_recorder().of("compile_start")),
    "wall_s": round(time.monotonic() - t0, 3),
}))
EOF
}

COLD=$(run_once | tail -n 1)
WARM=$(run_once | tail -n 1)
echo "cold: $COLD"
echo "warm: $WARM"

python - "$COLD" "$WARM" <<'EOF'
import json, sys

cold, warm = json.loads(sys.argv[1]), json.loads(sys.argv[2])
assert warm["disk_hit"] >= 1, "warm run recorded no compile-cache disk hit"
assert warm["compile_start"] == 0, \
    "warm run recompiled a cached signature"
assert warm["disk_miss"] == 0, "warm run missed the disk tier"
assert warm["loss"] == cold["loss"], \
    "warm fetch diverged: %r vs %r" % (warm["loss"], cold["loss"])
print("warm start OK: disk_hit=%d, compile_start=0, "
      "cold %.3fs -> warm %.3fs"
      % (warm["disk_hit"], cold["wall_s"], warm["wall_s"]))
EOF
