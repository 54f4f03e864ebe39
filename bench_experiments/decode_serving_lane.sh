#!/usr/bin/env bash
# Decode-serving lane: the smoke for the continuous-batching KV-cache
# decode subsystem (ISSUE 9).
#
#   bash bench_experiments/decode_serving_lane.sh
#
# Lane 1 runs the decode pytest slice (prefill/step bit-identity vs
# build_gpt_generate, slot lifecycle, deadline shed before prefill,
# HTTP chunked streaming + disconnect-cancels-slot). Lane 2 is the
# zero-dependency end-to-end smoke: a tiny GPT is trained in-process,
# a DecodeEngine comes up behind the HTTP ``:generate`` endpoint on an
# ephemeral port, 8 concurrent mixed-length clients stream tokens
# through chunked transfer-encoding, and the lane asserts aggregate
# tokens/s, p50/p99 TTFT and per-token latency, the slot-utilization
# gauge peaked, continuous batching beat the full-batch-barrier
# baseline, every stream was bit-identical to a solo generate, and a
# rebuilt engine warm-restarted with ZERO XLA compiles through the
# shared compile-cache dir. Prints the numbers so regressions show up
# as a ratio, not a vibe.
set -euo pipefail
cd "$(dirname "$0")/.."

export JAX_PLATFORMS=cpu
export PADDLE_TPU_TELEMETRY=on

echo "== lane 1: decode pytest slice =="
python -m pytest -q -p no:cacheprovider tests/test_decode_serving.py \
    tests/test_gpt.py -k "prefill or decode or generate"

echo "== lane 2: continuous batching under 8 concurrent streams =="
CACHE_DIR="$(mktemp -d /tmp/paddle_tpu_decode_lane.XXXXXX)"
trap 'rm -rf "$CACHE_DIR"' EXIT
export PADDLE_TPU_COMPILE_CACHE_DIR="$CACHE_DIR"
# the XLA tier is placed from outside too: nothing in the program sets it
export JAX_COMPILATION_CACHE_DIR="$CACHE_DIR/xla"

python - <<'EOF'
import json

import bench

out = bench._measure_decode_serving()
print(json.dumps(out, indent=1))

assert out["clients"] >= 8, out
assert out["tokens_per_sec"] > 0, out
for k in ("ttft_ms_p50", "ttft_ms_p99", "per_token_ms_p50",
          "per_token_ms_p99"):
    assert out[k] is not None and out[k] > 0, (k, out)
assert out["ttft_ms_p50"] <= out["ttft_ms_p99"], out
# continuous batching admitted into freed slots mid-flight: the gauge
# must have peaked at full utilization during the mixed-length load
assert out["slot_utilization_peak"] >= 0.75, out
# the point of the subsystem: beat the full-batch barrier schedule
assert out["continuous_vs_barrier_speedup"] > 1.0, out
assert out["bit_identical_to_solo_generate"] is True, out
# a rebuilt engine resolves every program through the disk tier
assert out["warm_restart_sources"].get("compile", 0) == 0, out
print("decode serving OK: %.0f tok/s | ttft p50 %.1fms p99 %.1fms | "
      "per-token p50 %.2fms p99 %.2fms | util peak %.2f | "
      "continuous/barrier %.2fx | warm restart %s"
      % (out["tokens_per_sec"], out["ttft_ms_p50"], out["ttft_ms_p99"],
         out["per_token_ms_p50"], out["per_token_ms_p99"],
         out["slot_utilization_peak"],
         out["continuous_vs_barrier_speedup"],
         out["warm_restart_sources"]))
EOF
