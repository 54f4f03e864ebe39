"""The Pallas grouped matrix product of the routed layer (kernel `gmm`, three
calls a sparse layer) in the Kimi-VL decode step, against the chip's
roofline: the least time of the step's calls in the traced window
(benchmark/costs_kimi_vl.py `grouped_products_min_seconds`: the touched
experts' three matrices once, the rows in and out; at the window's mean live
slots, times the traced executions of `jit_fwd_decode_step`) over the device
time of the kernel's events that are the step's: those whose result has slots
x experts per token rows, as the kernel holds them: padded to whole tiles of
128 (48 x 6 = 288 -> 384). The chunk program's calls carry thousands of rows
and are not read here. None where the program has no such kernel."""
import re

from benchmark import costs_kimi_vl
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import named_module

KERNEL = re.compile(r"^%?gmm(\.\d+)? = \w+\[(\d+),")
TILE_ROWS = 128     # `ops.hybrid_ops.GMM_ROWS`: a step's rows a tile


def read(run):
    m, live = _kimi_vl.sizes(run), _kimi_vl.mean_live_slots(run)
    step = named_module(run, "fwd_decode_step")
    if not m or not live or not step:
        return None
    rows = run.config["serving"]["slots"] * m["num_experts_per_tok"]
    held = {rows, -(-rows // TILE_ROWS) * TILE_ROWS}
    ops = (run.obs.get("trace") or {}).get("ops") or {}
    hits = ((KERNEL.match(name), seconds) for name, seconds in ops.items())
    seconds = sum(s for hit, s in hits if hit and int(hit.group(2)) in held)
    if not seconds:
        return None
    least = step["count"] * costs_kimi_vl.grouped_products_min_seconds(
        m, live, run.peaks, _kimi_vl.mean_touched(run))
    return 100.0 * costs_kimi_vl.sparse_layers(m) * least / seconds
