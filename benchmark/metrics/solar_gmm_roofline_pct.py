"""The Pallas grouped matrix product of the gated held-experts layer (kernel
`gmm` in the trace's operations, three calls a layer) in the decode step,
against the chip's roofline: the least time of the step's calls in the
traced window (benchmark/costs_solar.py `grouped_products_min_seconds`: the
touched experts' three matrices once, the rows that land here in and out; at
the window's mean live slots, times the traced executions of
`jit_fwd_decode_step`) over the device time of the kernel's events that are
the step's: those whose result has slots x experts per token rows (the trace
names an operation by its HLO text, shape and all). A prefill program's
calls carry thousands of rows and the three traced seconds cut its
executions anywhere: they are not read here. None where the program has no
such kernel (a CPU run)."""
import re

from benchmark import costs_solar
from benchmark.metrics import _solar
from benchmark.metrics._program import named_module

KERNEL = re.compile(r"^%?gmm(\.\d+)? = \w+\[(\d+),")


def read(run):
    m, live = _solar.sizes(run), _solar.mean_live_slots(run)
    step = named_module(run, "fwd_decode_step")
    if not m or not live or not step:
        return None
    rows = run.config["serving"]["slots"] * m["num_experts_per_tok"]
    ops = (run.obs.get("trace") or {}).get("ops") or {}
    hits = ((KERNEL.match(name), seconds) for name, seconds in ops.items())
    seconds = sum(s for hit, s in hits if hit and int(hit.group(2)) == rows)
    if not seconds:
        return None
    least = step["count"] * costs_solar.grouped_products_min_seconds(
        m, live, run.peaks, _solar.mean_touched(run))
    return 100.0 * sum(costs_solar.layers(m)) * least / seconds
