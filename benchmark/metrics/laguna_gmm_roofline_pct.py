"""The Pallas grouped matrix product of the gated held-experts layer (kernel
`gmm` in the trace's operations, three calls a sparse layer) against the
chip's roofline: the least time of the calls the traced window made
(benchmark/costs_laguna.py `grouped_products_min_seconds`: the touched
experts' three matrices once, the rows that land here in and out; at the
window's mean live slots for the executions of `jit_fwd_decode_step`, at its
mean prompt length for those of `jit_fwd_prefill_*`) over the device time of
the kernel's events in the same window. None where the program has no such
kernel (a CPU run)."""
import re
import statistics

from benchmark import costs_laguna
from benchmark.metrics import _laguna
from benchmark.metrics._program import named_module

KERNEL = re.compile(r"^%?gmm(\.\d+)?\b")


def read(run):
    ops = (run.obs.get("trace") or {}).get("ops") or {}
    seconds = sum(v for k, v in ops.items() if KERNEL.match(k))
    m, live = _laguna.sizes(run), _laguna.mean_live_slots(run)
    step = named_module(run, "fwd_decode_step")
    if not seconds or not m or not live or not step:
        return None
    least = step["count"] * costs_laguna.grouped_products_min_seconds(
        m, live, run.peaks, _laguna.mean_touched(run))
    pre = named_module(run, "fwd_prefill")
    if pre and run.obs.get("prompt_lens"):
        least += pre["count"] * costs_laguna.grouped_products_min_seconds(
            m, statistics.fmean(run.obs["prompt_lens"]), run.peaks)
    return 100.0 * costs_laguna.sparse_layers(m) * least / seconds
