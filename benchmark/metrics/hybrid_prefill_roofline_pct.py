"""Kernels of the hybrid prefill programs against the chip's roofline: the
least time of one batch-1 prefill at the window's mean prompt length
(benchmark/costs_hybrid.py: the larger of its FLOPs at the bf16 peak and
its bytes, weights once) over the mean device time of one execution of
`jit_fwd_prefill_*`, seconds and executions both from the traced window."""
import statistics

from benchmark import costs_hybrid
from benchmark.metrics import _hybrid
from benchmark.metrics._program import named_module


def read(run):
    pre, m = named_module(run, "fwd_prefill"), _hybrid.sizes(run)
    if not pre or not pre["seconds"] or not m \
            or not run.obs.get("prompt_lens"):
        return None
    least = costs_hybrid.prefill_min_seconds(
        m, statistics.fmean(run.obs["prompt_lens"]), run.peaks)
    return 100.0 * least / (pre["seconds"] / pre["count"])
