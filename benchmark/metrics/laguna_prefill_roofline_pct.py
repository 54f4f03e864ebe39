"""Kernels of the Laguna prefill programs against the chip's roofline: the
least time of one batch-1 prefill (benchmark/costs_laguna.py: the larger of
its FLOPs at the bf16 peak, the window layers at the window's cost and the
full layers at the causal half, and its bytes, weights once), the mean over
the window's prompts of each one's REAL length, over the mean device time of
one execution of `jit_fwd_prefill_*`, seconds and executions both from the
traced window."""
import statistics

from benchmark import costs_laguna
from benchmark.metrics import _laguna
from benchmark.metrics._program import named_module


def read(run):
    pre, m = named_module(run, "fwd_prefill"), _laguna.sizes(run)
    if not pre or not pre["seconds"] or not m \
            or not run.obs.get("prompt_lens"):
        return None
    least = statistics.fmean(
        costs_laguna.prefill_min_seconds(m, p, run.peaks)
        for p in run.obs["prompt_lens"])
    return 100.0 * least / (pre["seconds"] / pre["count"])
