"""Kernels of the serving programs against the chip's roofline: the least
time the chip could take for the window's dispatches, per second of window,
over the device time of the `_fwd` programs per second of traced window.

Per decode step: the weights once and every live slot's written K/V rows
once at the memory bandwidth; the live rows are the harness's own log of
which requests were running at which position, averaged over the window.
Per prefill: the larger of its FLOPs at the bf16 peak and the weights' bytes,
at the window's mean prompt length. Counts are the engine's `steps` and
`prefills`. The functions are benchmark/costs.py."""
import statistics

from benchmark import costs
from benchmark.metrics._common import step_module


def read(run):
    tr, c = run.obs.get("trace"), run.obs.get("counters") or {}
    m = step_module(tr, "fwd")
    if not m or not m["seconds"] or not c.get("steps"):
        return None
    model, window_s = run.config["model"], run.obs["window_s"]
    live_rows = run.obs["live_row_seconds"] / window_s
    least = c["steps"] * costs.gpt_step_min_seconds(
        model, live_rows, run.peaks)
    if c.get("prefills") and run.obs.get("prompt_lens"):
        least += c["prefills"] * costs.gpt_prefill_min_seconds(
            model, statistics.fmean(run.obs["prompt_lens"]), run.peaks)
    return 100.0 * (least / window_s) / (m["seconds"] / tr["window_s"])
