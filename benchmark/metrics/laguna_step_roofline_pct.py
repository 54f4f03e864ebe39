"""Kernels of the Laguna decode step against the chip's roofline: the least
time of one step (benchmark/costs_laguna.py: weights once, of the held
experts those the step program counted as touched, every LIVE K/V row once:
a full layer's rows up to each sequence's position, a window layer's ring up
to the window; at the memory bandwidth) at the window's mean live slots and
rows, over the mean device time of one execution of `jit_fwd_decode_step`.
Both the device seconds and the number of executions come from the traced
window."""
from benchmark import costs_laguna
from benchmark.metrics import _laguna
from benchmark.metrics._program import named_module


def read(run):
    step, m = named_module(run, "fwd_decode_step"), _laguna.sizes(run)
    live = _laguna.mean_live_slots(run)
    if not step or not step["seconds"] or not m or not live:
        return None
    live_rows = run.obs["live_row_seconds"] / run.obs["window_s"]
    least = costs_laguna.step_min_seconds(m, live, live_rows, run.peaks,
                                          _laguna.mean_touched(run))
    return 100.0 * least / (step["seconds"] / step["count"])
