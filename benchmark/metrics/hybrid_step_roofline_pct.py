"""Kernels of the hybrid decode step against the chip's roofline: the
least time of one step (benchmark/costs_hybrid.py: weights once, of the
held experts those the step program counted as touched, every live slot's
fixed state read and written once, every live K/V row once, at the memory
bandwidth) at the window's mean live slots and rows, over the mean
device time of one execution of `jit_fwd_decode_step`. Both the device
seconds and the number of executions come from the traced window, so the
quotient is per step whatever the host's own window counted."""
from benchmark import costs_hybrid
from benchmark.metrics import _hybrid
from benchmark.metrics._program import named_module


def read(run):
    step, m = named_module(run, "fwd_decode_step"), _hybrid.sizes(run)
    live = _hybrid.mean_live_slots(run)
    if not step or not step["seconds"] or not m or not live:
        return None
    live_rows = run.obs["live_row_seconds"] / run.obs["window_s"]
    least = costs_hybrid.step_min_seconds(m, live, live_rows, run.peaks,
                                          _hybrid.mean_touched(run))
    return 100.0 * least / (step["seconds"] / step["count"])
