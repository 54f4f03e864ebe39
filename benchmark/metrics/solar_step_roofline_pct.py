"""Kernels of the Solar-Open2 decode step against the chip's roofline: the
least time of one step (benchmark/costs_solar.py: weights once, of the held
experts those the step program counted as touched; each LIVE slot's
delta-rule state and windows once in and once out; the K and V of every
live position once; at the memory bandwidth) at the window's mean live slots
and rows, over the mean device time of one execution of
`jit_fwd_decode_step`. Both the device seconds and the number of executions
come from the traced window."""
from benchmark import costs_solar
from benchmark.metrics import _solar
from benchmark.metrics._program import named_module


def read(run):
    step, m = named_module(run, "fwd_decode_step"), _solar.sizes(run)
    live = _solar.mean_live_slots(run)
    if not step or not step["seconds"] or not m or not live:
        return None
    live_rows = run.obs["live_row_seconds"] / run.obs["window_s"]
    least = costs_solar.step_min_seconds(m, live, live_rows, run.peaks,
                                         _solar.mean_touched(run))
    return 100.0 * least / (step["seconds"] / step["count"])
