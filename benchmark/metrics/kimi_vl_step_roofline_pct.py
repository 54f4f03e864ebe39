"""Kernels of the Kimi-VL decode step against the chip's roofline: the least
time of one step (benchmark/costs_kimi_vl.py: weights once, of the routed
experts those the step program counted as touched; every LIVE position's
latent row once a layer; at the memory bandwidth) at the window's mean live
slots and rows, over the mean device time of one execution of
`jit_fwd_decode_step`, both from the traced window. The step reads every
column of every slot's cache whatever is live, so the share says what the
dead rows and the 64 padded values a row cost."""
from benchmark import costs_kimi_vl
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import named_module


def read(run):
    step, m = named_module(run, "fwd_decode_step"), _kimi_vl.sizes(run)
    live = _kimi_vl.mean_live_slots(run)
    if not step or not step["seconds"] or not m or not live:
        return None
    live_rows = run.obs["live_row_seconds"] / run.obs["window_s"]
    least = costs_kimi_vl.step_min_seconds(m, live, live_rows, run.peaks,
                                           _kimi_vl.mean_touched(run))
    return 100.0 * least / (step["seconds"] / step["count"])
