"""Kernels of the GLM-5 decode step against the chip's roofline: the least
time of one step (benchmark/costs_glm5.py: weights once, of the held experts
those the step program counted as touched; the indexer's row of every LIVE
position; `index_topk` latent rows a sequence and layer, fewer while the
sequence is shorter; at the memory bandwidth) at the window's mean live
slots and rows, over the mean device time of one execution of
`jit_fwd_decode_step`. Both the device seconds and the number of executions
come from the traced window."""
from benchmark import costs_glm5
from benchmark.metrics import _glm5
from benchmark.metrics._program import named_module


def read(run):
    step, m = named_module(run, "fwd_decode_step"), _glm5.sizes(run)
    live = _glm5.mean_live_slots(run)
    if not step or not step["seconds"] or not m or not live:
        return None
    live_rows = run.obs["live_row_seconds"] / run.obs["window_s"]
    least = costs_glm5.step_min_seconds(m, live, live_rows, run.peaks,
                                        _glm5.mean_touched(run))
    return 100.0 * least / (step["seconds"] / step["count"])
