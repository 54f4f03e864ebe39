"""Kernels of the decode-step program against the chip's roofline: the
least time the chip could take for the window's steps (weights once and
every live slot's written K/V rows once at the memory bandwidth,
benchmark/costs.py), per second of window, over the device time of the
`jit_fwd_decode_step` program per second of traced window. The step's half
of `serve_roofline_pct`; memory-bound."""
from benchmark import costs
from benchmark.metrics._program import named_module


def read(run):
    m, c = named_module(run, "fwd_decode_step"), run.obs.get("counters") or {}
    if not m or not m["seconds"] or not c.get("steps"):
        return None
    window_s = run.obs["window_s"]
    least = c["steps"] * costs.gpt_step_min_seconds(
        run.config["model"], run.obs["live_row_seconds"] / window_s,
        run.peaks)
    return 100.0 * (least / window_s) / (
        m["seconds"] / run.obs["trace"]["window_s"])
