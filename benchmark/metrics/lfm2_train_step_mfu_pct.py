"""Model FLOPs of the LFM2-MoE training steps of the window
(benchmark/costs_lfm2.py: forward x 3, recomputation not counted, the held
experts' part from the assignments the program COUNTED, attention at the
causal half, the head over the labelled positions) over the window's
seconds by the host's clock and the chip's bf16 peak: the share of the peak
the whole step reaches end to end, idle time and host stalls included. It
is `train_tokens_per_s` in units of the peak, at the counted rows: the
trace keeps no count of whole executions to take a device time per step
from (`_lfm2.traced_steps`). None where no trace shows the step program on
a device (a CPU run has no peak to be a share of)."""
from benchmark import costs_lfm2
from benchmark.metrics import _lfm2


def read(run):
    m, held = _lfm2.sizes(run), _lfm2.held_per_step(run)
    if (not m or held is None or not run.obs.get("window_s")
            or not _lfm2.traced_step(run)):
        return None
    flops = run.obs["steps"] * costs_lfm2.step_flops(
        m, run.traffic["seq_len"], run.obs["tokens_per_step"], held)
    return (100.0 * flops / run.obs["window_s"]
            / run.peaks["bf16_flops_per_s"])
