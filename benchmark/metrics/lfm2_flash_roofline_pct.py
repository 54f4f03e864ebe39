"""The three flash-attention kernels (`flash_fwd`, `flash_dq`,
`flash_dkdv` in the trace's operations) of the attention layers against
their causal FLOPs at the bf16 peak (benchmark/costs_lfm2.py
`flash_min_seconds`), over the traced steps (`_lfm2.traced_steps`: through
the host's mean step time). None where the trace has no such kernel (a CPU
run, a sequence shorter than the op sends to the kernels)."""
from benchmark import costs_lfm2
from benchmark.metrics import _lfm2


def read(run):
    m, steps = _lfm2.sizes(run), _lfm2.traced_steps(run)
    seconds = _lfm2.kernel_seconds(run, _lfm2.FLASH)
    if not m or not steps or not seconds:
        return None
    least = costs_lfm2.flash_min_seconds(
        m, run.traffic["seq_len"], run.obs["tokens_per_step"], run.peaks)
    layers = m["layer_types"].count("full_attention")
    return 100.0 * steps * layers * least / seconds
