"""Kernels of the GLM-5 prefill program against the chip's roofline, by a
WHOLE execution of the longest bucket's program (`_glm5.traced_fill`): the
least time of that batch-1 prefill (benchmark/costs_glm5.py: the larger of
the FLOPs at the bf16 peak, attention counted over the keys each token
KEEPS and the indexer's scores over the causal half, and the bytes, weights
once) at the prompt's REAL length, over the device time of the execution on
the trace's XLA-module line. None where the run kept no such trace."""
from benchmark import costs_glm5
from benchmark.metrics import _glm5


def read(run):
    fill = _glm5.traced_fill(run)
    if not fill:
        return None
    program, plen, _ = fill
    return 100.0 * program["count"] * costs_glm5.prefill_min_seconds(
        _glm5.sizes(run), plen, run.peaks) / program["seconds"]
