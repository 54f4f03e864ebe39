"""Kernels of the Solar-Open2 prefill program against the chip's roofline,
by a WHOLE execution of the longest bucket's program
(`_solar.traced_fill`): the least time of that batch-1 prefill
(benchmark/costs_solar.py: the larger of the FLOPs at the bf16 peak, softmax
attention over the causal half and the delta rule as its recurrence's three
products a position, and the bytes, weights once) at the prompt's REAL
length, over the device time of the execution on the trace's XLA-module
line. None where the run kept no such trace."""
from benchmark import costs_solar
from benchmark.metrics import _solar


def read(run):
    fill = _solar.traced_fill(run)
    if not fill:
        return None
    program, plen = fill
    return 100.0 * program["count"] * costs_solar.prefill_min_seconds(
        _solar.sizes(run), plen, run.peaks) / program["seconds"]
