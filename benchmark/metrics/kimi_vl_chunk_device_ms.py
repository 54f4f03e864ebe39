"""Model step: mean device time of one execution of the Kimi-VL chunk program
(`jit_fwd_chunk_<rows>` on the XLA-module line of the traced window: 4,096
positions of a prompt against the latent rows so far, a unit of the engine's
turn). An execution an edge of the three traced seconds cut is kept by the
profiler with what was left of it: one or two of about twenty, so the mean
reads a few percent low. None where the trace holds no such program."""
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import module_ms


def read(run):
    return module_ms(run, "fwd_chunk_") if _kimi_vl.sizes(run) else None
