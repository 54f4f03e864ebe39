"""Model step: mean device time of one execution of the Kimi-VL vision tower
(`jit_fwd_tower_<patches>`, pooled over the patch buckets, on the XLA-module
line of the traced window: one image through 27 blocks and the projector, a
unit of the engine's turn). None where the trace holds no such program."""
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import module_ms


def read(run):
    return module_ms(run, "fwd_tower_") if _kimi_vl.sizes(run) else None
