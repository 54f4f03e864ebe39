"""Model step: mean device time of one execution of a prefill program of
the Laguna decoder (`jit_fwd_prefill_<bucket>` on the trace's XLA-module
line, the buckets pooled)."""
from benchmark.metrics import _laguna
from benchmark.metrics._program import module_ms


def read(run):
    return module_ms(run, "fwd_prefill") if _laguna.sizes(run) else None
