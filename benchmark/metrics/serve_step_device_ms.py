"""Model step: mean device time of one execution of the decode-step
program alone (`jit_fwd_decode_step` on the trace's XLA-module line),
where `serve_dispatch_device_ms` pools it with the prefill buckets."""
from benchmark.metrics._program import module_ms


def read(run):
    return module_ms(run, "fwd_decode_step")
