"""Mean device time of one execution of the Predictor's programs (decode
step and prefill buckets pooled: all are `jit(_fwd)` and carry one name),
from the trace's XLA-module line."""
from benchmark.metrics._common import step_module


def read(run):
    m = step_module(run.obs.get("trace"), "fwd")
    return 1000.0 * m["seconds"] / m["count"] if m and m["count"] else None
