"""Executor dispatch: median duration of the `executor.device_compute`
spans inside the window: the call of the compiled step until it returns,
which is the enqueue and not the step (the device runs behind it). One of
the three parts of `train_dispatch_ms`."""
from benchmark.metrics._program import median_span_ms


def read(run):
    return median_span_ms(run, "executor.device_compute")
