"""Executor dispatch: median duration of the `executor.fetch` spans inside
the window: writing the new state back into the scope and handing out the
fetches. One of the three parts of `train_dispatch_ms`."""
from benchmark.metrics._program import median_span_ms


def read(run):
    return median_span_ms(run, "executor.fetch")
