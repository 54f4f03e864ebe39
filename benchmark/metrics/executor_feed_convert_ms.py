"""Executor dispatch: median duration of the `executor.feed_convert` spans
inside the window: converting the feeds to device arrays and gathering the
program's state from the scope. One of the three parts of
`train_dispatch_ms`."""
from benchmark.metrics._program import median_span_ms


def read(run):
    return median_span_ms(run, "executor.feed_convert")
