"""DecodeEngine loop: host time of `decode.step.emit` (the per-slot loop
that hands each token to its stream, the retires it causes, the gauges)
per decode step of the window, from the engine's phase totals."""
from benchmark.metrics._program import per_step_ms


def read(run):
    return per_step_ms(run, "emit_seconds")
