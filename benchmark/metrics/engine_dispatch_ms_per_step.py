"""DecodeEngine loop: host time of `decode.step.dispatch` (building the
step's feeds and `Predictor.run` until it returns, the enqueue) per decode
step of the window, from the engine's phase totals."""
from benchmark.metrics._program import per_step_ms


def read(run):
    return per_step_ms(run, "dispatch_seconds")
