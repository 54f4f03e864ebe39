"""DecodeEngine loop: host time of `decode.step.release` per decode step of
the window, from the engine's phase totals. The step's inputs are dropped
there, the last reference to the previous K/V buffers with them; jaxlib
frees device buffers with the GIL released, so every stream thread the emit
just woke runs before the engine thread has the GIL back and can dispatch
the next step. The device has nothing queued meanwhile."""
from benchmark.metrics._program import per_step_ms


def read(run):
    return per_step_ms(run, "release_seconds")
