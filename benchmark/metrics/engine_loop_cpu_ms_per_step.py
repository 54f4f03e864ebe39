"""DecodeEngine loop: the engine thread's CPU (`time.thread_time()`, read
once a loop turn) per decode step of the window, from `loop_cpu_seconds` of
the engine's totals: the thread RUNNING, against its seven phases' wall
time, in which it also waits for the device, the GIL and requests."""
from benchmark.metrics._program import per_step_ms


def read(run):
    return per_step_ms(run, "loop_cpu_seconds")
