"""Service: the whole process's CPU (`time.process_time()`, every thread)
per decode step of the window, from `process_cpu_seconds` of the engine's
totals. Less `engine_loop_cpu_ms_per_step` and `stream_cpu_ms_per_step` it
is the load generator's client threads (they share the server's process),
the samplers and the runtime's own threads."""
from benchmark.metrics._program import per_step_ms


def read(run):
    return per_step_ms(run, "process_cpu_seconds")
