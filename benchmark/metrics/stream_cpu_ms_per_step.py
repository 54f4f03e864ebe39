"""Stream delivery: the server's reader threads' CPU for one step's tokens,
in the unit of `engine_release_ms_per_step`: (sum of `cpu_s` / sum of
`tokens` over the window's `decode.stream.read` spans) x the window's
`tokens` / `steps` from the counters. What it bounds: thread CPU includes
the system call's kernel time, which runs WITHOUT the GIL, so
`stream_cpu_ms_per_step / engine_release_ms_per_step` is an UPPER bound on
the share of `release` for which the server's own reader threads hold the
GIL; the rest of `release` is other threads (the load generator's clients,
which share the process) and hand-overs of the GIL."""
from benchmark.metrics._stream import READ, ms_per


def read(run):
    c = run.obs.get("counters") or {}
    ms = ms_per(run, READ, "cpu_s", "tokens")
    if ms is None or not c.get("steps") or not c.get("tokens"):
        return None
    return ms * c["tokens"] / c["steps"]
