"""HTTP + registry: per chunk written (one a token and the closing one), the
wall time of the handler's write + flush: sum of `write_s` / sum of `chunks`
over the window's `http.generate` spans. It holds the system call and the
wait for the GIL after it; `stream_consume_ms_per_token` less this is the
encoding and the loop."""
from benchmark.metrics._stream import ms_per


def read(run):
    return ms_per(run, "http.generate", "write_s", "chunks")
