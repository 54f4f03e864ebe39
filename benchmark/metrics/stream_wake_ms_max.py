"""Stream delivery: the longest a ready token waited for its waiting reader
to run: the largest `wake_max_s` of the window's `decode.stream.read`
spans. One of the stall's two witnesses: a client's long gap with a short
`engine_turn_ms_max` and a long reading here is a reader thread that was
not run; with both short it lies outside the server."""
from benchmark.metrics._stream import READ, fields_with


def read(run):
    rows = fields_with(run, READ, "wake_max_s")
    return 1000.0 * max(f["wake_max_s"] for f in rows) if rows else None
