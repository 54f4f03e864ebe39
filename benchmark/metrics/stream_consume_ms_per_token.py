"""Stream delivery: per token, what the consumer did with it, from the
reader's `get` returning it to the consumer asking for the next: sum of
`consume_s` over the window's `decode.stream.read` spans / their tokens. For
the HTTP handler that is `json.dumps`, one write, one flush and getting the
GIL back after the system call; `http_write_ms_per_token` is the part in
write + flush."""
from benchmark.metrics._stream import READ, ms_per


def read(run):
    return ms_per(run, READ, "consume_s", "tokens")
