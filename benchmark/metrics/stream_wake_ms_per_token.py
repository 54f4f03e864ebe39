"""Stream delivery: per token, the time from the moment it was ready AND its
reader was waiting for it (the later of the engine's put and the reader's
`get`) to the moment the reader ran: sum of `wake_s` over the window's
`decode.stream.read` spans / their tokens. Scheduling and the GIL: a step
wakes every live stream's reader at once and they take the GIL in turn."""
from benchmark.metrics._stream import READ, ms_per


def read(run):
    return ms_per(run, READ, "wake_s", "tokens")
