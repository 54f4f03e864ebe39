"""DecodeEngine loop: the longest turn of the loop: the longest time between
the starts of two consecutive `decode.step.dispatch` spans of the window
with no `decode.loop.idle` between them. At least the cell's period; a turn
that fills a slot holds the prefill. One of the stall's two witnesses: a
client's long gap with a long reading here is the engine's (the ring then
names the phase that held it); see `stream_wake_ms_max`."""
from benchmark.metrics._program import window_spans


def read(run):
    spans = window_spans(run, ("decode.step.dispatch", "decode.loop.idle"))
    turns, last = [], None
    for s in sorted(spans or (), key=lambda s: s["t0"]):
        idle = s["name"] == "decode.loop.idle"
        if last is not None and not idle:
            turns.append(s["t0"] - last)
        last = None if idle else s["t0"]
    return 1000.0 * max(turns) if turns else None
