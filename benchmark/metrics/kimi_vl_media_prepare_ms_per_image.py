"""HTTP + registry: host milliseconds an image costs on the handler's thread
before the engine sees the request (base64 to pixels to patches in the
program's order): the window's `serving.decode.media_prepare` spans, their
seconds summed over their `images`. None where the program records no such
span."""
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import window_spans


def read(run):
    spans = window_spans(run, "serving.decode.media_prepare")
    if not _kimi_vl.sizes(run) or not spans:
        return None
    images = sum(s["fields"].get("images", 0) for s in spans)
    if not images:
        return None
    return 1000.0 * sum(s["t1"] - s["t0"] for s in spans) / images
