"""DecodeEngine admission: 90th percentile of the `decode.queue` spans
(submit to the start of the request's own prefill) that started in the
window: per request, where `queue_wait_ms_mean` is a difference of two
means."""
from benchmark import stats
from benchmark.metrics._program import window_spans


def read(run):
    spans = window_spans(run, "decode.queue")
    if not spans:
        return None
    return stats.tail_ms([s["t1"] - s["t0"] for s in spans], 90)
