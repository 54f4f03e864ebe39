"""HTTP + registry: per request, the `http.generate` span's `first_byte_s`
(first byte of the body read to the first token chunk flushed) minus the
engine's own time to first token for the same request id; median over the
window's requests. What the handler adds around the engine: parsing,
submit, the wake-up of the handler thread, the first chunk."""
import statistics

from benchmark.metrics._program import (engine_ttft_by_request,
                                        window_spans)


def read(run):
    engine = engine_ttft_by_request(run)
    over = [s["fields"]["first_byte_s"] - engine[s["fields"]["request"]]
            for s in window_spans(run, "http.generate") or ()
            if s["fields"].get("first_byte_s") is not None
            and s["fields"].get("request") in engine]
    return 1000.0 * statistics.median(over) if over else None
