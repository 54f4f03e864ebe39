"""DecodeEngine admission: 90th percentile over the window's requests of
`decode.queue` + `decode.prefill` of one request id: submit to first
token inside the engine, the tail it hands to HTTP."""
from benchmark import stats
from benchmark.metrics._program import engine_ttft_by_request


def read(run):
    ttft = engine_ttft_by_request(run)
    return stats.tail_ms(ttft.values(), 90) if ttft else None
