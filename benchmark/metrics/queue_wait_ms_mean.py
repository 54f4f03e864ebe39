"""DecodeEngine admission: the engine's mean time to first token minus its
mean `prefill_seconds`, over the window: what a request waits between
submit and the start of its own prefill (the step in flight and the
prefills admitted before it)."""
from benchmark.metrics._common import hub_mean


def read(run):
    ttft, prefill = hub_mean(run, "ttft_seconds"), hub_mean(
        run, "prefill_seconds")
    if ttft is None or prefill is None:
        return None
    return 1000.0 * (ttft - prefill)
