"""HTTP + registry: mean client time to first token minus the engine's mean
`serving.decode.ttft_seconds` (submit -> first emit) over the same window.
The difference of the means is the mean of the per-request differences; a
per-request median needs the engine's TTFT on the wire, which the program
does not send (PERF.md, open questions)."""
import statistics

from benchmark.metrics._common import hub_mean


def read(run):
    ttft, engine = run.obs.get("ttft_s"), hub_mean(run, "ttft_seconds")
    if not ttft or engine is None:
        return None
    return 1000.0 * (statistics.fmean(ttft) - engine)
