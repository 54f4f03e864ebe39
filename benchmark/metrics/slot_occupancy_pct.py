"""Share of the engine's slots in use: its `slot_utilization` gauge sampled
every 50 ms by the harness, averaged over the window."""
import statistics


def read(run):
    vals = [g["slot_utilization"] for g in run.obs.get("gauges") or []
            if g.get("slot_utilization") is not None]
    return 100.0 * statistics.fmean(vals) if vals else None
