"""Median host time of one Executor.run call (enqueue + feed staging),
harness clock around each call of the window."""
import statistics


def read(run):
    d = run.obs.get("dispatch_s")
    return 1000.0 * statistics.median(d) if d else None
