"""Arithmetic of the metrics: every rate is all the work over the whole
window, every tail is over all samples of the window. No medians of chunks:
a stall inside the window has to move each of them."""
import math
import statistics


def percentile(values, q):
    """The q-th percentile (0..100) by linear interpolation between the
    closest ranks; None for no samples."""
    vals = sorted(values)
    if not vals:
        return None
    k = (len(vals) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    if math.isinf(vals[hi]):     # a missed request has no finite latency
        return vals[hi] if hi == lo or k > lo else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (k - lo)


def tail_ms(samples_s, q, missed=0):
    """Percentile in milliseconds of samples given in seconds; each of
    `missed` (failed or refused requests) counts as a sample of infinite
    length, so it misses every limit and drags the tail."""
    vals = [1000.0 * s for s in samples_s] + [math.inf] * int(missed)
    return percentile(vals, q)


def rate(work, window_s):
    return work / window_s if window_s > 0 else None


def spread(values):
    """Distance between the first and third quartile as a share of the
    median, the contract's measure of run-to-run noise."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)
