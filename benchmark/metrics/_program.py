"""What the readers of the program's own tracing share: its span ring
(`paddle_tpu.observability.spans`, on the harness's own clock,
`time.monotonic()`) cut to the run's window, and the engine's phase totals
differenced over the window. A program without the ring, or without the
totals, gives None: the reader then has nothing to read."""
import statistics

from benchmark.metrics._common import step_module


def ring_spans(name, since, until=None):
    """The program's finished spans called `name` that started in
    [since, until), oldest first; None where the program keeps no ring."""
    from paddle_tpu import observability as obs

    spans = getattr(obs, "spans", None)
    return spans(name, since=since, until=until) if spans else None


def window_spans(run, name):
    """The spans called `name` that started inside the run's window; None
    where the program keeps no ring or the run opened no window."""
    t0, window_s = run.obs.get("window_t0"), run.obs.get("window_s")
    if t0 is None or not window_s:
        return None
    return ring_spans(name, t0, t0 + window_s)


def durations_by_request(spans):
    """{request id: seconds} of spans that carry a `request` field."""
    return {s["fields"]["request"]: s["t1"] - s["t0"] for s in spans or ()
            if s["fields"].get("request") is not None}


def engine_ttft_by_request(run):
    """Per request whose queue wait started in the window: seconds from
    its submit to the end of its own prefill (`decode.queue` +
    `decode.prefill` of one id): what the engine hands to HTTP."""
    queue = durations_by_request(window_spans(run, "decode.queue"))
    if not queue:
        return {}
    # a prefill starts where its queue wait ends, so it may lie past the
    # window's end
    prefill = durations_by_request(ring_spans(
        "decode.prefill", run.obs["window_t0"]))
    return {r: queue[r] + prefill[r] for r in queue if r in prefill}


def per_step_ms(run, key):
    """Milliseconds of the engine's phase total `key` per decode step of
    the window."""
    c = run.obs.get("counters") or {}
    if key not in c or not c.get("steps"):
        return None
    return 1000.0 * c[key] / c["steps"]


def named_module(run, part):
    """{"count", "seconds", ...} pooled over the traced programs whose name
    holds `part`; None where the trace has none (a program whose Predictor
    programs are all `jit_fwd` has none), and never `step_module`'s
    fallback to whichever program took most time."""
    tr = run.obs.get("trace") or {}
    if not any(part in name for name in tr.get("modules") or {}):
        return None
    return step_module(tr, part)


def module_ms(run, part):
    """Mean device milliseconds of one execution of those programs."""
    m = named_module(run, part)
    return 1000.0 * m["seconds"] / m["count"] if m else None


def median_span_ms(run, name):
    spans = window_spans(run, name)
    if not spans:
        return None
    return 1000.0 * statistics.median(s["t1"] - s["t0"] for s in spans)
