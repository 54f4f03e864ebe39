"""What the readers of the Solar-Open2 decoder's cell share: the sizes the
cost functions take, the window's mean load, and the traced fill of the
longest prefill program."""
from benchmark import costs_solar
from benchmark.metrics._common import step_module
from benchmark.metrics._laguna import mean_live_slots  # noqa: F401


def sizes(run):
    """The configuration's published keys plus the router's width; None
    where the run holds no such configuration."""
    if (run.config.get("model") or {}).get("model_type") != "solar_open2":
        return None
    return costs_solar.sizes(run.config)


def mean_touched(run):
    """Held experts that got any token, per step and layer, as the step
    program counted them over the window; None where it did not."""
    c, m = run.obs.get("counters") or {}, sizes(run)
    if not m or not c.get("steps") or "moe_experts_touched_sum" not in c:
        return None
    return c["moe_experts_touched_sum"] / float(
        c["steps"] * sum(costs_solar.layers(m)))


def traced_fill(run):
    """(the line of the LONGEST bucket's prefill program, {"count",
    "seconds", ...}; the prompt's real length) of the fill that the
    system's adapter ran alone under the profiler before the window
    (`solar_decode_server.Server.trace_one_fill`, in a traced run): every
    execution on it is WHOLE, where the window's three traced seconds cut
    most fills they touch. None where the run has no such configuration or
    kept no such trace (a plain run, a CPU)."""
    fill = run.obs.get("solar_fill")
    if not sizes(run) or not fill:
        return None
    part = "fwd_prefill_%d" % max(run.traffic["prompt_buckets"])
    if not any(part in name for name in fill["trace"].get("modules") or {}):
        return None
    return step_module(fill["trace"], part), fill["plen"]
