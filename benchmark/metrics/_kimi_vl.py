"""What the readers of the Kimi-VL cell share: the sizes the cost functions
take, the window's mean load, and the window's spans of one kind."""
from benchmark import costs_kimi_vl
from benchmark.metrics import slot_occupancy_pct
from benchmark.metrics._program import window_spans


def sizes(run):
    """The configuration's published keys plus the vision group; None where
    the run holds no such configuration."""
    if (run.config.get("model") or {}).get("model_type") != "kimi_vl":
        return None
    return costs_kimi_vl.sizes(run.config)


def mean_touched(run):
    """Experts that got any token, per step and sparse layer, as the step
    program counted them over the window; None where it did not."""
    c, m = run.obs.get("counters") or {}, sizes(run)
    if not m or not c.get("steps") or "moe_experts_touched_sum" not in c:
        return None
    return c["moe_experts_touched_sum"] / float(
        c["steps"] * costs_kimi_vl.sparse_layers(m))


def mean_live_slots(run):
    occupied = slot_occupancy_pct.read(run)
    if occupied is None:
        return None
    return occupied / 100.0 * run.config["serving"]["slots"]


def mean_least_seconds(run, span, least):
    """The mean over the window's spans called `span` of `least(fields)`,
    the least seconds of the unit each dispatched; None where the program
    recorded none (the parent, a window without such units)."""
    spans = window_spans(run, span)
    if not spans:
        return None
    return sum(least(s["fields"]) for s in spans) / len(spans)
