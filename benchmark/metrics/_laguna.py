"""What the readers of the Laguna decoder's cell share: the sizes the cost
functions take and the window's mean load."""
from benchmark import costs_laguna
from benchmark.metrics import slot_occupancy_pct


def sizes(run):
    """The configuration's published keys plus the router's width; None
    where the run holds no such configuration."""
    if (run.config.get("model") or {}).get("model_type") != "laguna":
        return None
    return costs_laguna.sizes(run.config)


def mean_touched(run):
    """Held experts that got any token, per step and sparse layer, as the
    step program counted them over the window; None where it did not."""
    c, m = run.obs.get("counters") or {}, sizes(run)
    if not m or not c.get("steps") or "moe_experts_touched_sum" not in c:
        return None
    return c["moe_experts_touched_sum"] / float(
        c["steps"] * costs_laguna.sparse_layers(m))


def mean_live_slots(run):
    occupied = slot_occupancy_pct.read(run)
    if occupied is None:
        return None
    return occupied / 100.0 * run.config["serving"]["slots"]
