"""What the readers of the hybrid decoder's cell share: the sizes the cost
functions take and the window's mean load."""
from benchmark.metrics import slot_occupancy_pct


def sizes(run):
    """The configuration's published keys plus the router's width; None
    where the run holds no such configuration."""
    model, config = run.config.get("model") or {}, run.config
    if "hybrid_override_pattern" not in model:
        return None
    return dict(model, router_experts=config["reduced_from"][
        "n_routed_experts"])


def mean_touched(run):
    """Held experts that got any token, per step and expert layer, as the
    step program counted them over the window; None where it did not.
    Routing is skewed though nothing skews it (the largest count on one
    held expert is 6 x the mean), so about 116 of 128 are read a step
    where even routing would touch 127.5: with the expectation from shapes
    alone `gmm_roofline_pct` read 101.7 (PERF.md section 6)."""
    c = run.obs.get("counters") or {}
    if not c.get("steps") or "moe_experts_touched_sum" not in c:
        return None
    layers = run.config["model"]["hybrid_override_pattern"].count("E")
    return c["moe_experts_touched_sum"] / float(c["steps"] * layers)


def mean_live_slots(run):
    occupied = slot_occupancy_pct.read(run)
    if occupied is None:
        return None
    return occupied / 100.0 * run.config["serving"]["slots"]
