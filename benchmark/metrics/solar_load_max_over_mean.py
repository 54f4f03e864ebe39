"""Expert layer: stragglers among the held experts. Per step and layer the
largest count of assignments on one held expert, summed
(`moe_expert_load_max_sum`), over the mean count per held expert summed the
same way (`moe_assignments_held` / experts held)."""
from benchmark.metrics import _solar


def read(run):
    c, m = run.obs.get("counters") or {}, _solar.sizes(run)
    if not m or not c.get("moe_assignments_held"):
        return None
    return (c["moe_expert_load_max_sum"] * m["n_routed_experts"]
            / float(c["moe_assignments_held"]))
