"""Expert layer: stragglers among the experts of the Kimi-VL step. Per step
and sparse layer the largest count of assignments on one expert, summed
(`moe_expert_load_max_sum`), over the mean count per expert summed the same
way (`moe_assignments_held` / 64)."""
from benchmark.metrics import _kimi_vl


def read(run):
    c, m = run.obs.get("counters") or {}, _kimi_vl.sizes(run)
    if not m or not c.get("moe_assignments_held"):
        return None
    return (c["moe_expert_load_max_sum"] * m["n_routed_experts"]
            / float(c["moe_assignments_held"]))
