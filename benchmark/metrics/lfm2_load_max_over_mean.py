"""Expert layers in training: stragglers among the held experts. Per step
and expert layer the largest count of assignments on one held expert,
summed (`moe_expert_load_max_sum`), over the mean count per held expert
summed the same way (`moe_assignments_held` / experts held)."""
from benchmark.metrics import _lfm2


def read(run):
    m, c = _lfm2.sizes(run), run.obs.get("counters") or {}
    if not m or not c.get("moe_assignments_held"):
        return None
    return (c["moe_expert_load_max_sum"] * m["num_experts"]
            / float(c["moe_assignments_held"]))
