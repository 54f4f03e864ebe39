"""Expert layer: assignments per step, sparse layer and expert, from the
counts the Kimi-VL step program fetches (`moe_assignments_held` over steps x
sparse layers x 64 experts, every one held). With every slot live it is slots
x 6 / 64 = 4.5: what each expert sees when a layer is whole on the chip."""
from benchmark import costs_kimi_vl
from benchmark.metrics import _kimi_vl


def read(run):
    c, m = run.obs.get("counters") or {}, _kimi_vl.sizes(run)
    if not m or not c.get("steps") or "moe_assignments_held" not in c:
        return None
    return c["moe_assignments_held"] / float(
        c["steps"] * costs_kimi_vl.sparse_layers(m) * m["n_routed_experts"])
