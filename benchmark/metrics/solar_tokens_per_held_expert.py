"""Expert layer: assignments that landed on a held expert per step, layer
and held expert, from the counts the step program fetches
(`moe_assignments_held` over steps x layers x experts held). With every slot
live and even routing it is slots x experts per token / experts routed over:
an eighth of what each expert sees in the stated deployment, whose eight
chips' slots all send it rows."""
from benchmark import costs_solar
from benchmark.metrics import _solar


def read(run):
    c, m = run.obs.get("counters") or {}, _solar.sizes(run)
    if not m or not c.get("steps") or "moe_assignments_held" not in c:
        return None
    return c["moe_assignments_held"] / float(
        c["steps"] * sum(costs_solar.layers(m)) * m["n_routed_experts"])
