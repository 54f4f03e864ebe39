"""Expert layer: assignments that landed on a held expert per step, expert
layer and held expert, from the counts the step program fetches
(`moe_assignments_held` over steps x expert layers x experts held). With
every slot live and even routing it is slots x experts per token / experts
routed over: what each expert sees in the stated deployment."""
from benchmark.metrics import _hybrid


def read(run):
    c, m = run.obs.get("counters") or {}, _hybrid.sizes(run)
    if not m or not c.get("steps") or "moe_assignments_held" not in c:
        return None
    layers = m["hybrid_override_pattern"].count("E")
    return c["moe_assignments_held"] / float(
        c["steps"] * layers * m["n_routed_experts"])
