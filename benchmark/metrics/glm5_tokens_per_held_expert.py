"""Expert layer: assignments that landed on a held expert per step, sparse
layer and held expert, from the counts the step program fetches
(`moe_assignments_held` over steps x sparse layers x experts held). With
every slot live and even routing it is slots x experts per token / experts
routed over: what each expert sees in the stated deployment."""
from benchmark import costs_glm5
from benchmark.metrics import _glm5


def read(run):
    c, m = run.obs.get("counters") or {}, _glm5.sizes(run)
    if not m or not c.get("steps") or "moe_assignments_held" not in c:
        return None
    return c["moe_assignments_held"] / float(
        c["steps"] * costs_glm5.sparse_layers(m) * m["n_routed_experts"])
