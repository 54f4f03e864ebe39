"""Expert layers in training: assignments that landed on a held expert per
step, expert layer and held expert, from the counts the step program
fetches (`moe_assignments_held` over steps x expert layers x experts held).
Under even routing it is tokens x experts per token / experts routed over
(2,048 here): what each expert sees in the stated deployment."""
from benchmark.metrics import _lfm2


def read(run):
    m, held = _lfm2.sizes(run), _lfm2.held_per_step(run)
    if not m or held is None:
        return None
    return held / float(_lfm2.expert_layers(m) * m["num_experts"])
