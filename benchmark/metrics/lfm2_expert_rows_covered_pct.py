"""Expert layers in training: the sorted rows the gated experts' loops went
over (`moe_rows_covered`, whole chunks over the assignments that landed on
held experts, counted by the step program on the device) as a share of the
static bound their buffers keep, steps x expert layers x tokens a step x
experts per token. A program whose passes cover the bound whole counts no
such rows and reads nothing here (it would read 100); with a quarter of the
experts held and even routing the loops cover 28-31. None where the program
does not count it."""
from benchmark.metrics import _lfm2


def read(run):
    m, c = _lfm2.sizes(run), run.obs.get("counters") or {}
    tokens = run.obs.get("tokens_per_step")
    if not m or not c.get("steps") or not tokens or (
            "moe_rows_covered" not in c):
        return None
    bound = (c["steps"] * _lfm2.expert_layers(m) * tokens
             * m["num_experts_per_tok"])
    return 100.0 * c["moe_rows_covered"] / float(bound)
