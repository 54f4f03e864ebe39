"""Model FLOPs of one training step (benchmark/costs.py, recomputation not
counted) over the step program's mean device time on a chip, over the
chip's bf16 peak and the number of chips: how near the step program itself
is to compute-bound. Not the end-to-end utilisation: idle time between
steps is left out."""
from benchmark import costs
from benchmark.metrics._common import step_module


def read(run):
    m = step_module(run.obs.get("trace"), "step")
    if not m or not m["count"] or not m["seconds"]:
        return None
    flops = costs.bert_train_flops_per_token(
        run.config["model"], run.traffic["seq_len"]) * run.obs[
            "tokens_per_step"]
    step_s = m["seconds"] / m["count"]          # mean over chips and steps
    return 100.0 * flops / run.chips / step_s / run.peaks["bf16_flops_per_s"]
