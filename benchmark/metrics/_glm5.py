"""What the readers of the GLM-5 decoder's cell share: the sizes the cost
functions take, the window's mean load, and the traced fill of the longest
prefill program."""
from benchmark import costs_glm5
from benchmark.metrics import slot_occupancy_pct
from benchmark.metrics._common import step_module


def sizes(run):
    """The configuration's published keys plus the router's width; None
    where the run holds no such configuration."""
    if (run.config.get("model") or {}).get("model_type") != "glm_moe_dsa":
        return None
    return costs_glm5.sizes(run.config)


def mean_touched(run):
    """Held experts that got any token, per step and sparse layer, as the
    step program counted them over the window; None where it did not."""
    c, m = run.obs.get("counters") or {}, sizes(run)
    if not m or not c.get("steps") or "moe_experts_touched_sum" not in c:
        return None
    return c["moe_experts_touched_sum"] / float(
        c["steps"] * costs_glm5.sparse_layers(m))


def mean_live_slots(run):
    occupied = slot_occupancy_pct.read(run)
    if occupied is None:
        return None
    return occupied / 100.0 * run.config["serving"]["slots"]


def longest_bucket(run):
    return max(run.traffic["prompt_buckets"])


def traced_fill(run):
    """(the line of the LONGEST bucket's prefill program, {"count",
    "seconds", ...}; the prompt's real length; the operations' seconds) of
    the fill that the system's adapter ran alone under the profiler before
    the window (`glm5_decode_server.Server.trace_one_fill`, in a traced run):
    every execution on it is WHOLE. The window's own three traced seconds
    are not read for a program that runs a second: the profiler's program
    line keeps an execution that an edge of the trace cut, with what was
    left of it as its duration, and benchmark/trace.py pools a program's
    events, so four traced runs in five told no whole fill's time (my chip
    runs, PR 39). None where the run has no such configuration or kept no
    such trace (a plain run, a CPU)."""
    fill = run.obs.get("glm5_fill")
    if not sizes(run) or not fill:
        return None
    part = "fwd_prefill_%d" % longest_bucket(run)
    if not any(part in name for name in fill["trace"].get("modules") or {}):
        return None
    return (step_module(fill["trace"], part), fill["plen"],
            fill["trace"].get("ops") or {})
