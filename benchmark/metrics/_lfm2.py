"""What the readers of the LFM2-MoE training cell share: the sizes the cost
functions take, the window's counted assignments, and how many steps the
traced seconds hold."""
import re

from benchmark import costs_lfm2
from benchmark.metrics._common import step_module

GROUPED = re.compile(r"^%?t?gmm(\.\d+)?\b")
FLASH = re.compile(r"^%?flash_(fwd|dq|dkdv)(\.\d+)?\b")


def sizes(run):
    """The configuration's published keys plus `layer_types` and the
    router's width; None where the run holds no such configuration."""
    config = run.config
    if "layer_types" not in config or "moe_intermediate_size" not in (
            config.get("model") or {}):
        return None
    return costs_lfm2.sizes(config)


def expert_layers(m):
    return len(m["layer_types"]) - m["num_dense_layers"]


def held_per_step(run):
    """Assignments that landed on held experts in a step, summed over the
    expert layers: the mean of what the program counted over the window
    (the routers' score corrections are balanced every step, so the load
    is the same all through it); None where it did not count."""
    c = run.obs.get("counters") or {}
    if not c.get("steps") or "moe_assignments_held" not in c:
        return None
    return c["moe_assignments_held"] / float(c["steps"])


def traced_step(run):
    """The step program in the traced window: {"count", "seconds"} of its
    executions; None without a trace."""
    m = step_module(run.obs.get("trace"), "step")
    return m if m and m["count"] and m["seconds"] else None


def traced_steps(run):
    """The steps the device ran in the traced seconds, a fraction: the step
    program's device seconds there over the window's mean step time BY THE
    HOST'S CLOCK (window seconds over its steps); None without a trace or a
    window. The trace alone cannot say it: `trace.reduce` keeps a program's
    executions as a count and a sum of seconds, and the profiler cuts the
    executions at the trace's two ends, so three seconds of 0.4 s steps are
    7.4 steps in 9 events and seconds over count reads a step a fifth too
    short. The device is busy all through this cell's window (idle 0.03%),
    so the host's mean step time is the device's; a host stall in the
    window would make these steps read low. A count of WHOLE executions in
    `trace.reduce` would replace the quotient (PERF.md section 7)."""
    step, steps = traced_step(run), run.obs.get("steps")
    if not step or not steps or not run.obs.get("window_s"):
        return None
    return step["seconds"] / (run.obs["window_s"] / steps)


def kernel_seconds(run, pattern):
    ops = (run.obs.get("trace") or {}).get("ops") or {}
    return sum(v for k, v in ops.items() if pattern.match(k))
