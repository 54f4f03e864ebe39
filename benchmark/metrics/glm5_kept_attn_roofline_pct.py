"""The Pallas kernel of a prompt's attention over the kept keys (kernel
`kept_keys_attn_fwd` in the trace's operations, one call a layer) against
the chip's roofline, in the longest bucket's prefill program: the least
time of one call (benchmark/costs_glm5.py `kept_attention_min_seconds`: the
FLOPs of the pairs a prompt KEEPS at the bf16 peak; the kernel computes the
causal half of its bucket and reads a lower share) at the prompt's real
length, over the device time of one call. Both come from the adapter's own
traced fill (`_glm5.traced_fill`), whose executions are whole: the trace
keeps an operation's seconds, not its count, and there the calls are the
program line's executions times the call sites. None where the run kept no
such trace or the trace holds no such kernel."""
import re

from benchmark import costs_glm5
from benchmark.metrics import _glm5

KERNEL = re.compile(r"^%?kept_keys_attn_fwd(\.\d+)? = \w+\[\d+,(\d+),")


def read(run):
    fill = _glm5.traced_fill(run)
    if not fill:
        return None
    program, plen, ops = fill
    bucket = _glm5.longest_bucket(run)
    sites = [seconds for name, seconds in ops.items()
             if seconds and KERNEL.match(name)
             and int(KERNEL.match(name).group(2)) == bucket]
    if not sites:
        return None
    return 100.0 * program["count"] * len(sites) \
        * costs_glm5.kept_attention_min_seconds(
            _glm5.sizes(run), plen, run.peaks) / sum(sites)
