"""The Pallas kernel of the delta rule's chunked scan (kernel
`kda_scan_fwd` in the trace's operations: one call a delta-rule layer and
run of 4,096 positions, so 3 layers x 4 runs in the longest bucket's
prefill program) against the chip's roofline. Both sides come from the
adapter's own traced fill (`_solar.traced_fill`), whose executions are
whole and which every traced run has: 100 x the least seconds of the
program's scans over the summed device seconds of the operations with the
kernel's name.

The least seconds of ONE layer's scan over a prompt (`scan_min_seconds`)
are the larger of
  (a) the products the chunked form cannot avoid, in chunks of C = 64
      positions of a head of D channels: `5 C^2 D + 6 C D^2` FLOPs a head
      and chunk (the causal halves of kk, qk and `qk U`, the triangular
      solve over `[w | u]`, `w S`, `qe S`, `ke^T U`), times the six
      bfloat16 passes of a float32 product at `highest`, the precision the
      configuration states, at the bf16 peak, and
  (b) the operands' bytes once (q, k, v, o 2 bytes an element, the raw decay
      4) at the HBM peak,
for the prompt's real length rounded up to whole chunks. The pairs' decays
(vector work) are not counted, so the share cannot near 100. None where the
run kept no such trace or the trace holds no such kernel (the parent's XLA
form, a CPU)."""
import re

from benchmark import costs_solar
from benchmark.metrics import _solar

KERNEL = re.compile(r"^%?kda_scan_fwd(\.\d+)? = ")
CHUNK = 64
PASSES = 6             # bfloat16 passes of a float32 product at `highest`


def scan_min_seconds(m, prompt_len, peaks):
    """One delta-rule layer's scan over `prompt_len` positions."""
    heads, d = costs_solar.kda_dims(m)[:2]
    chunks = -(-int(prompt_len) // CHUNK)
    flops = PASSES * heads * chunks * (5 * CHUNK ** 2 * d + 6 * CHUNK * d * d)
    moved = chunks * CHUNK * heads * d * (4 * costs_solar.W + 4)
    return max(flops / peaks["bf16_flops_per_s"],
               moved / peaks["hbm_bytes_per_s"])


def read(run):
    fill = _solar.traced_fill(run)
    if not fill:
        return None
    program, plen = fill
    spent = sum(seconds for name, seconds
                in (run.obs["solar_fill"]["trace"].get("ops") or {}).items()
                if KERNEL.match(name))
    if not spent or not program["count"]:
        return None
    m = _solar.sizes(run)
    return 100.0 * program["count"] * costs_solar.layers(m)[1] \
        * scan_min_seconds(m, plen, run.peaks) / spent
