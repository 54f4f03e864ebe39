"""The Pallas grouped products of the held-experts layers in training
(kernels `gmm` and `tgmm` in the trace's operations: three forward, three
for the rows' gradient, three for the matrices' in every expert layer)
against the chip's roofline: the least time of the calls the traced steps
made (benchmark/costs_lfm2.py `grouped_products_min_seconds`, at the mean
rows the program counted on an expert layer) over the kernels' device time
in the same seconds. How many steps those seconds hold comes through the
host's mean step time (`_lfm2.traced_steps`). None where the trace has no
such kernel (a CPU run)."""
from benchmark import costs_lfm2
from benchmark.metrics import _lfm2


def read(run):
    m, steps = _lfm2.sizes(run), _lfm2.traced_steps(run)
    held = _lfm2.held_per_step(run)
    seconds = _lfm2.kernel_seconds(run, _lfm2.GROUPED)
    if not m or not steps or held is None or not seconds:
        return None
    layers = _lfm2.expert_layers(m)
    least = costs_lfm2.grouped_products_min_seconds(
        m, held / float(layers), run.peaks)
    return 100.0 * steps * layers * least / seconds
