"""Device time of the grouped products (`gmm` + `tgmm`) over the step
program's, both from the traced window: whether the expert layers'
mechanism does the step's work."""
from benchmark.metrics import _lfm2


def read(run):
    step = _lfm2.traced_step(run)
    seconds = _lfm2.kernel_seconds(run, _lfm2.GROUPED)
    if not step or not seconds or not _lfm2.sizes(run):
        return None
    return 100.0 * seconds / step["seconds"]
