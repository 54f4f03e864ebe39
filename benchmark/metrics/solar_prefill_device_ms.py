"""Model step: device time of one WHOLE execution of the Solar-Open2
decoder's longest prefill program (`jit_fwd_prefill_16384` on the XLA-module
line of the adapter's own traced fill, `_solar.traced_fill`: a prompt that
fills the bucket, alone on the device, before the window). The other
bucket's program is the same builder at half the length and is not read.
None where the run kept no such trace."""
from benchmark.metrics import _solar


def read(run):
    fill = _solar.traced_fill(run)
    if not fill:
        return None
    return 1000.0 * fill[0]["seconds"] / fill[0]["count"]
