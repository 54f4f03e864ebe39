"""The Kimi-VL vision tower against the chip's roofline: the mean least time
of the tower units the window dispatched (benchmark/costs_kimi_vl.py
`tower_min_seconds` at each `serving.decode.tower` span's REAL patches:
attention at a head's own 72, so the bucket's padding and the kernel's 128
lanes read as a lower share) over the mean device time of one execution of
`jit_fwd_tower_<patches>` in the traced window."""
from benchmark import costs_kimi_vl
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import module_ms


def read(run):
    m = _kimi_vl.sizes(run)
    ms = module_ms(run, "fwd_tower_") if m else None
    if not ms:
        return None
    least = _kimi_vl.mean_least_seconds(
        run, "serving.decode.tower",
        lambda f: costs_kimi_vl.tower_min_seconds(m, f["patches"],
                                                  run.peaks))
    return None if least is None else 100.0 * 1000.0 * least / ms
