"""The Kimi-VL chunk program against the chip's roofline: the mean least time
of the chunks the window dispatched (benchmark/costs_kimi_vl.py
`chunk_min_seconds` at each `decode.prefill.chunk` span's real rows and start:
USEFUL operations only, attention at a head's own 192 / 128 widths over the
causal pairs, so what the kernel pads reads as a lower share) over the mean
device time of one execution of `jit_fwd_chunk_<rows>` in the traced window
(taken under the same load just after it)."""
from benchmark import costs_kimi_vl
from benchmark.metrics import _kimi_vl
from benchmark.metrics._program import module_ms


def read(run):
    m = _kimi_vl.sizes(run)
    ms = module_ms(run, "fwd_chunk_") if m else None
    if not ms:
        return None
    least = _kimi_vl.mean_least_seconds(
        run, "decode.prefill.chunk",
        lambda f: costs_kimi_vl.chunk_min_seconds(m, f["rows"], f["start"],
                                                  run.peaks))
    return None if least is None else 100.0 * 1000.0 * least / ms
