"""Slot cache: latent rows the Kimi-VL step's attention went over (`cache_len`
a slot and layer, live or not: `latent_rows_read`) over the latent rows that
held a position of a live sequence (`latent_rows_live`), both counted by the
step program on the device and summed over the window's steps. 1.0 would be a
step that reads live rows only; the XLA form reads every column of every
slot."""
from benchmark.metrics import _kimi_vl


def read(run):
    c = run.obs.get("counters") or {}
    if not _kimi_vl.sizes(run) or not c.get("latent_rows_live"):
        return None
    return c["latent_rows_read"] / float(c["latent_rows_live"])
