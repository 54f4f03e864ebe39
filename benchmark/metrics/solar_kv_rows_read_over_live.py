"""Slot cache: K/V rows the softmax layer's step went over (every column of
every slot: `kv_rows_read`) over the rows that held a position of a live
sequence (`kv_rows_live`), both counted by the step program on the device
and summed over the window's steps. `cache_len` over the mean live length
with every slot live: what a step that read only what it holds would save."""
from benchmark.metrics import _solar


def read(run):
    c = run.obs.get("counters") or {}
    if not _solar.sizes(run) or not c.get("kv_rows_live"):
        return None
    return c["kv_rows_read"] / float(c["kv_rows_live"])
