"""Slot cache: K/V rows the step's attention went over (every column of
every slot's rows and rings, `kv_rows_read`) over the rows that held a
position of a live sequence (`kv_rows_live`: a full layer's rows up to the
slot's position, a ring's up to the window), both counted by the step
program on the device and summed over the window's steps. 1.0 is the floor:
a step that reads live rows only."""
from benchmark.metrics import _laguna


def read(run):
    c = run.obs.get("counters") or {}
    if not _laguna.sizes(run) or not c.get("kv_rows_live"):
        return None
    return c["kv_rows_read"] / float(c["kv_rows_live"])
