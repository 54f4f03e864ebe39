"""Slot cache: delta-rule states the step updated (every slot's, live or
not: `kda_states_updated`) over the states that belong to a live sequence
(`kda_states_live`), both counted by the step program on the device and
summed over the window's steps. 1.0 with every slot live; over it is state
read and written for slots that hold no sequence."""
from benchmark.metrics import _solar


def read(run):
    c = run.obs.get("counters") or {}
    if not _solar.sizes(run) or not c.get("kda_states_live"):
        return None
    return c["kda_states_updated"] / float(c["kda_states_live"])
