"""Slot cache: latent rows the step's attention gathered (`index_topk` a
slot and layer, live or not: `latent_rows_read`) over the latent rows that
held a position of a live sequence (`latent_rows_live`), both counted by the
step program on the device and summed over the window's steps. Under 1.0 is
what the selection buys: a step over a long context reads a fraction of the
rows it holds."""
from benchmark.metrics import _glm5


def read(run):
    c = run.obs.get("counters") or {}
    if not _glm5.sizes(run) or not c.get("latent_rows_live"):
        return None
    return c["latent_rows_read"] / float(c["latent_rows_live"])
