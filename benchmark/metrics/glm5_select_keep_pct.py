"""Indexer: the share of a live sequence's positions its queries keep
(`dsa_rows_selected`: `min(position + 1, index_topk)` a live slot and layer,
over `latent_rows_live`), counted by the step program on the device and
summed over the window's steps: 100 while every context is shorter than
`index_topk`, 2,048 / context beyond."""
from benchmark.metrics import _glm5


def read(run):
    c = run.obs.get("counters") or {}
    if not _glm5.sizes(run) or not c.get("latent_rows_live"):
        return None
    return 100.0 * c["dsa_rows_selected"] / float(c["latent_rows_live"])
