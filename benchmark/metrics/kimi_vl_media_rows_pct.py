"""DecodeEngine scheduling: share of the prompt rows seated in the window that
an encoder made from an image (`media_rows` / `fill_rows`, the engine's
lifetime counters differenced over the window): the traffic is what its file
says (about 14). None where the engine counts neither (the parent)."""
from benchmark.metrics import _kimi_vl


def read(run):
    c = run.obs.get("counters") or {}
    if not _kimi_vl.sizes(run) or not c.get("fill_rows"):
        return None
    return 100.0 * c.get("media_rows", 0) / float(c["fill_rows"])
