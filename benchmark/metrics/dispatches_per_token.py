"""Programs the engine dispatched (decode steps + prefills) per output
token, from its own counters over the window."""


def read(run):
    c = run.obs.get("counters") or {}
    if not c.get("tokens"):
        return None
    return (c.get("steps", 0) + c.get("prefills", 0)) / c["tokens"]
