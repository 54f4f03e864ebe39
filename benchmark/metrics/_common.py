"""Helpers the readers share. A reader takes the run's observations and
returns a number, or None when there is nothing to read: the harness then
leaves the metric out of the line. No reader returns 0 for a share of a
roofline or of a peak that it could not measure."""


def hub_mean(run, name):
    """Mean over the window of one of the engine's histograms, from the
    difference of its count and sum across the window."""
    c = run.obs.get("counters") or {}
    n = c.get(name + ".count", 0)
    return c[name + ".sum"] / n if n else None


def step_module(tr, hint):
    """The traced program whose name holds `hint`; failing that, the one
    that took most device time."""
    mods = (tr or {}).get("modules") or {}
    named = {k: v for k, v in mods.items() if hint in k}
    pool = named or mods
    if not pool:
        return None
    if named:
        return {"count": sum(m["count"] for m in named.values()),
                "seconds": sum(m["seconds"] for m in named.values()),
                "by_plane": _merge(m["by_plane"] for m in named.values())}
    return max(pool.values(), key=lambda m: m["seconds"])


def _merge(tables):
    out = {}
    for t in tables:
        for k, v in t.items():
            out[k] = out.get(k, 0.0) + v
    return out


def device_idle_pct(run):
    """Share of the traced window in which no operation ran on the device
    (the fullest chip where there are several)."""
    tr = run.obs.get("trace") or {}
    busy = tr.get("busy_s_by_plane")
    if not busy or not max(busy.values()):
        return None
    return 100.0 * (1.0 - max(busy.values()) / tr["window_s"])


def peak_hbm_gb(run):
    peak = run.obs.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
