"""Parallel runner: time of the collective operations (all-reduce and kin)
during which no other operation ran on that chip, as a share of the traced
window, on the chip where it is largest."""


def read(run):
    tr = run.obs.get("trace") or {}
    coll = tr.get("collective_s_by_plane")
    if not coll or len(coll) < 2:
        return None
    return 100.0 * max(coll.values()) / tr["window_s"]
