"""DecodeEngine loop: share of the window the engine thread spent waiting
for the device (`sync_seconds` of its steps + `prefill_sync_seconds` of its
prefills, from `stats()` differenced over the window). The rest of the
window the thread did host work, or slept with nothing to do, while the
chip had nothing queued behind what it was running."""


def read(run):
    c = run.obs.get("counters") or {}
    if "sync_seconds" not in c or not run.obs.get("window_s"):
        return None
    return 100.0 * (c["sync_seconds"] + c.get("prefill_sync_seconds", 0.0)
                    ) / run.obs["window_s"]
