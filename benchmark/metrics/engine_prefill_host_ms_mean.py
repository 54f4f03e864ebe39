"""DecodeEngine admission: host time per prefill (`prefill_seconds_total`
less `prefill_sync_seconds`, per prefill of the window): building the
feeds, the enqueue, the slot write, the first emit. Every running stream
stalls for it and the device has nothing queued meanwhile."""


def read(run):
    c = run.obs.get("counters") or {}
    if "prefill_seconds_total" not in c or not c.get("prefills"):
        return None
    return 1000.0 * (c["prefill_seconds_total"]
                     - c.get("prefill_sync_seconds", 0.0)) / c["prefills"]
