"""DecodeEngine loop: share of the window's decode steps that were
dispatched while the step before's tokens were still undelivered
(`steps_ahead` / `steps`, from `stats()` differenced over the window). The
loop is pipelined by one step: it hands step n's tokens to their streams
only after step n+1 has gone out, so the stream threads run while the device
does. Near 100 with every slot live; the first step after an idle stretch or
a failed dispatch has nothing to go ahead of. A program whose loop delivers
before it dispatches has no such counter: nothing to read."""


def read(run):
    c = run.obs.get("counters") or {}
    if "steps_ahead" not in c or not c.get("steps"):
        return None
    return 100.0 * c["steps_ahead"] / c["steps"]
