"""Compile tiers: seconds the program spent compiling in this process, the
sums of its `executor.compile_seconds` and `predictor.compile_seconds`
histograms. All of it is set-up: a compile inside the window ends the run."""


def read(run):
    from paddle_tpu import observability as obs

    total, seen = 0.0, False
    for name in ("executor.compile_seconds", "predictor.compile_seconds"):
        h = obs.histogram(name)
        if h and h.get("count"):
            total, seen = total + h["sum"], True
    return total if seen else None
