"""Compile tiers: how many of set-up's compiles jax's persistent cache did
not hold (its cache_misses events). After the first run in a checkout this
is 0; anything else means set-up compiles anew on every run."""


def read(run):
    c = run.obs.get("setup_compiles")
    return float(c["misses"]) if c else None
