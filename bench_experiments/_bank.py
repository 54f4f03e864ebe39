"""Shared banking scaffold for the chip experiment scripts.

An experiment is a list of variants, each minutes long, run in one
process under the chip tool's time limit — so each variant's result is
flushed to the script's json ATOMICALLY the moment it lands, and a run
cut short leaves whatever was measured. A variant that raises is
recorded and the rest still run; ``done()`` then exits non-zero.
Usage::

    from _bank import Bank
    bank = Bank(__file__)                  # -> <script>.json
    for tag, fn in plan:
        bank.run(tag, fn)                  # measure, record, flush
    bank.done()
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable_compile_cache():
    """Persistent XLA compile cache (reruns skip 60-80s compiles), placed
    by the repository's one rule: fluid.compile_cache.configure_xla_cache."""
    from paddle_tpu.fluid.compile_cache import configure_xla_cache

    return configure_xla_cache()


class Bank:
    def __init__(self, script_path):
        self.out = os.path.splitext(os.path.abspath(script_path))[0] \
            + ".json"
        self.results = {"variants": [], "errors": []}
        self.flush()

    def flush(self):
        tmp = self.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(self.results, f, indent=1)
        os.replace(tmp, self.out)   # a mid-write kill can't truncate

    def run(self, tag, fn):
        """Measure one variant; bank the result or the failure."""
        try:
            t0 = time.time()
            r = fn()
            for v in (r if isinstance(r, list) else [r]):
                v.setdefault("tag", tag)
                v["wall_s"] = round(time.time() - t0, 1)
                self.results["variants"].append(v)
                print("[%s]" % os.path.basename(self.out), v, flush=True)
        except Exception as e:  # noqa: BLE001 — bank it, keep going
            self.results["errors"].append("%s: %r" % (tag, e))
            print("[%s] FAIL %s %r" % (os.path.basename(self.out), tag,
                                       e), flush=True)
        self.flush()

    def done(self):
        print("DONE", flush=True)
        if self.results["errors"]:
            sys.exit("%d variant(s) failed: %s" % (
                len(self.results["errors"]), self.results["errors"]))
