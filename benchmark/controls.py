#!/usr/bin/env python3
"""Read what a serving cell's limits are set from, at the cell's own size
and traffic: one set-up and one plain window, then the reference check of
what the window served (the sound reading), then the same check with each
control of the system's adapter judged in the system's place (`CONTROLS`
where the adapter names them: a lower precision or one departure planted in
the reference; else the configuration's `precision.control` alone). One
process, one JSON line a check; no result line.

  python3 benchmark/controls.py --workload solar_doc_context_decode \
      --seed 2100000043 [--seconds 40] [--controls float8,alpha_one | -]

`--controls -` runs no control: the window, its gaps and the sound check.
The first line also carries the window's gaps between tokens in bins of
milliseconds (`gaps_ms`: which gaps hold a fill of which bucket is read off
their steps), which a result line does not.

A limit belongs above the sound runs' largest and below each control's
reading of the number that holds its mechanism; the traffic file's `check`
group and PERF.md record the readings. (`sweep.py --check 1` reads
`logit_gap_sigma` alone, over short windows at several rates.)
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness  # noqa: E402


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--controls", default="")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    new_run, device, _ = harness.prepare(args.workload, args.rehearse_cpu, T0)
    run = new_run(args.seed, args.seconds
                  or harness.load_manifest()["run_seconds"])
    os.makedirs(run.out_dir, exist_ok=True)
    system = harness.load_part("systems", run.config["system"])
    driver = harness.load_part("drivers", run.traffic["driver"])
    sut = system.build(run)
    driver.warm(run, sut)
    end_to_end = driver.window(run, sut)
    harness.bounded(sut.close, 30, "closing the system under test", run)
    wanted = [c for c in args.controls.split(",") if c] or list(
        getattr(system, "CONTROLS", [run.config["precision"]["control"]]))
    if args.controls == "-":
        wanted = []
    edges = [0, 25, 50, 100, 200, 300, 400, 500, 600, 800, 1000, 2000]
    gaps = [1000.0 * g for g in run.obs.get("gaps_s", [])]
    bins = {"%d+" % lo: sum(lo <= g < hi for g in gaps)
            for lo, hi in zip(edges, edges[1:] + [float("inf")])}
    print(json.dumps({"gaps_ms": bins, "gaps": len(gaps),
                      "e2e": {k: v for k, v in end_to_end.items()}}),
          flush=True)
    for control in [None] + wanted:
        t0, run.compared = time.monotonic(), {}
        system.check(run, sut, control=control)
        print(json.dumps({
            "control": control, "seed": run.seed, "device": device["kind"],
            "check_s": round(time.monotonic() - t0, 1),
            "attempted": run.obs.get("attempted"),
            "failed": run.obs.get("failed"),
            "compared": {k: v["value"] for k, v in run.compared.items()},
            "over_its_limit": sorted(
                k for k, v in run.compared.items()
                if v["value"] is None or v["value"] > v["limit"])}),
            flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv[1:])
