#!/usr/bin/env python3
"""Run the benchmark's command many times back to back, each run a process
of its own with a seed of its own, as the driver's check does; stop at the
first non-zero exit and keep that run's whole stderr. It is how the spread
of each metric was measured and how a crash that shows once in ten runs is
looked for. This parent never touches jax: a chip has one owner at a time.

  python3 benchmark/soak.py --tag soak1 --cells gpt_doc_prefill,gpt_batch_decode \
      --runs 6 --sets 2 --seconds 40 --seed0 20240

Writes chiprun_out/<tag>.jsonl (one result line per run) and prints, per
cell and metric, every set's median and quartile spread.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--tag", required=True)
    ap.add_argument("--cells", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--seed0", type=int, default=2000000011)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--interleave", type=int, default=1,
                    help="1: one run of each cell in turn; 0: cell by cell")
    args = ap.parse_args(argv)
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    seconds = args.seconds or manifest["run_seconds"]
    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    out = open(os.path.join(out_dir, args.tag + ".jsonl"), "a")
    cells = args.cells.split(",")
    plan = []
    for s in range(args.sets):
        if args.interleave:
            plan += [(s, c, i) for i in range(args.runs) for c in cells]
        else:
            plan += [(s, c, i) for c in cells for i in range(args.runs)]
    results = {}
    for n, (s, cell, i) in enumerate(plan):
        # the same seeds in every set, a different seed for every run of
        # a set and for every cell
        seed = args.seed0 + 1000 * cells.index(cell) + i
        cmd = manifest["command"] + [
            "--workload", cell, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", str(args.trace)]
        t0 = time.monotonic()
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        wall = time.monotonic() - t0
        last = (p.stdout.strip().splitlines() or [""])[-1]
        try:
            line = json.loads(last)
        except ValueError:
            line = None
        tail = [l for l in p.stderr.splitlines() if l.startswith(
            ("[bench]", "compared", "benchmark:"))]
        rec = {"n": n, "set": s, "cell": cell, "seed": seed, "rc": p.returncode,
               "wall_s": wall, "line": line, "notes": tail}
        out.write(json.dumps(rec) + "\n")
        out.flush()
        print("run %d set %d %s seed %d rc %d wall %.1f s" % (
            n, s, cell, seed, p.returncode, wall), flush=True)
        if p.returncode != 0 or line is None or not line.get("correct"):
            path = os.path.join(out_dir, "%s.failed_run_%d.stderr" % (
                args.tag, n))
            with open(path, "w") as f:
                f.write(p.stderr)
            print("STOP: exit %d, correct %s; stderr kept in %s\n%s" % (
                p.returncode, line and line.get("correct"), path,
                "\n".join(tail[-12:]) or p.stderr[-3000:]), flush=True)
            return 1
        print("  " + json.dumps({k: v["value"] for k, v in
                                 line["metrics"].items()}), flush=True)
        print("  " + json.dumps(line["compared"]), flush=True)
        for k, v in line["metrics"].items():
            results.setdefault((cell, k), {}).setdefault(s, []).append(
                v["value"])
        results.setdefault((cell, "memory_peak_GB"), {}).setdefault(
            s, []).append(line["device"]["memory_peak_bytes"] / 1e9)
    print("\ncell metric set n median iqr/median min max")
    for (cell, k), sets in sorted(results.items()):
        for s, vals in sorted(sets.items()):
            sp = None
            if len(vals) >= 2:
                q = statistics.quantiles(vals, n=4)
                sp = (q[2] - q[0]) / statistics.median(vals)
            print("%s %s %d %d %.6g %s %.6g %.6g" % (
                cell, k, s, len(vals), statistics.median(vals),
                "%.4f" % sp if sp is not None else "-", min(vals),
                max(vals)))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
