#!/usr/bin/env python3
"""Read what a training cell's limits are set from: over many seeds, the
numbers `correct` compares for sound runs of the program (its first steps
against the float32 reference) and for the control (the reference computed
in the nearest precision below the one the configuration states, put in the
program's place). One process, one compiled step; no measured window.

  python3 benchmark/limits.py --workload bert_pretrain_s128 --seeds 12 \
      [--control-seeds 4] [--leaves-out chiprun_out/leaves.jsonl]

--leaves-out keeps every leaf's norms (program, reference, control) per seed,
so that another statistic of the same readings can be tried off the chip.

A limit belongs above the sound runs' largest and below the control's
smallest; PERF.md records the readings. (For the serving cells
`sweep.py --check 1` does the same over short windows at the cell's load.)
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
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--base-seed", type=int, default=2100000000)
    ap.add_argument("--control-seeds", type=int, default=None,
                    help="read the control on the first N seeds only")
    ap.add_argument("--leaves-out", default=None)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    new_run, _, _ = harness.prepare(args.workload, args.rehearse_cpu, T0)
    seeds = [args.base_seed + 7919 * i for i in range(args.seeds)]
    runs = [new_run(s, 0) for s in seeds]
    config, traffic = runs[0].config, runs[0].traffic
    system = harness.load_part("systems", config["system"])
    driver = harness.load_part("drivers", traffic["driver"])
    sut = system.build(runs[0])
    for i, run in enumerate(runs):      # the program's readings, seed by seed
        if i:
            sut.seed_weights(run.seed, fresh_state=True)
        driver.warm(run, sut)
    harness.bounded(sut.close, 30, "closing the system under test", runs[0])
    control = config["precision"]["control"]
    n_control = len(runs) if args.control_seeds is None else args.control_seeds
    for i, run in enumerate(runs):      # then the reference and the control
        want = system.check(run, sut)
        line = {"seed": run.seed,
                "sound": {k: v["value"] for k, v in run.compared.items()}}
        leaves = {"seed": run.seed, "program": run.obs["first_steps"],
                  "reference": want}
        if i < n_control:
            run.compared = {}
            got = system.check(run, sut, precision=control)
            system.compare(run, got, want, config["check"]["limits"])
            line.update(control=control, control_reads={
                k: v["value"] for k, v in run.compared.items()})
            leaves["control"] = got
        print(json.dumps(line), flush=True)
        if args.leaves_out:
            with open(args.leaves_out, "a") as f:
                f.write(json.dumps(leaves) + "\n")
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv[1:])
