#!/usr/bin/env python3
"""Find a serving cell's knee and the spread of its tails for a few
chip-minutes: one set-up, then several measured windows in the same process,
one JSON line each. Not part of the contract's command.

  python3 benchmark/sweep.py --workload gpt_doc_prefill --seconds 20 \
      --rates 24,28,32,36,40 --seeds 3 [--rehearse-cpu]

For an open-loop mix each window offers one of --rates (requests/s) in place
of the traffic file's own; for a closed-loop mix --rates is ignored and the
windows differ by seed alone. With --also-first N each window's tails are
printed a second time over its first N seconds alone, so one set of windows
shows what a longer run buys. `backlog` is the engine's queue depth at the
end of the window plus requests the generator had not yet sent: a rate with
a backlog that grows is above the knee. The weights stay those of
--base-seed; the traffic's seed changes from window to window.
"""
import argparse
import json
import os
import sys
import time

T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import harness, stats  # noqa: E402


def tails_of_first(run, first_s):
    """The window's tails over its first `first_s` seconds alone, from the
    requests that finished (all of them, where the window goes on)."""
    t0 = run.obs["window_t0"]
    t1 = t0 + first_s
    ttft, gaps = [], []
    for r in run.obs["finished"]:
        tt = r["token_times"]
        if t0 <= r["due"] < t1:
            ttft.append(tt[0] - r["due"])
        gaps += [b - a for a, b in zip(tt, tt[1:]) if t0 <= b <= t1]
    return {"first_s": first_s, "requests": len(ttft),
            "ttft_ms_p90": stats.tail_ms(ttft, 90),
            "itl_ms_p90": stats.tail_ms(gaps, 90)}


def main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--base-seed", type=int, default=1000)
    ap.add_argument("--also-first", type=float, default=0)
    ap.add_argument("--check", type=int, default=0,
                    help="run the reference (and the control) over each "
                         "window's sample and print the gaps")
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)

    new_run, device, _ = harness.prepare(args.workload, args.rehearse_cpu, T0)
    base = new_run(args.base_seed, args.seconds)
    config, traffic = base.config, base.traffic
    os.makedirs(base.out_dir, exist_ok=True)

    system = harness.load_part("systems", config["system"])
    driver = harness.load_part("drivers", traffic["driver"])
    sut = system.build(base)
    driver.warm(base, sut)
    print(json.dumps({"setup_s": time.monotonic() - T0, "device": device}),
          flush=True)
    rates = [float(r) for r in args.rates.split(",") if r] or [None]
    samples = []
    for rate in rates:
        tr = harness.overlay(traffic, {})
        if rate is not None and "arrivals" in tr:
            tr["arrivals"]["rate_per_s"] = rate
        for k in range(args.seeds):
            run = new_run(args.base_seed + 1 + k, args.seconds, traffic=tr)
            e2e = driver.window(run, sut)
            g = run.obs["gauges"][-5:]
            line = {"rate": rate, "seed": run.seed,
                    "attempted": run.obs["attempted"],
                    "failed": run.obs["failed"],
                    "backlog_end": max((x["queue_depth"] or 0) for x in g)
                    if g else None,
                    "late_ms_p90": harness.load_part(
                        "metrics", "gen_late_ms_p90").read(run),
                    "slot_occupancy_pct": harness.load_part(
                        "metrics", "slot_occupancy_pct").read(run),
                    "tokens_per_s": e2e["serve_tokens_per_s"],
                    "ttft_ms_p90": e2e["ttft_ms_p90"],
                    "itl_ms_p90": e2e["itl_ms_p90"]}
            if args.also_first:
                line["first"] = tails_of_first(run, args.also_first)
            print(json.dumps(line), flush=True)
            samples.append(run)
            time.sleep(1.0)   # let the engine run dry between windows
    harness.bounded(sut.close, 30, "closing the system under test", base)
    if args.check:
        # the weights are the base seed's: judge every window's sample
        # against them, served tokens first, then the control's choices
        for run in samples:
            run.seed_traffic, run.seed = run.seed, args.base_seed
            for control in (None, config["precision"]["control"]):
                run.compared = {}
                system.check(run, sut, control=control)
                print(json.dumps({
                    "check_of_seed": run.seed_traffic, "control": control,
                    "logit_gap_sigma":
                        run.compared["logit_gap_sigma"]["value"]}),
                    flush=True)
    sys.stdout.flush()
    os._exit(0)


if __name__ == "__main__":
    main(sys.argv[1:])
