"""``python -m paddle_tpu.analysis <program|model_dir>`` — lint saved
inference models (or raw Program JSON) without touching an executor.

Exit codes: 0 clean, 1 findings (errors+warnings; tune with
``--fail-on``), 2 usage/load failure. Output is a stable JSON report
(sorted keys, deterministically ordered diagnostics, no timestamps) so
CI lanes can diff it; ``--text`` renders for humans; ``--json-out``
additionally writes the JSON atomically to a file; ``--cost`` adds the
cost-model section (per-op FLOPs/bytes, roofline step/MFU prediction,
liveness peak-HBM vs the ``--device`` capacity).
"""
import argparse
import json
import os
import sys

__all__ = ["main"]

_EPILOG = """\
exit codes (stable API — lanes gate on them):
  0   clean (or --fail-on never); with --plan: a ranked plan exists
  1   findings — errors and warnings per --fail-on (predicted-oom is
      an error: the program's peak live-set exceeds the device HBM);
      with --plan: every candidate was rejected (nothing fits)
  2   usage error / target failed to load / malformed --mesh

lint gating:
  --fail-on picks the severity floor for exit 1: 'findings' (default:
  errors+warnings), 'perf' (errors+warnings+perf hints — the strict
  lane gate, e.g. `python -m paddle_tpu.analysis --fail-on perf DIR`),
  'error', 'never'. Recorded concurrency violations (--concurrency)
  count under every --fail-on except 'never'.

concurrency:
  --concurrency appends the in-process concurrency sanitizer report:
  the named-lock order graph, lock-order cycles (= potential
  deadlocks, with both acquisition stacks), blocking-under-lock /
  thread-leak / cross-program-donated-alias violations, and live
  framework threads. Arm recording with PADDLE_TPU_LOCK_SANITIZER=on
  (or analysis.concurrency.arm() in-process). TARGET is optional when
  --concurrency is given.

plan mode:
  --plan --devices N searches mesh factorizations of N (dp/tp/pp) x
  DistributedStrategy settings (gspmd vs explicit comms, int8
  quantized allreduce, bucketed overlap, ZeRO-1, AMP), prices each
  against the --device profile (compute roofline + pipeline bubble +
  ICI/DCN comm legs), drops predicted-OOM candidates with
  op-attributed diagnostics, and ranks the rest by predicted step
  seconds. TARGET may be omitted: a BERT-tiny pretrain program is
  built in-process. --json-out writes a plan document that
  DistributedStrategy.from_plan applies directly; with --mesh the
  given composition is also priced against the winner
  (suboptimal-parallel-plan finding at >=1.25x).
"""


def _bench_bert_program(batch=8, seq=64):
    """The default --plan target: a BERT-tiny pretrain step, built
    in-process so ``--plan --devices N`` needs no saved model."""
    from .. import fluid
    from ..fluid import framework
    from ..models import bert

    prog = framework.Program()
    startup = framework.Program()
    with framework.program_guard(prog, startup):
        cfg = bert.bert_tiny(seq=seq)
        vs = bert.build_bert_pretrain(cfg, seq)
        fluid.optimizer.Adam(learning_rate=1e-4).minimize(vs["loss"])
    return prog, ["input_ids", "mlm_labels"], [vs["loss"].name]


def _load_target(path):
    """Resolve a CLI target to (program, feed_names, fetch_names,
    state_specs)."""
    import numpy as np

    from ..fluid.framework import Program

    model_file = path
    params_file = None
    if os.path.isdir(path):
        model_file = os.path.join(path, "__model__")
        if not os.path.exists(model_file):
            raise IOError(
                "%s is a directory without a __model__ file — expected a "
                "save_inference_model dir" % path)
        cand = os.path.join(path, "__params__.npz")
        params_file = cand if os.path.exists(cand) else None
    with open(model_file) as f:
        doc = json.load(f)
    if isinstance(doc, dict) and "program" in doc:
        # save_inference_model meta: {program, feed_names, fetch_names}
        program = Program.from_json(json.dumps(doc["program"]))
        feed_names = list(doc.get("feed_names") or [])
        fetch_names = list(doc.get("fetch_names") or [])
    else:
        # raw Program.to_json dump
        program = Program.from_json(json.dumps(doc))
        feed_names, fetch_names = [], []
    state_specs = None
    if params_file is not None:
        data = np.load(params_file, allow_pickle=False)
        state_specs = {n: data[n] for n in data.files}
    return program, feed_names, fetch_names, state_specs


def _parse_mesh(spec):
    """``"dp=8,tp=2"`` -> {"dp": 8, "tp": 2}. Any axis name is legal
    (dp/data/batch/sp/seq shard activations; tp/mp/pp/ep shard params —
    see memory.shard_divisors). Raises ValueError with an actionable
    message on malformed entries; the CLI maps that to exit 2."""
    mesh = {}
    for part in (spec or "").split(","):
        part = part.strip()
        if not part:
            continue
        axis, _, size = part.partition("=")
        axis = axis.strip()
        if not axis or not size:
            raise ValueError(
                "bad --mesh entry %r (want axis=size, e.g. "
                "'dp=8,tp=2,pp=2')" % part)
        try:
            n = int(size)
        except ValueError:
            raise ValueError(
                "bad --mesh entry %r: size %r is not an integer"
                % (part, size.strip()))
        if n < 1:
            raise ValueError(
                "bad --mesh entry %r: axis size must be >= 1" % part)
        if axis in mesh:
            raise ValueError(
                "bad --mesh: axis %r given twice" % axis)
        mesh[axis] = n
    return mesh


def _atomic_write(path, text):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def _run_plan(args, mesh):
    """--plan mode: search mesh x strategy x comms and emit the ranked
    plan document. Exit 0 when a plan exists, 1 when every candidate
    was rejected, 2 on usage/load errors."""
    if not args.devices or args.devices < 1:
        print("error: --plan requires --devices N (a positive device "
              "count to lay the mesh over)", file=sys.stderr)
        return 2
    is_test = False
    state_specs = None
    if args.target is not None:
        try:
            program, feed_names, fetch_names, state_specs = _load_target(
                args.target)
        except Exception as e:  # noqa: BLE001 — CLI boundary
            print("error: cannot load %s: %s: %s"
                  % (args.target, type(e).__name__, e), file=sys.stderr)
            return 2
        is_test = True  # saved models are inference programs
        target_desc = args.target
    else:
        program, feed_names, fetch_names = _bench_bert_program(
            batch=args.batch)
        target_desc = "bench-bert-tiny (built in-process)"

    from ..planner import plan_search
    from .costs import device_profile

    # a search needs SOME roofline to rank against; with no --device
    # the v5e table row fills whatever the PADDLE_TPU_* env overrides
    # (applied on top, as always) leave unset
    device_defaulted = "v5e" if args.device is None else None
    profile = device_profile(args.device or "v5e")

    amp_choices = {"auto": (False, True), "on": (True,),
                   "off": (False,)}[args.amp]
    result = plan_search(
        program, args.devices, profile=profile,
        feed_names=feed_names, fetch_names=fetch_names,
        state_specs=state_specs,
        state_names=(set(state_specs) if state_specs is not None
                     else None),
        is_test=is_test, default_dim=args.batch,
        microbatches=args.microbatches, amp_choices=amp_choices)
    doc = {
        "target": target_desc,
        "devices": args.devices,
        "plan": result.to_dict(top=args.top),
    }
    if device_defaulted:
        doc["device_defaulted"] = device_defaulted
    if mesh:
        from .tpu_lint import lint_parallel_plan

        rep = lint_parallel_plan(
            program, mesh, n_devices=args.devices,
            microbatches=args.microbatches, level="full",
            search_result=result)
        doc["mesh_check"] = rep.to_dict()
    rendered = json.dumps(doc, sort_keys=True, indent=2)
    if args.text:
        print("target: %s" % target_desc)
        print(result.render_text(top=args.top))
        if mesh and doc.get("mesh_check", {}).get("diagnostics"):
            for d in doc["mesh_check"]["diagnostics"]:
                print("%s [%s] %s"
                      % (d["severity"], d["check"], d["message"]))
    else:
        print(rendered)
    if args.json_out:
        try:
            _atomic_write(args.json_out, rendered + "\n")
        except OSError as e:
            print("error: cannot write %s: %s" % (args.json_out, e),
                  file=sys.stderr)
            return 2
    return 0 if result.ranked else 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.analysis",
        description="Statically verify + shape-check + TPU-lint a saved "
                    "inference model or Program JSON.",
        epilog=_EPILOG,
        formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("target", nargs="?", default=None,
                    help="save_inference_model dir, __model__ meta file, "
                         "or Program.to_json dump; optional with --plan "
                         "(defaults to the bench BERT pretrain program)")
    ap.add_argument("--platform", choices=("tpu", "cpu"), default="tpu",
                    help="lint target platform (default: tpu — the "
                         "deployment target)")
    ap.add_argument("--level", choices=("verify", "full"), default="full")
    ap.add_argument("--batch", type=int, default=8,
                    help="placeholder for -1 feed dims (default: 8)")
    ap.add_argument("--cost", action="store_true",
                    help="add the cost-model section: per-op FLOPs/bytes, "
                         "roofline-predicted step seconds and MFU, and "
                         "the liveness peak-HBM estimate vs --device "
                         "capacity (forces --level full); with --mesh "
                         "dp=N also the predicted gradient-allreduce "
                         "seconds (ICI bandwidth from --device or "
                         "PADDLE_TPU_ICI_BW) and dp scaling efficiency")
    ap.add_argument("--device", default=None, metavar="KIND",
                    help="device kind for the roofline/capacity model "
                         "(e.g. v5e, v5p, v4); default: only the "
                         "PADDLE_TPU_PEAK_FLOPS / PADDLE_TPU_HBM_BYTES / "
                         "PADDLE_TPU_HBM_BW env overrides apply")
    ap.add_argument("--mesh", default=None, metavar="AXES",
                    help="mesh axes dividing footprints, e.g. "
                         "'dp=8,tp=2' or 'dp=2,pp=2,ep=2' — "
                         "dp/data/batch/sp axes divide activations, "
                         "every other axis (tp/mp/pp/ep) divides "
                         "params; with --plan, this composition is "
                         "priced against the search winner")
    ap.add_argument("--plan", action="store_true",
                    help="auto-parallelism planner: search mesh x "
                         "strategy x comms for --devices chips and "
                         "emit the ranked plan table (see epilog)")
    ap.add_argument("--devices", type=int, default=None, metavar="N",
                    help="device count the plan search targets "
                         "(required with --plan)")
    ap.add_argument("--microbatches", type=int, default=8, metavar="M",
                    help="pipeline microbatches pp plans amortize "
                         "their (pp-1)/M bubble over (default: 8)")
    ap.add_argument("--top", type=int, default=8, metavar="K",
                    help="ranked plans to include in the report "
                         "(default: 8)")
    ap.add_argument("--amp", choices=("auto", "on", "off"),
                    default="auto",
                    help="AMP leg of the plan search: auto tries both "
                         "(default); on/off pins it")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also write the JSON report to PATH atomically "
                         "(tmp + rename); stdout is unchanged")
    ap.add_argument("--text", action="store_true",
                    help="human-readable report instead of JSON")
    ap.add_argument("--concurrency", action="store_true",
                    help="append the in-process concurrency sanitizer "
                         "report (lock-order graph, potential-deadlock "
                         "cycles, blocking-under-lock/thread-leak "
                         "violations, live framework threads); recorded "
                         "violations make the exit nonzero; TARGET "
                         "becomes optional (see epilog)")
    ap.add_argument("--fail-on",
                    choices=("findings", "perf", "error", "never"),
                    default="findings",
                    help="severity floor for exit 1: findings (default: "
                         "errors+warnings), perf (also perf hints — the "
                         "strict lane lint gate), error, never")
    args = ap.parse_args(argv)

    # malformed --mesh is a usage error with its own message — not a
    # "cannot load target" traceback
    try:
        mesh = _parse_mesh(args.mesh)
    except ValueError as e:
        print("error: %s" % e, file=sys.stderr)
        return 2

    if args.plan:
        return _run_plan(args, mesh)

    if args.target is None and not args.concurrency:
        print("error: TARGET is required without --plan/--concurrency",
              file=sys.stderr)
        return 2

    report = None
    doc = {}
    level = "full" if args.cost else args.level
    if args.target is not None:
        try:
            program, feed_names, fetch_names, state_specs = _load_target(
                args.target)
        except Exception as e:  # noqa: BLE001 — CLI boundary
            print("error: cannot load %s: %s: %s"
                  % (args.target, type(e).__name__, e), file=sys.stderr)
            return 2

        from .analyzer import analyze
        from .memory import shard_divisors

        param_shards, act_shards = shard_divisors(mesh)

        # saved models are inference programs: analyze in test mode
        report = analyze(
            program, feed_names=feed_names, fetch_names=fetch_names,
            state_names=(set(state_specs)
                         if state_specs is not None else None),
            state_specs=state_specs, platform=args.platform, level=level,
            is_test=True, default_dim=args.batch,
            device_kind=args.device,
            param_shards=param_shards, act_shards=act_shards)

        doc = {
            "target": args.target,
            "platform": args.platform,
            "level": level,
            "report": report.to_dict(),
        }
    if args.cost and args.target is not None:
        from .costs import analyze_cost

        # gradient sync rides the batch-sharding axes; sp/seq shard the
        # sequence and keep full gradients, so they don't widen the group
        dp_shards = 1
        for axis, size in mesh.items():
            if str(axis).lower() in ("dp", "data", "batch"):
                dp_shards *= int(size)
        try:
            cost = analyze_cost(
                program, feed_names=feed_names, state_specs=state_specs,
                fetch_names=fetch_names,
                state_names=(set(state_specs)
                             if state_specs is not None else None),
                is_test=True, platform=args.platform,
                default_dim=args.batch, device_kind=args.device,
                param_shards=param_shards, act_shards=act_shards,
                dp_shards=dp_shards)
            doc["cost"] = cost.to_dict()
        except Exception as e:  # noqa: BLE001 — cost model must not
            # take down the structural report
            doc["cost"] = {"error": "%s: %s" % (type(e).__name__, e)}
    n_conc = 0
    if args.concurrency:
        from . import concurrency

        cdoc = concurrency.report()
        doc["concurrency"] = cdoc
        n_conc = len(cdoc["violations"]) + cdoc["violations_dropped"]

    rendered = json.dumps(doc, sort_keys=True, indent=2)
    if args.text:
        if report is not None:
            print("target: %s (platform %s, level %s)"
                  % (args.target, args.platform, level))
            print(str(report))
        if args.concurrency:
            cdoc = doc["concurrency"]
            print("concurrency: %d lock(s), %d order edge(s), "
                  "%d cycle(s), %d violation(s)%s, %d live thread(s)"
                  % (len(cdoc["locks"]), len(cdoc["edges"]),
                     len(cdoc["cycles"]), len(cdoc["violations"]),
                     " (+%d dropped)" % cdoc["violations_dropped"]
                     if cdoc["violations_dropped"] else "",
                     len(cdoc["live_threads"])))
            for v in cdoc["violations"]:
                print("%s: %s" % (v.get("check"), v.get("message")))
        if (args.cost and report is not None
                and "error" not in doc["cost"]):
            c = doc["cost"]
            print("cost: %.3g flops, %.3g bytes moved, peak HBM %.3g MB"
                  % (c["total_flops"], c["total_bytes"],
                     c["memory"]["peak_bytes"] / 1e6))
            if "predicted_step_seconds" in c:
                print("roofline: %.3g s/step, MFU %.3g (%s-bound on %s)"
                      % (c["predicted_step_seconds"],
                         c.get("predicted_mfu", 0.0),
                         c.get("bound", "?"),
                         c.get("device", {}).get("name", "?")))
            if "comm" in c:
                cc = c["comm"]
                line = ("comm: dp=%d, %.3g grad bytes"
                        % (cc["dp_shards"], cc["grad_bytes"]))
                if "predicted_allreduce_seconds" in cc:
                    line += (", allreduce %.3g s"
                             % cc["predicted_allreduce_seconds"])
                if "scaling_efficiency" in cc:
                    line += (", scaling efficiency %.3g"
                             % cc["scaling_efficiency"])
                print(line)
    else:
        print(rendered)
    if args.json_out:
        try:
            _atomic_write(args.json_out, rendered + "\n")
        except OSError as e:
            print("error: cannot write %s: %s" % (args.json_out, e),
                  file=sys.stderr)
            return 2

    if args.fail_on == "never":
        return 0
    # concurrency violations are error-grade under every gating mode:
    # a recorded lock-order cycle IS a latent deadlock
    if n_conc:
        return 1
    if report is None:
        return 0
    if args.fail_on == "error":
        return 1 if report.errors else 0
    if args.fail_on == "perf":
        return 1 if (report.findings
                     or report.by_severity("perf")) else 0
    return 1 if report.findings else 0


if __name__ == "__main__":  # pragma: no cover
    sys.exit(main())
