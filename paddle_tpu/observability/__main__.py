"""Observability CLI.

``python -m paddle_tpu.observability trace <dir> [-o out.json]``
merges the per-process ``trace-*.jsonl`` span files a traced serving
run left under ``$PADDLE_TPU_TRACE_DIR`` into one Perfetto-loadable
Chrome trace-event file (load it at https://ui.perfetto.dev or
``chrome://tracing``) and prints a per-trace phase summary. Given a
JSON file with a ``spans`` key in place of the directory — a crash
dump, or what ``obs.get_recorder().crash_dump(path)`` writes from a
live replica — it draws the in-memory span ring the same way: the
engine loop's phases and every request's spans, one track per thread,
with no trace directory.

``python -m paddle_tpu.observability perf <dir|snapshot.json>``
renders the executable ledger's predicted-vs-XLA-vs-measured drift
table from an ``ExecutableLedger.snapshot()`` JSON (bare, or under a
``"ledger"`` key) or a directory of them.

``python -m paddle_tpu.observability run <dir|snapshot.json> [B]``
renders a training run-health report — goodput decomposition, loss
trajectory, anomaly counts — from a ``RunHealth.dump()`` snapshot, a
StepSeries JSONL, a crash dump, or a directory of any. With a second
path it renders the A/B comparison table instead.
"""
import argparse
import json
import sys

from . import distributed as _dist
from . import perf as _perf
from . import runhealth as _rh


def _ring_spans(path):
    """The span ring a crash dump carries under ``spans``, as the
    records ``chrome_trace`` takes: one track per thread of the process
    (or per ``proc`` field), the parent span's name among the args."""
    with open(path, encoding="utf-8") as f:
        doc = json.load(f)
    out = []
    for i, s in enumerate(doc.get("spans") or ()):
        args = dict(s.get("fields") or {}, parent=s.get("parent"))
        out.append({
            "span": i, "name": s["name"], "tid": s.get("thread", "main"),
            "proc": args.pop("proc", None) or "pid%s" % doc.get("pid"),
            "t0": s["t0"], "dur": s["t1"] - s["t0"], "args": args})
    return out


def _cmd_trace(args):
    import os

    spans = (_dist.read_spans(args.dir) if os.path.isdir(args.dir)
             else _ring_spans(args.dir))
    if not spans:
        print("no span records under %s" % args.dir, file=sys.stderr)
        return 1
    doc = _dist.chrome_trace(spans, trace_id=args.trace_id)
    out = args.out or "trace.json"
    tmp = out + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(doc, f)
    os.replace(tmp, out)
    meta = doc["otherData"]
    print("wrote %s: %d spans, %d cross-process flows, %d process "
          "tracks, %d trace(s)" % (out, meta["spans"], meta["flows"],
                                   len(meta["processes"]),
                                   len(meta["traces"])))
    for tid in meta["traces"]:
        phases = _dist.phase_breakdown(spans, trace_id=tid)
        if not phases:
            continue
        parts = []
        for phase in _dist.PHASES:
            st = phases.get(phase)
            if st:
                parts.append("%s %.1fms x%d"
                             % (phase, st["total_s"] * 1e3, st["count"]))
        print("  trace %s: %s" % (tid[:16], ", ".join(parts) or "-"))
    return 0


def _cmd_perf(args):
    snap = _perf.load_snapshot(args.path)
    rows = _perf.drift_rows(snap)
    if not rows:
        print("no ledger entries under %s (want an "
              "ExecutableLedger.snapshot() file)" % args.path,
              file=sys.stderr)
        return 1
    print(_perf.render_drift_table(rows))
    s = _perf.drift_summary(rows)
    parts = ["%d executable(s)" % s["entries"],
             "%d partial" % s["partial"],
             "%d measured" % s["with_measured"]]
    if s["mean_abs_step_drift_pct"] is not None:
        parts.append("mean |step drift| %.1f%%"
                     % s["mean_abs_step_drift_pct"])
    if s["mean_abs_hbm_drift_pct"] is not None:
        parts.append("mean |hbm drift| %.1f%%"
                     % s["mean_abs_hbm_drift_pct"])
    print(", ".join(parts))
    if args.out:
        tmp = args.out + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"rows": rows, "summary": s}, f)
        import os

        os.replace(tmp, args.out)
        print("wrote %s" % args.out)
    return 0


def _cmd_run(args):
    run_a = _rh.load_run(args.path)
    if run_a["series"] is None and run_a["goodput"] is None:
        print("no run-health records under %s (want a RunHealth "
              "snapshot JSON, a StepSeries JSONL, a crash dump, or a "
              "directory of any)" % args.path, file=sys.stderr)
        return 1
    if args.path_b:
        run_b = _rh.load_run(args.path_b)
        if run_b["series"] is None and run_b["goodput"] is None:
            print("no run-health records under %s" % args.path_b,
                  file=sys.stderr)
            return 1
        print("A: %s\nB: %s" % (run_a["path"], run_b["path"]))
        print(_rh.render_comparison(run_a, run_b))
    else:
        print(_rh.render_health_report(run_a))
    if args.out:
        doc = {"a": run_a}
        if args.path_b:
            doc["b"] = run_b
        tmp = args.out + ".tmp"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        import os

        os.replace(tmp, args.out)
        print("wrote %s" % args.out)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.observability",
        description=__doc__)
    sub = ap.add_subparsers(dest="cmd", required=True)
    tr = sub.add_parser("trace", help="merge JSONL span files into a "
                        "Chrome trace-event JSON")
    tr.add_argument("dir", help="trace directory (the run's "
                    "$PADDLE_TPU_TRACE_DIR), or a crash-dump JSON "
                    "file whose `spans` key holds the span ring")
    tr.add_argument("-o", "--out", default=None,
                    help="output path (default: trace.json)")
    tr.add_argument("--trace-id", default=None,
                    help="keep only this trace id")
    tr.set_defaults(fn=_cmd_trace)
    pf = sub.add_parser("perf", help="render the executable ledger's "
                        "predicted-vs-XLA-vs-measured drift table")
    pf.add_argument("path", help="a ledger snapshot JSON or a "
                    "directory of them")
    pf.add_argument("-o", "--out", default=None,
                    help="also write the rows+summary as JSON here")
    pf.set_defaults(fn=_cmd_perf)
    rn = sub.add_parser("run", help="render a training run-health "
                        "report (goodput + anomalies), or an A/B "
                        "comparison of two runs")
    rn.add_argument("path", help="RunHealth snapshot JSON, StepSeries "
                    "JSONL, crash dump, or a directory of any")
    rn.add_argument("path_b", nargs="?", default=None,
                    help="optional second run for an A/B comparison")
    rn.add_argument("-o", "--out", default=None,
                    help="also write the loaded run doc(s) as JSON")
    rn.set_defaults(fn=_cmd_run)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
