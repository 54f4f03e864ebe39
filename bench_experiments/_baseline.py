"""Persistent perf-baseline store + regression gate.

``bench.py --update-baseline`` banks the best-per-metric figures of a
bench result JSON into ``bench_experiments/BASELINE.json`` (NOT the
repo-root BASELINE.json, which is the immutable seed reference);
``bench.py --check-regressions`` compares a fresh result against the
bank and fails with an attributed report when any metric moved beyond
its tolerance in the bad direction. Stdlib-only: the gate never imports
jax and never touches the chip.

Store schema (``version`` 1)::

    {"version": 1,
     "lanes": {
       "<lane>": {"metrics": {"<metric>": <number>, ...},
                  "banked_unix": <int>}}}

Lanes are the bench's independently-measured sections: the headline
training lane (keyed by the result's ``metric`` field, e.g.
``bert_tiny_pretrain_throughput_cpu``) plus ``serving`` /
``decode_serving`` / ``disagg_serving`` / ``spec_serving`` /
``retrieval`` when present. ``update`` keeps
the BEST value per metric across rounds (direction-aware), so a lucky
round ratchets the bar and a slow round never lowers it.

Tolerances are percentages of the banked value; direction says which
way is a regression. ``predicted_oom`` is absolute-zero-tolerance: any
newly predicted OOM is a fail.
"""
import json
import os
import time

__all__ = ["DEFAULT_TOLERANCES", "BaselineStore", "extract_lanes"]

# metric -> (better direction, tolerance % of banked value)
DEFAULT_TOLERANCES = {
    "tokens_per_sec": ("higher", 10.0),
    "step_ms": ("lower", 15.0),
    "compile_s": ("lower", 60.0),
    "ttft_ms_p99": ("lower", 25.0),
    "per_token_ms_p99": ("lower", 25.0),
    "predicted_oom": ("lower", 0.0),
    # spec_serving lane (ISSUE 19): the prefix-adoption economics must
    # not erode, and draft acceptance is seed-sensitive so it gets a
    # wide band — the lane itself hard-fails under 50% rows saved
    "prefill_flops_saved_pct": ("higher", 10.0),
    "spec_accept_rate": ("higher", 40.0),
    # retrieval lane (ISSUE 20): throughputs get the serving band;
    # recall is exact-or-fail (the lane hard-errors below 1.0, the
    # gate backstops a silently-degraded result doc)
    "lookup_ex_per_sec": ("higher", 25.0),
    "search_queries_per_sec": ("higher", 25.0),
    "recall_at_k": ("higher", 0.0),
    "blocked_matmul_gflops": ("higher", 30.0),
}

# keys lifted out of serving-style lane docs (top level + one nested
# dict level, so decode_serving's inner sections are covered)
_WANTED = ("ttft_ms_p99", "per_token_ms_p99", "tokens_per_sec",
           "step_ms", "compile_s", "prefill_flops_saved_pct",
           "spec_accept_rate", "lookup_ex_per_sec",
           "search_queries_per_sec", "recall_at_k",
           "blocked_matmul_gflops")


def _num(v):
    if isinstance(v, bool) or not isinstance(v, (int, float)):
        return None
    return float(v)


def _count_oom(obj, depth=0):
    """Occurrences of 'predicted-oom' in any string of a (shallowly
    nested) result section."""
    if isinstance(obj, str):
        return obj.count("predicted-oom")
    if depth >= 4:
        return 0
    if isinstance(obj, dict):
        return sum(_count_oom(v, depth + 1) for v in obj.values())
    if isinstance(obj, (list, tuple)):
        return sum(_count_oom(v, depth + 1) for v in obj)
    return 0


def extract_lanes(result):
    """{lane: {metric: value}} from one bench result JSON."""
    lanes = {}
    detail = result.get("detail") or {}
    head = {}
    v = _num(result.get("value"))
    if v is not None and v > 0:
        head["tokens_per_sec"] = v
    for k in ("step_ms", "compile_s"):
        n = _num(detail.get(k))
        if n is not None:
            head[k] = n
    head["predicted_oom"] = _count_oom(detail.get("errors") or [])
    lane_name = result.get("metric") or "headline"
    lanes[lane_name] = head
    for sect in ("serving", "decode_serving", "disagg_serving",
                 "spec_serving", "retrieval"):
        doc = detail.get(sect)
        if not isinstance(doc, dict):
            continue
        got = {}
        for k in _WANTED:
            n = _num(doc.get(k))
            if n is not None:
                got[k] = n
        for sub in doc.values():
            if not isinstance(sub, dict):
                continue
            for k in _WANTED:
                if k in got:
                    continue
                n = _num(sub.get(k))
                if n is not None:
                    got[k] = n
        got["predicted_oom"] = _count_oom(doc)
        if got:
            lanes[sect] = got
    return lanes


def _better(direction, new, old):
    return new > old if direction == "higher" else new < old


class BaselineStore:
    """Best-per-metric bank + tolerance gate over bench result JSONs."""

    def __init__(self, path=None):
        self.path = path or os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "BASELINE.json")

    def load(self):
        try:
            with open(self.path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            return {"version": 1, "lanes": {}}
        if not isinstance(doc, dict) or "lanes" not in doc:
            return {"version": 1, "lanes": {}}
        return doc

    def _save(self, doc):
        tmp = "%s.tmp-%d" % (self.path, os.getpid())
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1, sort_keys=True)
        os.replace(tmp, self.path)

    def update(self, result, tolerances=None):
        """Bank `result`, keeping the best value per (lane, metric).
        Returns {lane: [metrics that improved or are new]}."""
        tol = dict(DEFAULT_TOLERANCES)
        tol.update(tolerances or {})
        doc = self.load()
        banked = {}
        for lane, metrics in extract_lanes(result).items():
            slot = doc["lanes"].setdefault(
                lane, {"metrics": {}, "banked_unix": 0})
            for m, v in metrics.items():
                direction = tol.get(m, ("lower", 0.0))[0]
                old = _num(slot["metrics"].get(m))
                if old is None or _better(direction, v, old):
                    slot["metrics"][m] = v
                    banked.setdefault(lane, []).append(m)
            if lane in banked:
                slot["banked_unix"] = int(time.time())
        self._save(doc)
        return banked

    def check(self, result, tolerances=None):
        """Compare `result` against the bank. Returns
        ``{"regressions": [...], "checked": [...],
        "missing_lanes": [...]}`` — each regression dict carries lane,
        metric, baseline, current, change_pct, tolerance_pct, and the
        better-direction, so the report attributes the failure."""
        tol = dict(DEFAULT_TOLERANCES)
        tol.update(tolerances or {})
        doc = self.load()
        out = {"regressions": [], "checked": [], "missing_lanes": []}
        current = extract_lanes(result)
        for lane, metrics in current.items():
            slot = doc["lanes"].get(lane)
            if slot is None:
                out["missing_lanes"].append(lane)
                continue
            for m, v in metrics.items():
                base = _num(slot["metrics"].get(m))
                if base is None or m not in tol:
                    continue
                direction, t_pct = tol[m]
                if base == 0:
                    # zero baseline: any move in the bad direction of an
                    # absolute-tolerance metric (predicted_oom) fails
                    change_pct = None
                    bad = (v > base if direction == "lower"
                           else v < base) and t_pct == 0.0
                else:
                    change_pct = 100.0 * (v - base) / abs(base)
                    bad = (change_pct < -t_pct if direction == "higher"
                           else change_pct > t_pct)
                rec = {"lane": lane, "metric": m, "baseline": base,
                       "current": v,
                       "change_pct": (round(change_pct, 1)
                                      if change_pct is not None else None),
                       "tolerance_pct": t_pct, "direction": direction}
                out["checked"].append(rec)
                if bad:
                    out["regressions"].append(rec)
        return out

    def render_report(self, report):
        lines = []
        regs = report["regressions"]
        if regs:
            lines.append("PERF REGRESSIONS (%d):" % len(regs))
            for r in regs:
                delta = ("%+.1f%%" % r["change_pct"]
                         if r["change_pct"] is not None
                         else "%r -> %r" % (r["baseline"], r["current"]))
                lines.append(
                    "  FAIL %s.%s: %s vs banked %s (%s, tolerance "
                    "%.0f%%, better=%s)"
                    % (r["lane"], r["metric"], r["current"],
                       r["baseline"], delta, r["tolerance_pct"],
                       r["direction"]))
        else:
            lines.append("perf gate clean: no regressions")
        n_ok = len(report["checked"]) - len(regs)
        lines.append("  %d metric(s) checked, %d within tolerance"
                     % (len(report["checked"]), n_ok))
        for lane in report["missing_lanes"]:
            lines.append("  note: lane %r has no baseline yet "
                         "(run --update-baseline)" % lane)
        return "\n".join(lines)
