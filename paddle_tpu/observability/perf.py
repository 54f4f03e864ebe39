"""Predicted-vs-XLA-vs-measured drift reporting over the executable
ledger.

Three columns per executable, one source each:

- **predicted** — the static analyzer's roofline (``analysis.costs``)
  noted into the ledger per program fingerprint,
- **XLA** — what ``compiled.cost_analysis()`` /
  ``memory_analysis()`` reported at registration (absent on partial
  entries: deserialized disk artifacts, backends without the APIs),
- **measured** — steady-state step seconds a timed or serving loop
  attached via ``ExecutableLedger.note_measured``.

``drift_rows`` flattens a ledger (live object or ``snapshot()`` dict)
into comparable rows; ``render_drift_table`` prints them as an aligned
text table; ``load_snapshot`` reads them back from a telemetry JSON
that embeds the snapshot under a ``"ledger"`` key, a bare
ledger-snapshot JSON, or a directory of either. The
``python -m paddle_tpu.observability perf <dir|snapshot.json>`` CLI
wraps the three.

Stdlib-only, like the rest of the package.
"""
import json
import os

from . import ledger as _ledger

__all__ = ["drift_rows", "render_drift_table", "load_snapshot",
           "drift_summary"]


def _entries_of(snap):
    if snap is None:
        return []
    if isinstance(snap, _ledger.ExecutableLedger):
        return snap.entries()
    if isinstance(snap, dict):
        return list(snap.get("entries") or [])
    if isinstance(snap, (list, tuple)):
        return list(snap)
    return []


def _pct(new, ref):
    """Signed percent drift of `new` vs `ref` (None when either side
    is unknown or the reference is 0)."""
    if new is None or not ref:
        return None
    return 100.0 * (float(new) - float(ref)) / float(ref)


def drift_rows(snap):
    """One row per ledger entry: the predicted / XLA / measured
    columns plus signed drift percentages (``step_drift_pct`` =
    predicted vs measured step time, ``hbm_drift_pct`` = predicted vs
    XLA peak HBM)."""
    rows = []
    for e in _entries_of(snap):
        pred = e.get("predicted") or {}
        xla = e.get("xla") or {}
        mem = e.get("memory") or {}
        measured_s = e.get("measured_step_seconds")
        pred_s = pred.get("predicted_step_seconds")
        pred_hbm = pred.get("predicted_peak_hbm_bytes")
        xla_hbm = mem.get("total_bytes")
        rows.append({
            "n": e.get("n"),
            "kind": e.get("kind"),
            "source": e.get("source"),
            "fingerprint": (e.get("fingerprint") or "")[:12] or "-",
            "partial": bool(e.get("partial")),
            "compile_s": e.get("compile_seconds"),
            "predicted_step_ms": None if pred_s is None
            else 1e3 * pred_s,
            "predicted_mfu": pred.get("predicted_mfu"),
            "predicted_hbm_mb": None if pred_hbm is None
            else pred_hbm / 1e6,
            "predicted_gflops": None if pred.get("total_flops") is None
            else pred["total_flops"] / 1e9,
            "xla_gflops": None if xla.get("flops") is None
            else xla["flops"] / 1e9,
            "xla_bytes_mb": None if xla.get("bytes_accessed") is None
            else xla["bytes_accessed"] / 1e6,
            "xla_hbm_mb": None if xla_hbm is None else xla_hbm / 1e6,
            "measured_step_ms": None if measured_s is None
            else 1e3 * measured_s,
            "step_drift_pct": _pct(pred_s, measured_s),
            "flops_drift_pct": _pct(pred.get("total_flops"),
                                    xla.get("flops")),
            "hbm_drift_pct": _pct(pred_hbm, xla_hbm),
        })
    return rows


_COLUMNS = (
    # (header, row key, format)
    ("#", "n", "%d"),
    ("kind", "kind", "%s"),
    ("src", "source", "%s"),
    ("fingerprint", "fingerprint", "%s"),
    ("compile_s", "compile_s", "%.2f"),
    ("pred_ms", "predicted_step_ms", "%.2f"),
    ("xla_gflop", "xla_gflops", "%.3f"),
    ("xla_hbm_mb", "xla_hbm_mb", "%.1f"),
    ("meas_ms", "measured_step_ms", "%.2f"),
    ("step_drift%", "step_drift_pct", "%+.1f"),
    ("hbm_drift%", "hbm_drift_pct", "%+.1f"),
)


def render_drift_table(rows):
    """Aligned text table of :func:`drift_rows` output. Unknown cells
    render as ``-`` (partial entries have no XLA columns; executables
    never driven by a timed loop have no measured column)."""
    cells = []
    for r in rows:
        line = []
        for _, key, fmt in _COLUMNS:
            v = r.get(key)
            line.append("-" if v is None else fmt % v)
        cells.append(line)
    headers = [c[0] for c in _COLUMNS]
    widths = [max(len(h), *(len(row[i]) for row in cells))
              if cells else len(h) for i, h in enumerate(headers)]
    out = ["  ".join(h.ljust(widths[i])
                     for i, h in enumerate(headers))]
    out.append("  ".join("-" * w for w in widths))
    for line in cells:
        out.append("  ".join(line[i].ljust(widths[i])
                             for i in range(len(widths))))
    return "\n".join(out)


def drift_summary(rows):
    """Aggregate line: entry counts + mean absolute step/HBM drift over
    the rows where both sides are known."""
    step = [abs(r["step_drift_pct"]) for r in rows
            if r["step_drift_pct"] is not None]
    hbm = [abs(r["hbm_drift_pct"]) for r in rows
           if r["hbm_drift_pct"] is not None]
    return {
        "entries": len(rows),
        "partial": sum(1 for r in rows if r["partial"]),
        "with_measured": sum(1 for r in rows
                             if r["measured_step_ms"] is not None),
        "mean_abs_step_drift_pct": round(sum(step) / len(step), 1)
        if step else None,
        "mean_abs_hbm_drift_pct": round(sum(hbm) / len(hbm), 1)
        if hbm else None,
    }


def _snapshot_of_doc(doc):
    """A ledger snapshot out of one loaded JSON document: either
    embedded ({"ledger": {...}}) or bare ({"entries": [...]})."""
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("ledger"), dict):
        doc = doc["ledger"]
    if isinstance(doc.get("entries"), list):
        return doc
    return None


def load_snapshot(path):
    """Read ledger entries from `path`: a JSON file, or a directory
    whose ``*.json`` files are scanned (unreadable / unrelated files
    are skipped) and merged. Returns a snapshot dict; its ``entries``
    list is empty when nothing ledger-shaped was found."""
    merged = {"entries": [], "predictions": {}, "measured": {}}
    if os.path.isdir(path):
        names = sorted(n for n in os.listdir(path)
                       if n.endswith(".json"))
        paths = [os.path.join(path, n) for n in names]
    else:
        paths = [path]
    for p in paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        snap = _snapshot_of_doc(doc)
        if snap is None:
            continue
        merged["entries"].extend(snap.get("entries") or [])
        merged["predictions"].update(snap.get("predictions") or {})
        merged["measured"].update(snap.get("measured") or {})
    return merged
