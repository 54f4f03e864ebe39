"""Process-wide executable ledger: what XLA actually compiled.

The analyzers predict step seconds / MFU / peak HBM *before* a compile
(analysis/costs, analysis/memory); this module records what came out of
the other end — one entry per compiled executable (executor step,
dataset-scan body, Predictor engine, serving/decode warmup programs,
and compile-cache disk hits) carrying:

- the program's **structural fingerprint** (``fluid.compile_cache.
  program_fingerprint`` — stable across processes, unlike
  ``Program._uid``),
- XLA's own accounting, probed with guards so backends/artifacts
  without the APIs degrade to *partial* entries instead of failing:
  ``compiled.cost_analysis()`` FLOPs / bytes-accessed and
  ``compiled.memory_analysis()`` HBM breakdown (argument / output /
  temp / generated-code bytes),
- compile seconds and the donation set,
- the analyzer's *predicted* step-seconds/MFU/peak-HBM for the same
  fingerprint (:meth:`ExecutableLedger.note_prediction`), and
- measured steady-state step seconds when a bench/serving loop reports
  them (:meth:`ExecutableLedger.note_measured`).

That closes the predicted -> compiled -> measured loop per executable:
``observability.perf`` renders the drift table, ``analysis.costs.
DeviceProfile.calibrated_from`` fits effective device constants from
it, and ``FlightRecorder.crash_dump`` appends the ledger tail so a
post-mortem shows what was compiled and resident at death.

Telemetry (gated on ``PADDLE_TPU_TELEMETRY`` like every obs helper):
``ledger.registered`` / ``ledger.partial`` / ``ledger.disk_hits``
counters, ``ledger.entries`` gauge, ``ledger.compile_seconds`` and
``ledger.measured_step_seconds`` histograms, and one
``executable_registered`` flight-recorder event per entry.

Stdlib-only: jax objects are probed with ``getattr`` at registration
time, never imported — crash-path and supervisor code can read the
ledger without accelerator init.
"""
import collections
import threading
import time

from . import recorder as _r
from . import telemetry as _t

__all__ = ["ExecutableLedger", "get_ledger"]

# snapshot()/tail() field caps — entries ride in crash dumps and
# telemetry JSON, so every free-form field is bounded
_MAX_DONATED = 32
_MAX_PREDICTIONS = 256

# memory_analysis() attributes -> entry keys
_MEMORY_ATTRS = (
    ("argument_size_in_bytes", "argument_bytes"),
    ("output_size_in_bytes", "output_bytes"),
    ("temp_size_in_bytes", "temp_bytes"),
    ("alias_size_in_bytes", "alias_bytes"),
    ("generated_code_size_in_bytes", "generated_code_bytes"),
)

_PREDICTED_KEYS = ("predicted_step_seconds", "predicted_mfu",
                   "predicted_peak_hbm_bytes", "total_flops",
                   "total_bytes", "device")


def _num(v):
    if isinstance(v, bool):
        return None
    if isinstance(v, (int, float)):
        return float(v)
    return None


def _probe_cost(compiled):
    """``compiled.cost_analysis()`` -> {flops, bytes_accessed, ...} or
    None. Guarded: backends without the API (deserialized
    ``jax.export`` artifacts, some CPU paths) and API-shape drift
    (dict vs list-of-dict across jax versions) both degrade to None."""
    fn = getattr(compiled, "cost_analysis", None)
    if fn is None:
        return None
    try:
        ca = fn()
    except Exception:  # noqa: BLE001 — absent analysis, not an error
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for k, v in ca.items():
        v = _num(v)
        if v is None:
            continue
        key = str(k).replace(" ", "_")
        if key in ("flops", "bytes_accessed", "transcendentals",
                   "optimal_seconds"):
            out[key] = v
    return out or None


def _probe_memory(compiled):
    """``compiled.memory_analysis()`` -> HBM breakdown dict or None,
    with the same degradation guards as :func:`_probe_cost`."""
    fn = getattr(compiled, "memory_analysis", None)
    if fn is None:
        return None
    try:
        ma = fn()
    except Exception:  # noqa: BLE001
        return None
    if ma is None:
        return None
    out = {}
    for attr, key in _MEMORY_ATTRS:
        v = _num(getattr(ma, attr, None))
        if v is not None:
            out[key] = int(v)
    if not out:
        return None
    # XLA's convention: arguments + outputs + temps + generated code,
    # minus buffers aliased onto arguments (donation)
    total = (out.get("argument_bytes", 0) + out.get("output_bytes", 0)
             + out.get("temp_bytes", 0)
             + out.get("generated_code_bytes", 0)
             - out.get("alias_bytes", 0))
    out["total_bytes"] = int(max(total, 0))
    return out


def _clean_prediction(predicted):
    if not isinstance(predicted, dict):
        return None
    out = {}
    for k in _PREDICTED_KEYS:
        v = predicted.get(k)
        if k == "device":
            if isinstance(v, dict):
                out[k] = {dk: dv for dk, dv in v.items()
                          if dv is None or isinstance(dv,
                                                      (int, float, str))}
            continue
        v = _num(v)
        if v is not None:
            out[k] = v
    return out or None


class ExecutableLedger:
    """Bounded ring of executable entries + per-fingerprint prediction
    and measurement side tables. Thread-safe; every mutator is cheap
    and never raises past its guards (a ledger must not break a
    compile)."""

    def __init__(self, maxlen=512):
        self._lock = threading.Lock()
        self._entries = collections.deque(maxlen=int(maxlen))
        self._predictions = collections.OrderedDict()  # fp -> dict
        self._measured = collections.OrderedDict()     # fp -> seconds
        self._seq = 0

    # -- write side ------------------------------------------------------
    def register(self, kind, fingerprint=None, compiled=None,
                 source="compile", compile_seconds=None, donated=None,
                 extra=None):
        """Record one executable. ``compiled`` is probed (guarded) for
        ``cost_analysis``/``memory_analysis``; everything else is
        plain data. Returns the entry dict (a live reference — callers
        must not mutate it)."""
        xla = _probe_cost(compiled) if compiled is not None else None
        mem = _probe_memory(compiled) if compiled is not None else None
        with self._lock:
            self._seq += 1
            entry = {
                "n": self._seq,
                "wall": time.time(),
                "kind": str(kind),
                "source": str(source),
                "fingerprint": fingerprint,
                "compile_seconds": _num(compile_seconds),
                "donated": sorted(str(d) for d in donated)[:_MAX_DONATED]
                if donated else [],
                "xla": xla,
                "memory": mem,
                "partial": xla is None and mem is None,
                "predicted": self._predictions.get(fingerprint)
                if fingerprint else None,
                "measured_step_seconds": self._measured.get(fingerprint)
                if fingerprint else None,
            }
            if isinstance(extra, dict):
                for k, v in extra.items():
                    entry.setdefault(str(k), v)
            self._entries.append(entry)
            n_entries = len(self._entries)
        self._emit(entry, n_entries)
        return entry

    def _emit(self, entry, n_entries):
        if _t.mode() == _t.OFF:
            return
        hub = _t._hub
        hub.inc("ledger.registered")
        if entry["partial"]:
            hub.inc("ledger.partial")
        if entry["source"] == "disk":
            hub.inc("ledger.disk_hits")
        hub.set_gauge("ledger.entries", n_entries)
        if entry["compile_seconds"] is not None:
            hub.observe("ledger.compile_seconds",
                        entry["compile_seconds"])
        mem = entry.get("memory") or {}
        if mem.get("total_bytes") is not None:
            hub.set_gauge("ledger.hbm_total_bytes", mem["total_bytes"])
        fields = {"exe_kind": entry["kind"],
                  "exe_source": entry["source"],
                  "partial": entry["partial"]}
        if entry["fingerprint"]:
            fields["fingerprint"] = entry["fingerprint"][:16]
        if entry["compile_seconds"] is not None:
            fields["seconds"] = round(entry["compile_seconds"], 6)
        _r._global.record("executable_registered", source="ledger",
                          **fields)

    def note_prediction(self, fingerprint, predicted):
        """Attach the analyzer's prediction for a program fingerprint;
        backfills entries already registered under it. ``predicted``
        keys: predicted_step_seconds / predicted_mfu /
        predicted_peak_hbm_bytes / total_flops / total_bytes / device
        (a ``DeviceProfile.to_dict()``)."""
        if not fingerprint:
            return
        predicted = _clean_prediction(predicted)
        if predicted is None:
            return
        with self._lock:
            self._predictions[fingerprint] = predicted
            self._predictions.move_to_end(fingerprint)
            while len(self._predictions) > _MAX_PREDICTIONS:
                self._predictions.popitem(last=False)
            for e in self._entries:
                if e["fingerprint"] == fingerprint:
                    e["predicted"] = predicted

    def note_measured(self, fingerprint, step_seconds, kind=None):
        """Attach a measured steady-state step time (seconds) to every
        entry under ``fingerprint`` (optionally restricted to one
        ``kind``)."""
        t = _num(step_seconds)
        if not fingerprint or t is None or t <= 0:
            return
        with self._lock:
            self._measured[fingerprint] = t
            self._measured.move_to_end(fingerprint)
            while len(self._measured) > _MAX_PREDICTIONS:
                self._measured.popitem(last=False)
            for e in self._entries:
                if e["fingerprint"] == fingerprint and (
                        kind is None or e["kind"] == kind):
                    e["measured_step_seconds"] = t
        if _t.mode() != _t.OFF:
            _t._hub.observe("ledger.measured_step_seconds", t)

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._predictions.clear()
            self._measured.clear()

    # -- read side -------------------------------------------------------
    def entries(self):
        with self._lock:
            return [dict(e) for e in self._entries]

    def __len__(self):
        with self._lock:
            return len(self._entries)

    def snapshot(self):
        """JSON-safe view: {"entries": [...], "predictions": {...},
        "measured": {...}} — what the perf CLI reads back, bare or
        under a ``"ledger"`` key."""
        with self._lock:
            return {
                "entries": [dict(e) for e in self._entries],
                "predictions": {k: dict(v)
                                for k, v in self._predictions.items()},
                "measured": dict(self._measured),
            }

    def tail(self, n=16):
        """Compact newest-last view for crash dumps: fingerprint,
        kind/source, compile seconds, HBM bytes."""
        out = []
        for e in self.entries()[-int(n):]:
            mem = e.get("memory") or {}
            out.append({
                "n": e["n"],
                "kind": e["kind"],
                "source": e["source"],
                "fingerprint": (e["fingerprint"] or "")[:16] or None,
                "compile_seconds": e["compile_seconds"],
                "hbm_total_bytes": mem.get("total_bytes"),
                "partial": e["partial"],
            })
        return out


_global = ExecutableLedger()


def get_ledger():
    """The process-wide executable ledger."""
    return _global
