"""Training-run health: convergence flight recorder + goodput account.

Production TPU training is judged on two curves the rest of the
observability stack never sees: the *convergence trajectory* (loss,
gradient norms, AMP loss-scale — is the run still learning, or
quietly diverging toward the first NaN?) and *goodput* — the fraction
of wall-clock spent on productive optimizer steps rather than
compiles, input stalls, checkpoint writes, retry backoff, or steps
re-executed after a crash-resume (time-to-accuracy, not step time, is
the metric that matters at pod scale). This module is both recorders
plus the streaming anomaly detectors that close the loop into the
autopilot's TRAIN leg:

- :class:`StepSeries` — a bounded in-memory ring of per-step records
  (loss, grad global-norm pre/post clip, param/update-norm ratio, lr,
  AMP loss-scale + skipped flag, and the step's wall time split into
  data-wait / compute / fetch from the executor's existing phase
  timings), with JSONL export read back through the PR-17 tolerant
  reader. Each record also feeds the streaming detectors: loss-spike
  z-score over a trailing window, grad-norm explosion vs the trailing
  median, non-finite loss, plateau, and throughput sag — every firing
  bumps a ``runhealth.*`` counter and lands a flight-recorder event.
- :class:`GoodputAccount` — decomposes run wall-clock into
  ``productive_step`` / ``compile`` / ``data_stall`` / ``checkpoint``
  / ``retry_backoff`` / ``restart_rework`` buckets and reports the
  goodput fraction. The instrumented layers feed it through the
  module-level :func:`goodput_note` hook (inert without an active
  account, like every other observability hook): the executor notes
  compile seconds, ``GuardedExecutor`` its backoff sleeps,
  ``TrainGuard`` feed waits + checkpoint writes + crash-resume rework
  (steps the previous process ran past its last checkpoint, recomputed
  from the prior run's StepSeries JSONL vs ``latest_step``), and the
  pipelined runner its consumer-side queue waits.
- :class:`RunHealth` — the bundle ``TrainGuard(runhealth=...)`` wires
  in; :meth:`RunHealth.diverging` is the signal the autopilot's TRAIN
  leg confirms (through ActionGate hysteresis) before proposing — or
  in apply mode executing — a journaled rollback-to-last-finite-
  checkpoint + lr-cut.

Render a run-health report (or an A/B run comparison) with::

    python -m paddle_tpu.observability run <dir|snapshot.json> [B]

Stdlib-only, like the rest of the package.
"""
import collections
import json
import math
import os
import threading
import time

from . import recorder as _rec
from . import telemetry as _t

__all__ = [
    "StepSeries", "GoodputAccount", "RunHealth",
    "activate", "deactivate", "active", "active_goodput",
    "set_active_goodput", "goodput_note", "note_exec_phases",
    "take_exec_phases", "crash_snapshot", "load_run", "health_rows",
    "render_health_report", "compare_rows", "render_comparison",
]

GOODPUT_BUCKETS = ("productive_step", "compile", "data_stall",
                   "checkpoint", "retry_backoff", "restart_rework")

# anomaly kinds the detectors can emit (== the runhealth.<kind>
# counter family and the flight-recorder event kinds, source
# "runhealth")
ANOMALY_KINDS = ("loss_spike", "grad_explosion", "nonfinite_loss",
                 "plateau", "throughput_sag")


def _inc(name, n=1):
    if _t.mode() != _t.OFF:
        _t._hub.inc(name, n)


def _gauge(name, value):
    if _t.mode() != _t.OFF:
        _t._hub.set_gauge(name, value)


def _event(kind, **fields):
    # mirror obs.event(source="runhealth") without importing the
    # package facade (this module is imported BY it)
    if _t.mode() == _t.OFF:
        return
    _t._hub.inc("runhealth.%s" % kind)
    fields.setdefault("source", "runhealth")
    _rec._global.record(kind, **fields)


def _finite(v):
    try:
        return math.isfinite(float(v))
    except (TypeError, ValueError, OverflowError):
        return False


def _median(xs):
    xs = sorted(xs)
    if not xs:
        return None
    n = len(xs)
    mid = xs[n // 2]
    return mid if n % 2 else (xs[n // 2 - 1] + mid) / 2.0


# ---------------------------------------------------------------------------
# the per-step convergence recorder
# ---------------------------------------------------------------------------


class StepSeries:
    """Bounded ring of per-step training-health records + streaming
    anomaly detectors.

    :meth:`record` takes whatever the caller could measure this step —
    every field is optional — appends one dict record to the ring,
    optionally appends it to a JSONL sidecar (read back through the
    tolerant reader, so a torn final line from a crash never poisons
    the resume-side rework accounting), publishes the ``runhealth.*``
    gauges, and runs the detectors:

    - **loss spike** — z-score of this loss against the trailing
      ``window`` losses exceeds ``spike_z`` (needs ``min_samples``
      history; the detectors never fire cold).
    - **grad explosion** — grad global-norm over ``explode_factor``
      x the trailing median grad norm.
    - **nonfinite loss** — NaN/Inf loss (the binary signal the
      GuardedExecutor skip guard already acts on; recorded here so
      the trajectory shows WHEN finiteness was lost).
    - **plateau** — over the last ``plateau_window`` steps the loss
      improved by less than ``plateau_rel`` (relative); re-fires at
      most once per window.
    - **throughput sag** — step wall time over ``sag_factor`` x the
      trailing median step time.

    Detector state lives locally (``anomalies`` counter + per-kind
    last-firing step), so :meth:`diverging` works even with
    ``PADDLE_TPU_TELEMETRY=off``; the hub/ring routing is mode-gated
    like every other instrument.
    """

    def __init__(self, maxlen=4096, window=32, min_samples=8,
                 spike_z=6.0, explode_factor=10.0, plateau_window=64,
                 plateau_rel=1e-4, sag_factor=3.0, jsonl_path=None,
                 flush_every=8):
        self._lock = threading.Lock()
        self.records = collections.deque(maxlen=int(maxlen))
        self.window = int(window)
        self.min_samples = max(2, int(min_samples))
        self.spike_z = float(spike_z)
        self.explode_factor = float(explode_factor)
        self.plateau_window = int(plateau_window)
        self.plateau_rel = float(plateau_rel)
        self.sag_factor = float(sag_factor)
        self.jsonl_path = str(jsonl_path) if jsonl_path else None
        self._flush_every = max(1, int(flush_every))
        self._pending = []
        self._jsonl_dir_ok = False
        self.total = 0               # records ever taken (ring may drop)
        self.anomalies = collections.Counter()
        self._last_anomaly_step = {}  # kind -> step it last fired at
        self._losses = collections.deque(maxlen=self.window)
        # running first/second moments of _losses so the z-score costs
        # O(1) per step instead of re-summing the window
        self._loss_sum = 0.0
        self._loss_sumsq = 0.0
        self._grad_norms = collections.deque(maxlen=self.window)
        self._step_times = collections.deque(maxlen=self.window)
        self._plateau_hist = collections.deque(
            maxlen=max(2, self.plateau_window))
        self._last_plateau_check = 0
        self._last_step = None

    # -- recording -------------------------------------------------------
    def record(self, step, loss=None, grad_norm=None,
               grad_norm_clipped=None, update_ratio=None, lr=None,
               loss_scale=None, amp_skipped=None, skipped=None,
               retries=None, data_wait_s=None, compute_s=None,
               fetch_s=None, step_s=None, **extra):
        """Record one training step; returns the record dict."""
        rec = {"step": int(step), "wall": time.time()}
        for key, v in (("loss", loss), ("grad_norm", grad_norm),
                       ("grad_norm_clipped", grad_norm_clipped),
                       ("update_ratio", update_ratio), ("lr", lr),
                       ("loss_scale", loss_scale),
                       ("amp_skipped", amp_skipped),
                       ("skipped", skipped), ("retries", retries),
                       ("data_wait_s", data_wait_s),
                       ("compute_s", compute_s), ("fetch_s", fetch_s),
                       ("step_s", step_s)):
            if v is not None:
                rec[key] = v
        rec.update(extra)
        with self._lock:
            self.records.append(rec)
            self.total += 1
            self._last_step = rec["step"]
            if self.jsonl_path:
                self._pending.append(rec)
                if len(self._pending) >= self._flush_every:
                    self._flush_locked()
        # resolve the telemetry mode ONCE per step: the env lookup is
        # measurable at per-step hook rates
        if _t.mode() != _t.OFF:
            hub = _t._hub
            hub.inc("runhealth.steps")
            if loss is not None and _finite(loss):
                hub.set_gauge("runhealth.loss", float(loss))
            if grad_norm is not None and _finite(grad_norm):
                hub.set_gauge("runhealth.grad_norm", float(grad_norm))
            if loss_scale is not None and _finite(loss_scale):
                hub.set_gauge("runhealth.loss_scale", float(loss_scale))
            if step_s is not None:
                hub.set_gauge("runhealth.step_seconds", float(step_s))
        self._detect(rec)
        return rec

    def _fire(self, kind, step, **fields):
        self.anomalies[kind] += 1
        self._last_anomaly_step[kind] = step
        _event(kind, step=step, **fields)

    def _detect(self, rec):
        step = rec["step"]
        loss = rec.get("loss")
        if loss is not None:
            if not _finite(loss):
                self._fire("nonfinite_loss", step)
            else:
                loss = float(loss)
                n = len(self._losses)
                if n >= self.min_samples:
                    mean = self._loss_sum / n
                    var = max(0.0, self._loss_sumsq / n - mean * mean)
                    # std floor: a perfectly flat window must not turn
                    # numeric dust into an infinite z-score
                    std = max(math.sqrt(var), 1e-3 * abs(mean), 1e-12)
                    z = (loss - mean) / std
                    if z > self.spike_z:
                        self._fire("loss_spike", step,
                                   z=round(z, 2), loss=loss,
                                   window_mean=round(mean, 6))
                if n == self._losses.maxlen:
                    old = self._losses[0]
                    self._loss_sum -= old
                    self._loss_sumsq -= old * old
                self._losses.append(loss)
                self._loss_sum += loss
                self._loss_sumsq += loss * loss
                self._plateau_hist.append(loss)
                if (len(self._plateau_hist) >= self._plateau_hist.maxlen
                        and step - self._last_plateau_check
                        >= self.plateau_window):
                    self._last_plateau_check = step
                    hist = list(self._plateau_hist)
                    q = max(1, len(hist) // 4)
                    first = _median(hist[:q])
                    lastm = _median(hist[-q:])
                    denom = max(abs(first), 1e-12)
                    if (first - lastm) / denom < self.plateau_rel:
                        self._fire("plateau", step,
                                   first=round(first, 6),
                                   last=round(lastm, 6))
        gn = rec.get("grad_norm")
        if gn is not None:
            if _finite(gn):
                gn = float(gn)
                if len(self._grad_norms) >= self.min_samples:
                    med = _median(self._grad_norms)
                    if med and gn > self.explode_factor * med:
                        self._fire("grad_explosion", step,
                                   grad_norm=gn,
                                   window_median=round(med, 6))
                self._grad_norms.append(gn)
            else:
                self._fire("grad_explosion", step, grad_norm="nonfinite")
        st = rec.get("step_s")
        if st is not None and _finite(st):
            st = float(st)
            if len(self._step_times) >= self.min_samples:
                med = _median(self._step_times)
                if med and st > self.sag_factor * med:
                    self._fire("throughput_sag", step,
                               step_s=round(st, 6),
                               window_median_s=round(med, 6))
            self._step_times.append(st)

    # -- the autopilot signal -------------------------------------------
    def diverging(self, recent=4):
        """The divergence signal: a dict naming the anomaly when a
        ``nonfinite_loss`` / ``loss_spike`` / ``grad_explosion`` fired
        within the last ``recent`` recorded steps, else None. The
        autopilot TRAIN leg confirms this over ActionGate hysteresis
        before touching the run."""
        last = self._last_step
        if last is None:
            return None
        for kind in ("nonfinite_loss", "loss_spike", "grad_explosion"):
            at = self._last_anomaly_step.get(kind)
            if at is not None and last - at < int(recent):
                return {"kind": kind, "step": at, "last_step": last}
        return None

    def reset_anomalies(self):
        """Forget detector history (after a rollback: the restored
        trajectory must re-baseline, not re-trip on pre-rollback
        ghosts). The ring and counters stay — they are the record."""
        self._last_anomaly_step.clear()
        self._losses.clear()
        self._loss_sum = 0.0
        self._loss_sumsq = 0.0
        self._grad_norms.clear()
        self._step_times.clear()
        self._plateau_hist.clear()

    # -- reads -----------------------------------------------------------
    def tail(self, n=None):
        with self._lock:
            recs = list(self.records)
        return recs if n is None else recs[-int(n):]

    def __len__(self):
        with self._lock:
            return len(self.records)

    def last(self):
        with self._lock:
            return self.records[-1] if self.records else None

    def snapshot(self):
        """Aggregate view (JSON-safe): counts, loss trajectory, mean
        step time + phase split, anomaly counters."""
        recs = self.tail()
        losses = [float(r["loss"]) for r in recs
                  if r.get("loss") is not None and _finite(r["loss"])]
        steps_s = [float(r["step_s"]) for r in recs
                   if r.get("step_s") is not None]

        def _mean(key):
            vs = [float(r[key]) for r in recs if r.get(key) is not None]
            return sum(vs) / len(vs) if vs else None

        return {
            "steps": self.total,
            "ring": len(recs),
            "first_step": recs[0]["step"] if recs else None,
            "last_step": recs[-1]["step"] if recs else None,
            "loss_first": losses[0] if losses else None,
            "loss_last": losses[-1] if losses else None,
            "loss_min": min(losses) if losses else None,
            "mean_step_s": (sum(steps_s) / len(steps_s)
                            if steps_s else None),
            "mean_data_wait_s": _mean("data_wait_s"),
            "mean_compute_s": _mean("compute_s"),
            "mean_fetch_s": _mean("fetch_s"),
            "skipped": sum(1 for r in recs if r.get("skipped")),
            "retries": sum(int(r.get("retries") or 0) for r in recs),
            "anomalies": dict(self.anomalies),
        }

    # -- JSONL persistence ----------------------------------------------
    def _flush_locked(self):
        if not self._pending or not self.jsonl_path:
            return
        lines = []
        for rec in self._pending:
            try:
                lines.append(json.dumps(rec))
            except (TypeError, ValueError):
                continue
        self._pending = []
        try:
            if not self._jsonl_dir_ok:
                d = os.path.dirname(os.path.abspath(self.jsonl_path))
                os.makedirs(d, exist_ok=True)
                self._jsonl_dir_ok = True
            with open(self.jsonl_path, "a", encoding="utf-8") as f:
                f.write("".join(line + "\n" for line in lines))
        except OSError:
            _inc("runhealth.jsonl_errors")

    def flush(self):
        """Drain buffered records to the JSONL sidecar (appends are
        batched every ``flush_every`` records so the per-step hook
        stays off the disk)."""
        with self._lock:
            self._flush_locked()

    def dump_jsonl(self, path):
        """Write the whole ring as JSONL (one record per line);
        returns the path."""
        recs = self.tail()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = "%s.tmp-%d" % (path, os.getpid())
        with open(tmp, "w", encoding="utf-8") as f:
            for rec in recs:
                f.write(json.dumps(rec))
                f.write("\n")
        os.replace(tmp, path)
        return path

    @staticmethod
    def load(path):
        """Read step records back from a JSONL file through the
        tolerant reader -> ``(records, dropped)``. A torn final line
        (the writer crashed mid-append) is skipped and counted, never
        raised."""
        from ..integrity import jsonl as _jsonl

        records, dropped = _jsonl.read_jsonl(path)
        if dropped:
            _inc("integrity.jsonl_dropped", dropped)
        records = [r for r in records
                   if isinstance(r, dict) and "step" in r]
        return records, dropped


# ---------------------------------------------------------------------------
# goodput accounting
# ---------------------------------------------------------------------------


class GoodputAccount:
    """Wall-clock decomposition of a training run.

    ``start()`` opens the accounting window; the instrumented layers
    attribute seconds into the buckets (:data:`GOODPUT_BUCKETS`) as
    they spend them; :meth:`snapshot` reports the decomposition, the
    residual the instrumentation could not attribute (loop overhead,
    event emission — the 5%-of-wall-clock budget the runhealth lane
    enforces), and ``goodput_fraction`` = productive seconds / wall.

    :meth:`step` is the attribution primitive: a context manager that
    measures one optimizer step and books its elapsed time as
    ``productive_step`` MINUS whatever overhead buckets were fed
    during the window (a compile or retry-backoff inside ``run()``
    must not be double-counted as productive compute).
    """

    _OVERHEAD_IN_STEP = ("compile", "retry_backoff", "data_stall")

    def __init__(self, clock=time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self.buckets = {b: 0.0 for b in GOODPUT_BUCKETS}
        self.rework_steps = 0
        self._t0 = None
        self._elapsed = 0.0          # closed windows (stop() latches)

    # -- the window ------------------------------------------------------
    def start(self):
        if self._t0 is None:
            self._t0 = self._clock()
        return self

    def stop(self):
        if self._t0 is not None:
            self._elapsed += self._clock() - self._t0
            self._t0 = None
        _gauge("runhealth.goodput_fraction", self.goodput_fraction())
        return self

    def wall(self):
        """Seconds of accounted wall-clock so far."""
        live = 0.0 if self._t0 is None else self._clock() - self._t0
        return self._elapsed + live

    # -- attribution -----------------------------------------------------
    def add(self, bucket, seconds, steps=None):
        if bucket not in self.buckets:
            raise ValueError("unknown goodput bucket %r (want one of %s)"
                             % (bucket, ", ".join(GOODPUT_BUCKETS)))
        with self._lock:
            self.buckets[bucket] += max(0.0, float(seconds))
            if bucket == "restart_rework" and steps:
                self.rework_steps += int(steps)

    def step(self):
        """Context manager booking one optimizer step as productive
        time net of in-step overhead attributions."""
        return _StepWindow(self)

    def _overhead_total(self):
        with self._lock:
            return sum(self.buckets[b] for b in self._OVERHEAD_IN_STEP)

    # -- reads -----------------------------------------------------------
    def total(self, bucket):
        with self._lock:
            return self.buckets[bucket]

    def goodput_fraction(self):
        """Productive-step seconds / accounted wall-clock (0.0 before
        any time has passed)."""
        w = self.wall()
        if w <= 0.0:
            return 0.0
        with self._lock:
            return min(1.0, self.buckets["productive_step"] / w)

    def snapshot(self):
        w = self.wall()
        with self._lock:
            buckets = {b: round(v, 6) for b, v in self.buckets.items()}
            rework_steps = self.rework_steps
        accounted = sum(buckets.values())
        return {
            "wall_s": round(w, 6),
            "buckets": buckets,
            "accounted_s": round(accounted, 6),
            "unaccounted_s": round(max(0.0, w - accounted), 6),
            "rework_steps": rework_steps,
            "goodput_fraction": round(self.goodput_fraction(), 6),
        }


class _StepWindow:
    def __init__(self, acct):
        self._acct = acct
        self._t0 = None
        self._over0 = 0.0

    def __enter__(self):
        self._t0 = self._acct._clock()
        self._over0 = self._acct._overhead_total()
        return self

    def __exit__(self, exc_type, exc, tb):
        dt = self._acct._clock() - self._t0
        overhead = self._acct._overhead_total() - self._over0
        if exc_type is None:
            self._acct.add("productive_step", max(0.0, dt - overhead))
        # a step that raised was not productive; its backoff/compile
        # attributions already landed in their own buckets
        return False


# ---------------------------------------------------------------------------
# the bundle + process-wide hooks
# ---------------------------------------------------------------------------


class RunHealth:
    """StepSeries + GoodputAccount, bundled for ``TrainGuard``.

    ``extra_fetches`` maps record-field names to graph Variables the
    TrainGuard should fetch each step and feed into the record — the
    hook for grad global-norms (pre/post clip), the param/update-norm
    ratio, or a schedule's lr Variable, which live in the graph and
    are only host-visible when fetched::

        rh = RunHealth(extra_fetches={"grad_norm": gnorm_var,
                                      "lr": lr_var})
        TrainGuard(exe, ..., runhealth=rh).train(1000)
    """

    def __init__(self, series=None, goodput=None, extra_fetches=None,
                 jsonl_path=None, **series_opts):
        if series is None:
            series = StepSeries(jsonl_path=jsonl_path, **series_opts)
        self.series = series
        self.goodput = goodput if goodput is not None else GoodputAccount()
        self.extra_fetches = dict(extra_fetches or {})

    def diverging(self, recent=4):
        return self.series.diverging(recent=recent)

    def snapshot(self):
        return {"series": self.series.snapshot(),
                "goodput": self.goodput.snapshot()}

    def dump(self, path):
        """Write the snapshot as one JSON doc (the ``run`` CLI and the
        A/B comparison read it back); returns the path."""
        doc = self.snapshot()
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        tmp = "%s.tmp-%d" % (path, os.getpid())
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(doc, f)
        os.replace(tmp, path)
        return path


_active = None           # RunHealth a TrainGuard activated
_active_goodput = None   # bare GoodputAccount (bench loops)


def activate(rh):
    """Make ``rh`` the process-active RunHealth: executor/pipeline/
    guard hooks feed its goodput account, and crash dumps carry its
    series tail. Returns the previous active bundle (restore it in a
    finally)."""
    global _active, _active_goodput
    prev = _active
    _active = rh
    _active_goodput = rh.goodput if rh is not None else None
    return prev


def deactivate(prev=None):
    global _active, _active_goodput
    _active = prev
    _active_goodput = prev.goodput if prev is not None else None


def active():
    return _active


def set_active_goodput(acct):
    """Goodput-only activation (bench loops that want the account
    without a step series). Returns the previous account."""
    global _active_goodput
    prev = _active_goodput
    _active_goodput = acct
    return prev


def active_goodput():
    return _active_goodput


def goodput_note(bucket, seconds, steps=None):
    """Attribute seconds into the active goodput account; inert (one
    global read) when none is active — safe on every hot path."""
    acct = _active_goodput
    if acct is not None:
        acct.add(bucket, seconds, steps=steps)


_exec_phases = None  # last Executor.run phase split (consumer thread)


def note_exec_phases(feed_convert_s=None, compute_s=None, fetch_s=None):
    """Executor.run's per-step phase split, parked for the step
    recorder (TrainGuard pops it right after the guarded run returns —
    both run on the driving thread, so a one-slot handoff is exact)."""
    global _exec_phases
    if _active is not None:
        _exec_phases = {"feed_convert_s": feed_convert_s,
                        "compute_s": compute_s, "fetch_s": fetch_s}


def take_exec_phases():
    global _exec_phases
    p, _exec_phases = _exec_phases, None
    return p


def crash_snapshot(tail=32):
    """What the flight recorder embeds in a crash dump: the active
    run's last-N step records + goodput decomposition (convergence
    state at death), or None when nothing is active."""
    if _active is not None:
        return {"series_tail": _active.series.tail(tail),
                "series": _active.series.snapshot(),
                "goodput": _active.goodput.snapshot()}
    if _active_goodput is not None:
        return {"goodput": _active_goodput.snapshot()}
    return None


def reset():
    """Drop the active bundle/account (obs.reset() test scoping)."""
    global _active, _active_goodput, _exec_phases
    _active = None
    _active_goodput = None
    _exec_phases = None


# ---------------------------------------------------------------------------
# report loading + rendering (the `run` CLI)
# ---------------------------------------------------------------------------


def _series_from_records(records):
    """A StepSeries snapshot recomputed from loaded JSONL records (the
    ring is gone; the lines are the record)."""
    s = StepSeries(maxlen=max(1, len(records)) + 1)
    for rec in sorted(records, key=lambda r: r.get("step", 0)):
        fields = {k: v for k, v in rec.items()
                  if k not in ("step", "wall")}
        s.record(rec.get("step", 0), **fields)
    return s.snapshot()


def _run_of_doc(doc):
    """Normalize one loaded JSON doc into a run dict
    ``{"series":..., "goodput":...}`` or None when not run-shaped.
    Accepts a ``RunHealth.snapshot()``/``dump()`` doc, bare or under
    a ``"runhealth"`` key (a crash dump)."""
    if not isinstance(doc, dict):
        return None
    if isinstance(doc.get("runhealth"), dict):
        doc = doc["runhealth"]
    if not isinstance(doc, dict):
        return None
    if "series" in doc or "goodput" in doc:
        out = {"series": doc.get("series"), "goodput": doc.get("goodput")}
        if isinstance(doc.get("series_tail"), list):
            out["series"] = out["series"] or _series_from_records(
                doc["series_tail"])
        return out
    return None


def load_run(path):
    """Load a run-health doc from `path`: a snapshot JSON
    (``RunHealth.dump()`` or a crash dump), a StepSeries JSONL, or a
    directory scanned for both (first run-shaped ``*.json`` wins; every
    ``*.jsonl`` merges into the series). Returns ``{"path", "series", "goodput"}`` — either
    side may be None when that evidence wasn't found."""
    run = {"path": str(path), "series": None, "goodput": None}
    if os.path.isdir(path):
        names = sorted(os.listdir(path))
        json_paths = [os.path.join(path, n) for n in names
                      if n.endswith(".json")]
        jsonl_paths = [os.path.join(path, n) for n in names
                       if n.endswith(".jsonl")]
    elif str(path).endswith(".jsonl"):
        json_paths, jsonl_paths = [], [path]
    else:
        json_paths, jsonl_paths = [path], []
    for p in json_paths:
        try:
            with open(p, "r", encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        got = _run_of_doc(doc)
        if got is not None:
            run["series"] = run["series"] or got.get("series")
            run["goodput"] = run["goodput"] or got.get("goodput")
    if run["series"] is None and jsonl_paths:
        records = []
        for p in jsonl_paths:
            recs, _dropped = StepSeries.load(p)
            records.extend(recs)
        if records:
            run["series"] = _series_from_records(records)
    return run


_HEALTH_ROWS = (
    # (label, section, key, format)
    ("steps", "series", "steps", "%d"),
    ("last step", "series", "last_step", "%d"),
    ("loss first", "series", "loss_first", "%.4f"),
    ("loss last", "series", "loss_last", "%.4f"),
    ("loss min", "series", "loss_min", "%.4f"),
    ("mean step ms", "series", "mean_step_s", "%.2f"),
    ("mean data-wait ms", "series", "mean_data_wait_s", "%.2f"),
    ("mean compute ms", "series", "mean_compute_s", "%.2f"),
    ("mean fetch ms", "series", "mean_fetch_s", "%.2f"),
    ("skipped steps", "series", "skipped", "%d"),
    ("retries", "series", "retries", "%d"),
    ("wall s", "goodput", "wall_s", "%.3f"),
    ("goodput fraction", "goodput", "goodput_fraction", "%.3f"),
)

_MS_KEYS = frozenset({"mean_step_s", "mean_data_wait_s",
                      "mean_compute_s", "mean_fetch_s"})


def _row_value(run, section, key):
    doc = run.get(section) or {}
    v = doc.get(key)
    if v is None:
        return None
    if key in _MS_KEYS:
        return 1e3 * float(v)
    return v


def health_rows(run):
    """Flatten a loaded run into ``(label, value, fmt)`` rows: the
    headline metrics, the goodput bucket decomposition, and the
    anomaly counters."""
    rows = [(label, _row_value(run, section, key), fmt)
            for label, section, key, fmt in _HEALTH_ROWS]
    gp = run.get("goodput") or {}
    buckets = gp.get("buckets") or {}
    wall = gp.get("wall_s") or 0.0
    for b in GOODPUT_BUCKETS:
        v = buckets.get(b)
        if v is None:
            continue
        pct = (" (%.1f%%)" % (100.0 * v / wall)) if wall else ""
        rows.append(("  %s s" % b.replace("_", "-"),
                     "%.3f%s" % (v, pct), "%s"))
    if gp.get("unaccounted_s") is not None and wall:
        rows.append(("  unaccounted s",
                     "%.3f (%.1f%%)" % (gp["unaccounted_s"],
                                        100.0 * gp["unaccounted_s"] / wall),
                     "%s"))
    anomalies = (run.get("series") or {}).get("anomalies") or {}
    for kind in ANOMALY_KINDS:
        n = anomalies.get(kind)
        if n:
            rows.append(("anomaly %s" % kind, n, "%d"))
    return rows


def render_health_report(run, title=None):
    """The run-health report text block for one loaded run."""
    out = ["run health: %s" % (title or run.get("path") or "-")]
    width = max(len(label) for label, _, _, _ in _HEALTH_ROWS) + 4
    for label, v, fmt in health_rows(run):
        out.append("  %s %s" % (label.ljust(width),
                                "-" if v is None else fmt % v))
    return "\n".join(out)


def compare_rows(run_a, run_b):
    """A/B comparison rows ``(label, a, b, delta_pct)`` over the
    numeric health metrics + goodput buckets of two loaded runs."""
    rows = []

    def _num(run, section, key):
        v = _row_value(run, section, key)
        try:
            return None if v is None else float(v)
        except (TypeError, ValueError):
            return None

    for label, section, key, fmt in _HEALTH_ROWS:
        a = _num(run_a, section, key)
        b = _num(run_b, section, key)
        if a is None and b is None:
            continue
        delta = (100.0 * (b - a) / a) if (a and b is not None) else None
        rows.append((label, a, b, delta, fmt))
    ga = (run_a.get("goodput") or {}).get("buckets") or {}
    gb = (run_b.get("goodput") or {}).get("buckets") or {}
    for bucket in GOODPUT_BUCKETS:
        a, b = ga.get(bucket), gb.get(bucket)
        if a is None and b is None:
            continue
        delta = (100.0 * (b - a) / a) if (a and b is not None) else None
        rows.append(("%s s" % bucket.replace("_", "-"), a, b, delta,
                     "%.3f"))
    return rows


def render_comparison(run_a, run_b, label_a="A", label_b="B"):
    """Aligned A-vs-B table (same renderer family as the PR-15 drift
    table: fixed columns, ``-`` for unknown cells)."""
    headers = ["metric", label_a, label_b, "delta%"]
    cells = []
    for label, a, b, delta, fmt in compare_rows(run_a, run_b):
        cells.append([
            label,
            "-" if a is None else fmt % a,
            "-" if b is None else fmt % b,
            "-" if delta is None else "%+.1f" % delta,
        ])
    widths = [max(len(h), *(len(row[i]) for row in cells))
              if cells else len(h) for i, h in enumerate(headers)]
    out = ["  ".join(h.ljust(widths[i]) for i, h in enumerate(headers))]
    out.append("  ".join("-" * w for w in widths))
    for row in cells:
        out.append("  ".join(row[i].ljust(widths[i])
                             for i in range(len(widths))))
    return "\n".join(out)
