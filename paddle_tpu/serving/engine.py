"""ServingEngine: dynamic micro-batching over an AOT-compiled Predictor.

One bounded request queue + one dispatch thread per model. Concurrent
``submit()`` calls enqueue requests; the dispatch thread coalesces them
into micro-batches (flushing on ``max_batch_size`` rows or
``max_wait_ms``, whichever comes first), pads each same-tail-shape
group up to a declared :class:`~paddle_tpu.serving.batcher.BucketSpec`
batch size, runs ONE pre-warmed AOT executable per bucket, and slices
per-request rows back into each caller's future. ``warmup()`` compiles
every declared (bucket, batch size) through the predictor's
compile-cache disk tier, so a restarted server deserializes the AOT
artifacts instead of paying XLA again (zero ``compile_start`` events on
a warm start).

Admission control (the resilience posture of PR 1, applied to serving):

- **load shedding** — a full queue fast-rejects at ``submit()`` with
  :class:`ShedError` (HTTP 429 upstream) instead of building unbounded
  latency;
- **deadlines** — a request whose ``deadline_ms`` expires while queued
  is dropped at dispatch with :class:`DeadlineExceededError` (504)
  rather than burning chip time on an answer nobody is waiting for;
- **graceful drain** — ``stop(drain=True)`` rejects new work, finishes
  everything queued, then parks the dispatch thread.

Telemetry: ``serving.queue_wait_seconds`` / ``serving.batch_size`` /
``serving.batch_rows`` / ``serving.padding_waste`` /
``serving.request_seconds`` histograms, ``serving.shed`` and
``serving.deadline_miss`` counters (every reject also lands in the
flight recorder), and a ``serving.queue_depth.<model>`` gauge.
"""
import collections
import queue
import threading
import time
from concurrent.futures import Future

from .. import observability as obs
from ..analysis import concurrency as _conc
from .batcher import assemble, round_up_pow2, tail_signature

__all__ = [
    "DeadlineExceededError", "EngineClosedError", "ServingEngine",
    "ShedError",
]


class ShedError(RuntimeError):
    """Fast-reject: the bounded request queue is full (load shedding).

    Carries enough context for the HTTP frontend to answer usefully:
    ``model`` / ``replica`` identify who shed, ``retry_after`` is the
    engine's drain-rate-derived backoff hint in seconds (the 429
    ``Retry-After`` header upstream)."""

    def __init__(self, message="", model=None, replica=None,
                 retry_after=None):
        super().__init__(message)
        self.model = model
        self.replica = replica
        self.retry_after = retry_after


class DeadlineExceededError(RuntimeError):
    """The request's deadline expired while it waited in the queue."""


class EngineClosedError(RuntimeError):
    """The engine is stopped or draining; no new work is admitted."""


class _Request:
    __slots__ = ("feeds", "rows", "sig", "deadline", "future", "t_enqueue")


class ServingEngine:
    """Micro-batching dispatch loop around one Predictor (one model
    version — :class:`~paddle_tpu.serving.registry.ModelRegistry` swaps
    whole engines for hot reload)."""

    def __init__(self, predictor, buckets=(), max_batch_size=8,
                 max_wait_ms=2.0, queue_capacity=64,
                 default_deadline_ms=None, request_timeout_s=60.0,
                 name="default", replica_id=None, auto_start=True):
        self._predictor = predictor
        self.name = str(name)
        # attribute this engine's executables in the ledger/perf CLI
        try:
            predictor.ledger_tag = "serving:%s" % self.name
            if getattr(predictor, "name", "") is None:
                predictor.name = "predict"  # module jit_fwd_predict
        except Exception:  # noqa: BLE001 — duck-typed predictors in tests
            pass
        self.replica_id = replica_id
        self._max_batch_size = int(max_batch_size)
        self._max_wait_s = float(max_wait_ms) / 1000.0
        self._default_deadline_ms = default_deadline_ms
        self.request_timeout_s = float(request_timeout_s)
        self._q = queue.Queue(maxsize=int(queue_capacity))
        self._bucket_specs = tuple(buckets)
        self._buckets = {
            spec.signature(): spec.batch_sizes for spec in self._bucket_specs
        }
        self._stop_event = threading.Event()
        self._closed = False
        # admission vs stop() is a race without this lock: a submitter
        # that passed the closed check could land its queue.put AFTER a
        # drain finished, silently stranding the request. Admission
        # (closed check + put) and the stop-side closed flip are both
        # atomic under _admit_lock, so every request either reaches the
        # queue before the drain starts or gets EngineClosedError.
        self._admit_lock = _conc.named_lock("serving.engine.admit")
        self._thread = None
        self._stats_lock = _conc.named_lock("serving.engine.stats")
        self._owner = _conc.owner_token("serving-engine", self.name, self)
        self._stats = collections.Counter()
        # (t_done, n_requests) per dispatched group — the drain-rate
        # window behind retry_after_hint()
        self._rate = collections.deque(maxlen=64)
        if auto_start:
            self.start()

    # -- lifecycle -------------------------------------------------------
    def start(self):
        """Start the dispatch thread (idempotent)."""
        if self._closed:
            raise EngineClosedError("engine %r is closed" % self.name)
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="serving-dispatch-%s" % self.name)
            _conc.track_thread(self._thread, self._owner)
            self._thread.start()
        return self

    def stop(self, drain=True, timeout=30.0):
        """Stop admitting work; with ``drain=True`` finish everything
        already queued first, else fail queued requests with
        :class:`EngineClosedError`. Idempotent."""
        with self._admit_lock:
            self._closed = True
        alive = self._thread is not None and self._thread.is_alive()
        if drain and alive:
            t_end = time.monotonic() + float(timeout)
            while not self._q.empty() and time.monotonic() < t_end:
                if _conc._on:
                    _conc.note_blocking("time.sleep(drain)")
                time.sleep(0.005)
        self._stop_event.set()
        if alive:
            self._thread.join(timeout=max(0.1, float(timeout)))
        # anything still queued (no thread, or a non-drain stop that
        # beat the loop to them) fails loudly rather than hanging
        while True:
            try:
                r = self._q.get_nowait()
            except queue.Empty:
                break
            r.future.set_exception(EngineClosedError(
                "engine %r stopped before dispatch" % self.name))
        # the dispatch thread must be gone now — a survivor is a leak
        # (recorded as a violation when the lock sanitizer is armed).
        # Grace outlasts an in-flight jit compile on short-join stops;
        # the poll returns the instant the thread exits.
        _conc.check_stopped(self._owner, grace=10.0)
        obs.event("engine_stop", source="serving", count=False,
                  model=self.name, drained=bool(drain))

    # -- admission -------------------------------------------------------
    def submit(self, feeds, deadline_ms=None, trace_ctx=None):
        """Enqueue one request; returns a ``concurrent.futures.Future``
        resolving to the per-request fetch list (rows sliced back out of
        the coalesced batch). Raises :class:`ShedError` immediately when
        the queue is full and :class:`EngineClosedError` after
        ``stop()``. A sampled ``trace_ctx`` exports one
        ``serving.predict`` span (queue wait + batch compute) when the
        request resolves."""
        if self._closed:  # cheap early reject; re-checked under the lock
            raise EngineClosedError(
                "engine %r is draining/stopped" % self.name)
        prepared, _ = self._predictor._prepare(feeds)
        if not prepared:
            raise ValueError("empty request: no feeds")
        rows = int(next(iter(prepared.values())).shape[0])
        for n, v in prepared.items():
            if int(v.shape[0]) != rows:
                raise ValueError(
                    "feed %r has %d rows but %r has %d — all feeds must "
                    "share the leading batch dim"
                    % (n, v.shape[0], self._predictor.feed_names[0], rows))
        if rows < 1:
            raise ValueError("empty request: 0 rows")
        req = _Request()
        req.feeds = prepared
        req.rows = rows
        req.sig = tail_signature(prepared)
        if deadline_ms is None:
            deadline_ms = self._default_deadline_ms
        req.deadline = (
            time.monotonic() + float(deadline_ms) / 1000.0
            if deadline_ms is not None else None)
        req.future = Future()
        req.t_enqueue = time.monotonic()
        try:
            with self._admit_lock:
                if self._closed:
                    raise EngineClosedError(
                        "engine %r is draining/stopped" % self.name)
                self._q.put_nowait(req)
        except queue.Full:
            self._bump("shed")
            obs.event("shed", source="serving", model=self.name, rows=rows,
                      queue_capacity=self._q.maxsize)
            raise ShedError(
                "serving queue full (%d) for model %r%s — request shed"
                % (self._q.maxsize, self.name,
                   "" if self.replica_id is None
                   else " (replica %s)" % self.replica_id),
                model=self.name, replica=self.replica_id,
                retry_after=self.retry_after_hint())
        self._bump("requests")
        obs.set_gauge("serving.queue_depth.%s" % self.name, self._q.qsize())
        if trace_ctx is not None and getattr(trace_ctx, "sampled", False):
            ctx = trace_ctx.child()
            t_wall = time.time()
            req.future.add_done_callback(
                lambda f, c=ctx, t=t_wall: obs.export_span(
                    "serving.predict", c, t, time.time() - t,
                    {"proc": "engine:%s" % self.name, "rows": rows,
                     "error": (type(f.exception()).__name__
                               if f.exception() else None)}))
        return req.future

    def predict(self, feeds, deadline_ms=None, timeout=None):
        """Synchronous submit + wait: returns the fetch list for this
        request's rows."""
        fut = self.submit(feeds, deadline_ms=deadline_ms)
        return fut.result(
            timeout if timeout is not None else self.request_timeout_s)

    # -- warmup ----------------------------------------------------------
    def check_hbm_budget(self, budget_bytes=None):
        """Predict each bucket ladder's worst-bucket peak HBM with the
        static liveness analyzer and reject ladders that cannot fit.

        ``budget_bytes=None`` resolves the device capacity from the
        analyzer's device table (or ``PADDLE_TPU_HBM_BYTES``); when no
        capacity is known the check is a no-op. Raises
        :class:`~paddle_tpu.analysis.ProgramVerifyError` listing every
        over-budget ladder — BEFORE any warmup compile touches XLA."""
        from ..analysis import costs as _costs, memory as _memory
        from ..analysis.diagnostics import ProgramVerifyError
        from ..fluid.executor import _device_kind

        if budget_bytes is None:
            profile = _costs.device_profile(_device_kind())
            budget_bytes = profile.hbm_bytes if profile else None
        if not budget_bytes:
            return []
        pred = self._predictor
        results = []
        worst = 0
        for spec in self._bucket_specs:
            b = spec.max_batch_size
            est = _memory.estimate(
                pred.program, feed_specs=spec.feed_specs(b),
                state_specs=pred._state,
                fetch_names=pred.fetch_names,
                state_names=set(pred._state), default_dim=b)
            worst = max(worst, est.peak_bytes)
            results.append((spec, b, est))
        obs.set_gauge(
            "serving.predicted_peak_hbm.%s" % self.name, worst)
        over = [(spec, b, est) for spec, b, est in results
                if est.peak_bytes > budget_bytes]
        if not over:
            return results
        lines = [
            "bucket %s at batch %d: predicted peak %.2f MB "
            "(params %.2f MB + activations %.2f MB at op %s '%s')"
            % (spec.signature(), b, est.peak_bytes / 1e6,
               est.param_bytes / 1e6, est.act_bytes_at_peak / 1e6,
               est.peak_op_index, est.peak_op_type)
            for spec, b, est in over]
        obs.event("bucket_rejected", source="serving", model=self.name,
                  rejected=len(over), budget_bytes=int(budget_bytes))
        raise ProgramVerifyError(
            "predicted-oom: %d of %d bucket ladder(s) exceed the HBM "
            "budget (%.2f MB) — trim the worst batch sizes or shard the "
            "model:\n%s"
            % (len(over), len(results), budget_bytes / 1e6,
               "\n".join(lines)))

    def warmup(self, check_hbm=True):
        """Pre-build one executable per declared (bucket, batch size)
        through the predictor's compile-cache disk tier. On a restarted
        server every entry resolves from disk — ``source == "disk"``,
        zero ``compile_start`` events. Returns the per-entry report.

        ``check_hbm=True`` first runs :meth:`check_hbm_budget`: a
        ladder whose worst bucket cannot fit the device raises before
        any compile is attempted."""
        if check_hbm:
            self.check_hbm_budget()
        report = []
        for spec in self._bucket_specs:
            for b in spec.batch_sizes:
                source = self._predictor.warm(spec.feeds_for(b))
                report.append({
                    "signature": spec.signature(), "batch_size": b,
                    "source": source,
                })
        if report:
            obs.event(
                "warmup", source="serving", count=False, model=self.name,
                engines=len(report),
                compiled=sum(1 for r in report if r["source"] == "compile"),
                disk_warm=sum(1 for r in report if r["source"] == "disk"))
        return report

    # -- dispatch --------------------------------------------------------
    def _loop(self):
        carry = None  # request popped but not fitting the last batch
        while True:
            if carry is not None:
                first, carry = carry, None
            else:
                try:
                    if _conc._on:
                        _conc.note_blocking("queue.get")
                    first = self._q.get(timeout=0.05)
                except queue.Empty:
                    if self._stop_event.is_set():
                        return
                    continue
            batch = [first]
            rows = first.rows
            t_flush = time.monotonic() + self._max_wait_s
            while rows < self._max_batch_size:
                remaining = t_flush - time.monotonic()
                if remaining <= 0:
                    break
                try:
                    if _conc._on:
                        _conc.note_blocking("queue.get")
                    r = self._q.get(timeout=remaining)
                except queue.Empty:
                    break
                if rows + r.rows > self._max_batch_size:
                    # would overshoot the bucket ladder: starts the NEXT
                    # micro-batch instead of forcing an ad-hoc shape
                    carry = r
                    break
                batch.append(r)
                rows += r.rows
            obs.set_gauge(
                "serving.queue_depth.%s" % self.name, self._q.qsize())
            self._execute(batch)

    def _execute(self, batch):
        now = time.monotonic()
        live = []
        for r in batch:
            if r.deadline is not None and now > r.deadline:
                self._bump("deadline_miss")
                waited_ms = round(1000 * (now - r.t_enqueue), 3)
                obs.event("deadline_miss", source="serving",
                          model=self.name, rows=r.rows,
                          waited_ms=waited_ms)
                r.future.set_exception(DeadlineExceededError(
                    "deadline expired after %s ms in queue (model %r)"
                    % (waited_ms, self.name)))
            else:
                live.append(r)
        groups = collections.OrderedDict()
        for r in live:
            groups.setdefault(r.sig, []).append(r)
        for sig, reqs in groups.items():
            self._run_group(sig, reqs)

    def _bucket_rows(self, sig, rows):
        """The padded batch size for `rows` rows of tail-shape `sig`:
        the smallest declared bucket that fits, exact when the request
        outgrows every bucket, next-pow2 (capped at max_batch_size) for
        undeclared shapes."""
        declared = self._buckets.get(sig)
        if declared:
            for b in declared:
                if b >= rows:
                    return b
            return rows
        if rows >= self._max_batch_size:
            return rows
        return min(round_up_pow2(rows), self._max_batch_size)

    def _run_group(self, sig, reqs):
        t0 = time.monotonic()
        rows = sum(r.rows for r in reqs)
        target = self._bucket_rows(sig, rows)
        for r in reqs:
            obs.observe("serving.queue_wait_seconds", t0 - r.t_enqueue)
        try:
            feeds = assemble(self._predictor.feed_names, reqs, target)
            if _conc._on:
                _conc.note_blocking("device.dispatch")
            outs = self._predictor.run(feeds, return_numpy=True)
            for o in outs:
                if getattr(o, "ndim", 0) < 1 or o.shape[0] != target:
                    raise ValueError(
                        "fetch output shape %s is not row-aligned with "
                        "the %d-row batch — ServingEngine needs per-row "
                        "outputs to slice results back to requests"
                        % (getattr(o, "shape", None), target))
        except Exception as e:  # noqa: BLE001 — fail the requests, not the loop
            self._bump("batch_errors")
            obs.event("batch_error", source="serving", model=self.name,
                      rows=rows, error="%s: %s"
                      % (type(e).__name__, str(e)[:200]))
            for r in reqs:
                r.future.set_exception(e)
            with self._stats_lock:  # errors still drain the queue
                self._rate.append((time.monotonic(), len(reqs)))
            return
        self._bump("batches")
        if len(reqs) > 1:
            self._bump("coalesced")
        self._bump("rows", rows)
        obs.observe("serving.batch_size", len(reqs))
        obs.observe("serving.batch_rows", rows)
        obs.observe("serving.padding_waste", (target - rows) / float(target))
        done = time.monotonic()
        with self._stats_lock:
            self._rate.append((done, len(reqs)))
        off = 0
        for r in reqs:
            # copy the slices: a view would pin the whole padded batch
            # (and every other request's rows) in memory for as long as
            # the caller holds its result
            r.future.set_result(
                [o[off:off + r.rows].copy() for o in outs])
            off += r.rows
            obs.observe("serving.request_seconds", done - r.t_enqueue)

    # -- introspection ---------------------------------------------------
    def _bump(self, key, n=1):
        with self._stats_lock:
            self._stats[key] += n

    def stats(self):
        """Local lifetime counters (independent of the telemetry mode):
        requests/shed/deadline_miss/batches/coalesced/rows/batch_errors."""
        with self._stats_lock:
            out = dict(self._stats)
        for k in ("requests", "shed", "deadline_miss", "batches",
                  "coalesced", "rows", "batch_errors"):
            out.setdefault(k, 0)
        return out

    def queue_depth(self):
        return self._q.qsize()

    def drain_rate(self):
        """Requests/sec the dispatch loop completed over its recent
        window (None until the first batch lands, or after 30s idle)."""
        now = time.monotonic()
        with self._stats_lock:
            pts = [(t, n) for t, n in self._rate if now - t < 30.0]
        if not pts:
            return None
        span = max(1e-3, now - min(t for t, _ in pts))
        return sum(n for _, n in pts) / span

    def retry_after_hint(self):
        """Seconds until the current queue likely drains at the
        observed rate — what a shed client should wait before retrying
        (the HTTP 429 ``Retry-After``). Clamped to [1, 60]."""
        rate = self.drain_rate()
        if not rate:
            return 1.0
        return min(60.0, max(1.0, (self.queue_depth() + 1) / rate))

    @property
    def predictor(self):
        return self._predictor

    @property
    def closed(self):
        return self._closed
