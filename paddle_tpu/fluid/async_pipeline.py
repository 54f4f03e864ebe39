"""Pipelined dispatch: overlap host-side feed staging with device compute.

The synchronous step loop serializes three phases that have no data
dependency across adjacent steps: feed conversion + ``device_put`` for
batch N+1 could run while the device computes batch N, and the numpy
fetch for batch N-1 could wait lazily instead of blocking the dispatch
of N. :class:`PipelinedRunner` (surfaced as ``Executor.run_pipelined``)
rebuilds the loop that way:

- a **stager thread** pulls feed dicts from the caller's iterable (or
  from the program's started py_reader) and runs
  ``Executor._prepare_feeds`` — dtype coercion + batched host→device
  transfer — into a bounded queue (``depth``, default 2: classic double
  buffering);
- the **consumer loop** (the generator you iterate) pops staged
  device-resident batches and dispatches ``Executor.run(...,
  return_numpy=False)``, which returns lazy jax handles without a host
  round-trip;
- a bounded **in-flight window** (default ``depth``) caps how many
  dispatched-but-unmaterialized steps exist at once — each in-flight
  step pins one generation of donated state buffers, so the window is
  what keeps ``donate_argnums`` memory bounded — blocking on the oldest
  step's results before dispatching further ahead.

Step semantics are bit-identical to the sync loop: batches are
dispatched in order on one thread, so the executor's PRNG counter
advances exactly as it would have, and the staged arrays are the same
``_prepare_feeds`` output the sync path would compute.

Telemetry: staging runs under ``executor.stage_feed`` spans (on the
stager thread) and the dispatch under the usual ``executor.run`` spans,
so a trace-mode flight recording shows the overlap directly; the
``executor.overlap_ratio`` gauge summarizes it (fraction of staging
seconds that ran while at least one step was in flight).

Invalidation contract: ``close()`` (also called when the generator is
exhausted, errors, or is dropped) stops the stager and discards staged
device batches — resilience-layer retries/warm-starts must not consume
stale staging (TrainGuard restarts readers, which bumps the reader
generation and drops reader-level staging the same way).
"""
import collections
import queue as _queue_mod
import threading
import time

import numpy as np

from . import core
from .. import observability as obs
from ..observability import runhealth as _runhealth
from ..analysis import concurrency as _conc

__all__ = ["PipelinedRunner"]

# classic double buffering: stage batch N+1 while the device computes N
DEFAULT_DEPTH = 2

_END = object()


class PipelinedRunner:
    """Iterate per-step fetch lists with feed staging pipelined against
    device compute. Single-use: iterate it once.

    ``feeds`` is an iterable of feed dicts; ``None`` pulls from the
    program's started py_reader(s) until EOF (the run then ends
    normally instead of raising ``core.EOFException``).
    """

    def __init__(self, executor, program=None, feeds=None, fetch_list=None,
                 scope=None, return_numpy=True, depth=None, window=None):
        from .framework import default_main_program

        self._exe = executor
        self._program = program if program is not None \
            else default_main_program()
        self._feeds = feeds
        self._fetch_list = fetch_list
        self._scope = scope
        self._return_numpy = return_numpy
        if depth is None:
            depth = DEFAULT_DEPTH
        self._depth = max(1, int(depth))
        self._window = max(1, int(window if window is not None else depth))
        self._q = _queue_mod.Queue(self._depth)
        self._stop = threading.Event()
        self._thread = None
        self._iterated = False
        self._owner = _conc.owner_token("pipelined-runner", "stager", self)
        # timing records for the overlap gauge (and for tests):
        # stage = [(t0, t1), ...] per staged batch (stager thread),
        # busy  = [(dispatch_t0, results_t1), ...] per step (consumer)
        self.stage_intervals = []
        self.busy_intervals = []
        self.steps = 0

    # -- stager thread -----------------------------------------------------
    def _feed_source(self):
        if self._feeds is not None:
            for feed in self._feeds:
                yield feed
            return
        src = getattr(self._program, "_program", self._program)
        readers = getattr(src, "_py_readers", [])
        started = [r for r in readers if getattr(r, "_started", False)]
        if not started:
            raise core.ReaderNotStartedError(
                "run_pipelined with feeds=None needs a started py_reader "
                "attached to the program")
        while True:
            try:
                for r in started:
                    batch = r._next_feed()
                    if batch is not None:
                        yield dict(batch)
                        break
                else:
                    return
            except core.EOFException:
                return

    def _put(self, item):
        while not self._stop.is_set():
            try:
                self._q.put(item, timeout=0.1)
                return True
            except _queue_mod.Full:
                continue
        return False

    def _stage_loop(self):
        try:
            for feed in self._feed_source():
                if self._stop.is_set():
                    return
                t0 = time.monotonic()
                with obs.span("executor.stage_feed"):
                    staged = self._exe._prepare_feeds(self._program, feed)
                t1 = time.monotonic()
                self.stage_intervals.append((t0, t1))
                if not self._put((staged, t0, t1)):
                    return
        except BaseException as e:  # surfaced at the consumer
            self._put(("__error__", e))
            return
        self._put(_END)

    # -- consumer ----------------------------------------------------------
    def _materialize(self, entry):
        fetches, t0 = entry
        if self._return_numpy:
            out = [np.asarray(v) for v in fetches]
        else:
            # still fence the step so the in-flight window really bounds
            # live donated-state generations, then hand back lazy handles
            for v in fetches:
                if hasattr(v, "block_until_ready"):
                    v.block_until_ready()
                    break
            out = fetches
        self.busy_intervals.append((t0, time.monotonic()))
        self.steps += 1
        return out

    def __iter__(self):
        if self._iterated:
            raise RuntimeError("PipelinedRunner is single-use; build a "
                               "fresh one per run")
        self._iterated = True
        return self._iterate()

    def _iterate(self):
        self._thread = threading.Thread(
            target=self._stage_loop, daemon=True,
            name="paddle_tpu-feed-stager")
        _conc.track_thread(self._thread, self._owner)
        self._thread.start()
        inflight = collections.deque()
        try:
            while True:
                if _conc._on:
                    _conc.note_blocking("queue.get")
                t_wait = time.monotonic()
                item = self._q.get()
                # consumer-side queue wait IS the input-bound signal: a
                # fully overlapped pipeline pops instantly, so any time
                # here is data stall in the goodput decomposition
                _runhealth.goodput_note(
                    "data_stall", time.monotonic() - t_wait)
                if item is _END:
                    break
                if isinstance(item, tuple) and item[0] == "__error__":
                    raise item[1]
                staged, _s0, _s1 = item
                t0 = time.monotonic()
                fetches = self._exe.run(
                    self._program, feed=staged,
                    fetch_list=self._fetch_list, scope=self._scope,
                    return_numpy=False)
                inflight.append((fetches, t0))
                if len(inflight) >= self._window:
                    yield self._materialize(inflight.popleft())
            while inflight:
                yield self._materialize(inflight.popleft())
        finally:
            self.close()

    # -- teardown / reporting ----------------------------------------------
    def overlap_ratio(self):
        """Fraction of feed-staging seconds that overlapped an in-flight
        step (dispatch→materialize). 0.0 when nothing was staged."""
        total = sum(t1 - t0 for t0, t1 in self.stage_intervals)
        if total <= 0.0:
            return 0.0
        busy = sorted(self.busy_intervals)
        merged = []
        for b0, b1 in busy:
            if merged and b0 <= merged[-1][1]:
                merged[-1] = (merged[-1][0], max(merged[-1][1], b1))
            else:
                merged.append((b0, b1))
        overlapped = 0.0
        for s0, s1 in self.stage_intervals:
            for b0, b1 in merged:
                lo, hi = max(s0, b0), min(s1, b1)
                if hi > lo:
                    overlapped += hi - lo
        return min(1.0, overlapped / total)

    def close(self):
        """Stop the stager and discard staged (in-flight) batches. Safe
        to call repeatedly; iteration calls it on exhaustion/error."""
        self._stop.set()
        dropped = 0
        while True:
            try:
                item = self._q.get_nowait()
                if item is not _END and not (
                        isinstance(item, tuple) and item[0] == "__error__"):
                    dropped += 1
            except _queue_mod.Empty:
                break
        if dropped:
            obs.event("staging_discard", source="executor", count=False,
                      dropped=dropped)
        if self.stage_intervals:
            obs.set_gauge("executor.overlap_ratio", self.overlap_ratio())
        t = self._thread
        if t is not None and t.is_alive():
            t.join(timeout=2.0)
        # the stager must be gone after close(); a survivor is a leak
        # (a violation when the lock sanitizer is armed)
        _conc.check_stopped(self._owner, grace=0.5)
