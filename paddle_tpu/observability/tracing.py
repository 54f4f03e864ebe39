"""Nestable spans with a thread-local stack and three sinks.

``span("executor.run")`` is a context manager: entering pushes onto the
current thread's stack; exiting pops and hands the finished span to

1. the telemetry hub, as a ``span.<name>.seconds`` histogram;
2. a process-wide bounded **ring** of finished spans (``spans()``):
   name, ``t0``/``t1`` on ``time.monotonic()``, thread name, the parent
   span's name, and the span's fields — so a reader can cut a window
   out of what the program did and join the spans of one request by
   their ``request`` field, with nothing written to disk;
3. the **profiler**: the same enter/exit opens a
   ``jax.profiler.TraceAnnotation("paddle_tpu.<name>")``, so while a
   profiler session runs the span is written by the profiler itself,
   on the profiler's clock, into the same ``.xplane.pb`` as the
   device's "XLA Ops" and "XLA Modules" lines. With no session active
   an annotation is a flag test. ``jax`` is looked up in
   ``sys.modules`` and never imported from here.

In ``trace`` mode every exit additionally records a ``span`` event
(name, seconds, depth, parent) into the flight recorder so the crash
dump carries the step timeline. With telemetry off the context manager
is inert — no clock read, no stack push, nothing recorded.

Per-thread stacks are registered in a process-wide table so the crash
dumper can report what every thread was inside when the process died
(``active_spans()``).

Spans optionally participate in **distributed traces**: pass a sampled
:class:`~paddle_tpu.observability.distributed.TraceContext` as
``ctx=`` (or hand it to a live span with :meth:`span.adopt`) and the
span derives a child span id (readable as ``.ctx`` for further
propagation) and appends a JSONL record to ``$PADDLE_TPU_TRACE_DIR``
from the same exit. With no ctx (or an unsampled one) nothing touches
the disk.

:func:`record_span` records a span whose start and end were taken on
different threads (a queue wait): ring and histogram, no annotation.
"""
import collections
import sys
import threading
import time

from . import telemetry as _t

__all__ = ["span", "record_span", "spans", "clear_spans", "active_spans",
           "current_span", "RING_LEN"]

RING_LEN = 65536

_tls = threading.local()
_registry_lock = threading.Lock()
_stacks = {}  # thread ident -> (thread name, stack list)
# finished spans, oldest first: (name, t0, t1, thread, parent, fields).
# deque.append is atomic and drops the oldest entry at RING_LEN
_ring = collections.deque(maxlen=RING_LEN)
_annotation = None  # jax.profiler.TraceAnnotation, once jax is imported


def _stack():
    st = getattr(_tls, "stack", None)
    if st is None:
        # registered for the thread's lifetime: active_spans() filters
        # empty stacks, and an ident reused by a later thread simply
        # overwrites this entry (fresh thread -> fresh thread-local)
        st = _tls.stack = []
        t = threading.current_thread()
        with _registry_lock:
            _stacks[t.ident] = (t.name, st)
    return st


def _annotation_cls():
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


def _wall(t0):
    """The wall-clock time of the monotonic instant `t0`."""
    return time.time() - (time.monotonic() - t0)


def _sink(name, t0, t1, parent, fields, ctx):
    """Where every finished span goes: the hub's histogram, the ring and,
    when its context is sampled, the process's JSONL trace file."""
    _t._hub.observe("span.%s.seconds" % name, t1 - t0)
    _ring.append((name, t0, t1, threading.current_thread().name, parent,
                  fields))
    if ctx is not None and ctx.sampled:
        from . import distributed as _dist

        _dist.export_span(name, ctx, _wall(t0), t1 - t0, fields)


class span:
    """``with span("executor.run", program=uid) as sp: ...``

    ``ctx=`` attaches a distributed :class:`TraceContext`; when it is
    sampled the span gets its own child span id (``.ctx``) and its
    exit is exported as a JSONL trace record. ``note(**fields)`` adds
    fields the block learns on its way; ``seconds`` is the duration
    once the block has ended."""

    __slots__ = ("name", "fields", "t0", "seconds", "_live", "_mode",
                 "_ctx", "_ann")

    def __init__(self, name, ctx=None, **fields):
        self.name = name
        self.fields = fields
        self.t0 = None
        self.seconds = 0.0
        self._live = False
        self._mode = _t.OFF
        self._ctx = ctx
        self._ann = None

    @property
    def ctx(self):
        """The context to propagate downstream: this span's own child
        context once entered (so downstream spans parent to it), else
        whatever was passed in."""
        return self._ctx

    def adopt(self, ctx):
        """Join a distributed trace after entry, when the context only
        arrives inside what the span covers (a request body). Returns
        the context to propagate downstream."""
        if ctx is not None and ctx.sampled and self._live:
            ctx = ctx.child()
        self._ctx = ctx
        return ctx

    def note(self, **fields):
        """Add fields to a live span (a status, a count, an id)."""
        if self._live:
            self.fields.update(fields)

    def __enter__(self):
        m = _t.mode()
        self._mode = m
        if m == _t.OFF:
            return self
        self._live = True
        _stack().append(self)
        ctx = self._ctx
        if ctx is not None and ctx.sampled:
            self._ctx = ctx.child()
        cls = _annotation_cls()
        if cls is not None:
            # the fields known at entry ride along as the event's stats
            self._ann = cls("paddle_tpu." + self.name, **self.fields)
            self._ann.__enter__()
        self.t0 = time.monotonic()
        return self

    def __exit__(self, exc_type, exc, tb):
        if not self._live:
            return False
        t1 = time.monotonic()
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        self.seconds = dt = t1 - self.t0
        self._live = False
        st = _stack()
        # pop self even if an inner span leaked (exception paths)
        while st and st.pop() is not self:
            pass
        parent = st[-1].name if st else None
        fields = self.fields
        if exc_type is not None:
            fields = dict(fields, error=exc_type.__name__)
        _sink(self.name, self.t0, t1, parent, fields, self._ctx)
        if self._mode == _t.TRACE:
            from . import recorder as _r

            _r.get_recorder().record(
                "span", name=self.name, seconds=round(dt, 9),
                depth=len(st) + 1, parent=parent, **fields)
        return False

    def elapsed(self):
        """Seconds since entry (live spans only)."""
        return time.monotonic() - self.t0 if self.t0 is not None else 0.0


def record_span(name, t0, t1, ctx=None, **fields):
    """Record a span that started at monotonic `t0` on one thread and
    ended at `t1` on another (a queue wait): ring and histogram, and
    the JSONL export when `ctx` is sampled. Returns the child context
    the export used, for the spans that follow to parent to."""
    if _t.mode() == _t.OFF:
        return ctx
    if ctx is not None and ctx.sampled:
        ctx = ctx.child()
    _sink(name, t0, t1, None, fields, ctx)
    return ctx


def spans(name=None, since=None, until=None):
    """Copies of the ring's finished spans, oldest first, as dicts
    ``{"name", "t0", "t1", "thread", "parent", "fields"}`` on the
    ``time.monotonic()`` clock. ``name`` keeps one span name (or any of
    a tuple); ``since``/``until`` keep the spans that *started* in
    ``[since, until)``."""
    names = (name,) if isinstance(name, str) else name
    while True:
        try:
            rows = list(_ring)
            break
        except RuntimeError:  # appended to while it was copied
            continue
    return [
        {"name": n, "t0": t0, "t1": t1, "thread": thread,
         "parent": parent, "fields": dict(fields)}
        for n, t0, t1, thread, parent, fields in rows
        if (names is None or n in names)
        and (since is None or t0 >= since)
        and (until is None or t0 < until)]


def clear_spans():
    _ring.clear()


def current_span():
    """The innermost live span on this thread, or None."""
    st = getattr(_tls, "stack", None)
    return st[-1] if st else None


def active_spans():
    """{thread name: [(span name, seconds elapsed), ...]} for every
    thread currently inside at least one span — outermost first. Used
    by the crash dumper to answer 'what was each thread doing'."""
    out = {}
    with _registry_lock:
        items = list(_stacks.items())
    for _ident, (tname, st) in items:
        frames = [(s.name, round(s.elapsed(), 6)) for s in list(st)
                  if s._live]
        if frames:
            out[tname] = frames
    return out
