"""DecodeEngine: slotted KV-cache decode with continuous batching.

The micro-batching :class:`~paddle_tpu.serving.engine.ServingEngine`
coalesces fixed-shape ``predict`` calls; the millions-of-users workload
is autoregressive *decode*, where a full-batch ``lax.scan`` generator
(:func:`~paddle_tpu.models.gpt.build_gpt_generate`) makes every request
wait for the slowest sequence in its batch and admits nothing
mid-generation. This engine removes the full-batch barrier:

- **Slotted state** — ONE pre-allocated set of device buffers
  (:class:`SlotCache`) holds every live sequence's state, as the served
  model declares it (``models.decode_utils.DecodeModel.state``): for an
  attention layer a ``(slots, cache_len, width)`` K and V (``rows``, one
  per position), for a state-space layer a convolution window and a
  recurrent state per slot (``fixed``, a whole value per sequence), for
  a window attention layer a ``(slots, window, width)`` K and V (``ring``,
  position p at row ``p mod window``), for an expert layer nothing. Every
  program that takes the buffers is given them **donated** and updates
  them in place: a step writes one row per slot and attention layer (of a
  ring, over the row that has left the window), overwrites each fixed
  state, and copies nothing. A slot is a sequence's home for its whole
  generation;
  retiring frees the slot the same step.
- **Two programs, both AOT** — a *prefill* program per declared prompt
  bucket (parallel pass over the right-padded prompt writes a slot's
  cache and emits the first token) and ONE *step* program (one token
  for ALL slots per iteration, per-slot positions). Both resolve
  through the PR-4 compile-cache disk tier at :meth:`warmup`, so a
  restarted server never compiles and steady-state decode never sees
  XLA.
- **Continuous batching** — a single dispatch thread interleaves the
  two: finished sequences (EOS or max-new) retire in-flight and queued
  requests are prefilled into freed slots between steps; the other
  slots never stall on a barrier. The loop is pipelined by one step:
  step n+1 is dispatched before step n's tokens are delivered, so the
  stream threads' work per token overlaps the device's step.
  Per-request tokens are bit-identical to a solo
  ``build_gpt_generate`` run (row independence + per-slot masks),
  which the tests assert token-for-token.
- **Streaming** — ``submit()`` returns a :class:`DecodeStream` whose
  ``tokens()`` generator yields each token as the step loop produces
  it; ``serving.http`` exposes it as a chunked-transfer ``:generate``
  endpoint. Cancelling a stream (client disconnect) frees its slot at
  the next loop iteration.

Admission control mirrors the serving engine: full queue fast-rejects
with :class:`~paddle_tpu.serving.engine.ShedError` (HTTP 429 +
Retry-After from the observed retire rate), a queued request whose
deadline expires is shed BEFORE its prefill with
:class:`~paddle_tpu.serving.engine.DeadlineExceededError` (504), and
:meth:`check_hbm_budget` prices the KV buffer pair + params + step
peak with the static liveness analyzer before any warmup compile.

Telemetry: ``serving.decode.slot_utilization`` /
``serving.decode.cache_occupancy`` gauges,
``serving.decode.prefill_seconds`` / ``step_seconds`` /
``ttft_seconds`` / ``request_seconds`` histograms, and
``serving.decode.tokens`` / ``requests`` / ``retired`` / ``shed`` /
``deadline_miss`` / ``cancelled`` counters.

Disaggregation hooks (PR 12, ``serving.disagg``): ``kv_dtype="int8"``
keeps the slot cache **resident in int8** with per-(slot, layer, row)
fp32 scales — ~4x the decode slots at equal HBM, priced honestly by
:meth:`check_hbm_budget` — swapping in the dequantize-in-program step
(:func:`~paddle_tpu.models.gpt.build_gpt_decode_step_q`);
``role="decode"`` builds NO prefill programs (a pure step replica) and
:meth:`submit_prefilled` adopts a serialized
:class:`~paddle_tpu.serving.disagg.kv_wire.KVHandoff` from a prefill
replica straight into a slot.

KV-reuse + speculation hooks (``serving.prefix_pool`` /
``serving.spec``):

- ``prefix_pool=PrefixPool(...)`` — before a cold prefill the engine
  hashes the prompt against the pool; a full hit adopts the cached
  rows and emits the cached first token with NO program run, a prefix
  hit adopts ``plen`` rows and **delta-prefills** only the suffix
  (:func:`~paddle_tpu.models.gpt.build_gpt_prefill_delta`), and every
  cold/delta prefill inserts its rows back. Redundant-prefill
  economics land in the ``prefill_rows_computed`` /
  ``prefill_rows_saved`` counters.
- ``draft=DraftModel(...)`` — speculative decoding (fp32-resident
  engines): each iteration the draft proposes ``k`` tokens and ONE
  verify dispatch (:func:`~paddle_tpu.models.gpt.
  build_gpt_verify_block`) scores the block; the longest prefix
  matching the target's own greedy picks is emitted (plus the
  correction/bonus token), so every stream stays bit-exact with
  non-speculative decode while one dispatch yields up to ``k + 1``
  tokens. Near the cache edge the engine falls back to the plain step
  (mirrored into the draft via ``sync_step``). Acceptance is exported
  as ``serving.spec.accept_rate``.
- ``session_tier=SessionTier(...)`` — ``submit(session=...)``
  hibernates the slot's KV rows to host RAM (the KVHandoff wire
  format) when the stream retires, and a later submit with the same
  session id re-adopts them and delta-prefills only the new turn, so
  concurrent sessions stop being bounded by live slots.
"""
import collections
import itertools
import queue
import threading
import time

import numpy as np

from .. import observability as obs
from ..analysis import concurrency as _conc
from ..analysis import dataflow as _dataflow
from ..fluid import resilience as R
from ..models.decode_utils import require_rows_only
from .engine import DeadlineExceededError, EngineClosedError, ShedError

__all__ = ["DecodeEngine", "DecodeStream", "SlotCache",
           "default_prompt_buckets", "kv_slot_bytes"]


# every stream of the process gets its own id: the ``request`` field the
# spans of one request share (http.generate, decode.queue, .prefill,
# .stream)
_stream_ids = itertools.count(1)


def kv_slot_bytes(cfg, cache_len, kv_dtype="fp32"):
    """HBM bytes ONE decode slot's state occupies: the sum of the model's
    own declaration (``cfg.decode_model(cache_len, kv_dtype).state``),
    whatever its entries are. For a model of K/V rows alone this is the
    slot economics `disagg` trades on: int8 residency pays 1 byte/element
    plus one fp32 scale per (layer, row) instead of 4 bytes/element, so
    slots-per-budget multiplies by ~4 (3.9x at hidden 32+)."""
    return cfg.decode_model(int(cache_len), kv_dtype).slot_bytes()


def default_prompt_buckets(cache_len, smallest=8):
    """Pow2 prompt-length ladder up to ``cache_len`` (always at least
    one bucket)."""
    buckets = []
    b = min(int(smallest), int(cache_len))
    while b < cache_len:
        buckets.append(b)
        b *= 2
    buckets.append(int(cache_len))
    return tuple(sorted(set(buckets)))


class SlotCache:
    """The slots' state in the form the step programs take it, and its
    ONE owner: for every :class:`~paddle_tpu.models.decode_utils.
    StateEntry` the served model declares, a ``(slots,) + entry.shape``
    device buffer of ``entry.dtype``, kept as the flat list ``bufs`` in
    the declaration's order, which is the order of a step program's
    ``cache_feed_names``. ``rows`` entries (K and V of an attention
    layer), ``ring`` entries (K and V of a window attention layer, the
    window long) and ``fixed`` entries (a convolution window, a
    state-space state) live side by side; allocation, donation,
    :meth:`write_slot` and :meth:`read_slot` treat them alike.

    Every program that takes the buffers consumes them
    (``Predictor(donate_feeds=...)``) and hands them back updated in
    place (:meth:`run`). A reference to a buffer held anywhere else is
    dead after the next step, so nothing outside this class keeps one:
    a sequence goes in through :meth:`write_slot` (one donated dispatch
    for all entries, every entry written whole, so nothing of the slot's
    last sequence is left behind) and comes out as host arrays, in the
    model's packed form (``DecodeModel.pack``: for K/V rows the
    ``(layers, cache_len, width)`` geometry of the wire format and the
    prefix pool), through :meth:`read_slot`."""

    def __init__(self, jax, model, slots):
        self._jax = jax
        self._model, self.slots = model, int(slots)
        self.specs = [jax.ShapeDtypeStruct((self.slots,) + tuple(e.shape),
                                           e.dtype) for e in model.state]
        unpack, pack = model.unpack, model.pack

        def write(bufs, vals, slot):
            # vals: what a prefill fetched after its token (or the same
            # form from the host); unpack -> one (1,) + shape per entry
            return [jax.lax.dynamic_update_slice(
                        b, v.astype(b.dtype), (slot,) + (0,) * (b.ndim - 1))
                    for b, v in zip(bufs, unpack(*vals))]

        def read(bufs, slot):
            return pack([jax.lax.dynamic_index_in_dim(b, slot, 0, False)
                         for b in bufs])

        # the slot index is a traced scalar: each compiles once
        self._write = jax.jit(write, donate_argnums=(0,))
        self._read = jax.jit(read)
        self.reallocs = 0   # times a failed dispatch cost the cache
        self.bufs = None
        self.allocate()

    def allocate(self):
        """Fresh zeroed buffers, filled on the device (the old ones are
        dropped first)."""
        self.bufs = None
        zeros = self._jax.numpy.zeros
        self.bufs = [zeros(sp.shape, sp.dtype) for sp in self.specs]

    def release(self):
        """Drop the buffers for good (a stopped engine's: whoever still
        holds the engine then holds none of its device state)."""
        self.bufs = None

    def feeds(self, names):
        """The buffers under a program's cache feed names."""
        return dict(zip(names, self.bufs))

    def run(self, pred, names, feeds):
        """Dispatch a program that takes the whole cache: the buffers go
        in, donated, under its cache feed ``names`` beside ``feeds``, and
        the updated ones come back as its fetches after the first.
        Returns ``(fetches, in_place)``; ``in_place`` is False when the
        run left its inputs alive, i.e. worked on a copy. A dispatch
        that raises after consuming the buffers leaves no valid cache:
        it is replaced by zeroed buffers (as ``Executor.run`` evicts a
        poisoned donated state) before the error goes on to the caller,
        whose sequences are lost either way."""
        fed = self.bufs
        feeds = dict(feeds)
        feeds.update(zip(names, fed))
        try:
            outs = pred.run(feeds, return_numpy=False)
        except Exception:
            if any(b.is_deleted() for b in fed):
                del fed, feeds
                self.allocate()
                self.reallocs += 1
            raise
        self.bufs = list(outs[1:1 + len(fed)])
        return outs, fed[0].is_deleted()

    def write_slot(self, slot, *vals):
        """Install one sequence's whole state: ``vals`` as a prefill
        fetched them after its token (for K/V rows alone: per group
        (k, v[, k_scale, v_scale]) a ``(1, layers, cache_len, width)``
        array), in the residency dtype."""
        self.bufs = self._write(self.bufs, list(vals), np.int32(slot))

    def read_slot(self, slot):
        """One slot's state as host arrays in the model's packed form
        (K/V rows: ``(layers, cache_len, width)``, one per group)."""
        return [np.asarray(a)
                for a in self._read(self.bufs, np.int32(slot))]

    def snapshot(self):
        """Device copies of every buffer (what a replay of a step needs,
        since the step consumes the originals)."""
        return [self._jax.numpy.copy(b) for b in self.bufs]

    def nbytes(self, kind=None):
        """Device bytes of all buffers (of entries of one ``kind``)."""
        return self.slots * self._model.slot_bytes(kind)


class DecodeStream:
    """Streaming handle for one generation request.

    The dispatch thread feeds it; the caller either iterates
    :meth:`tokens` (per-token streaming — what the HTTP chunked
    endpoint does) or blocks on :meth:`result` for the full list.
    ``finish_reason`` is ``"eos"`` / ``"length"`` / ``"cancelled"`` /
    ``"error"`` once done. :meth:`cancel` (idempotent, thread-safe)
    frees the request's slot at the dispatch loop's next iteration —
    or drops it from the queue if it never reached a slot."""

    # distributed-trace context of a sampled request (None otherwise);
    # class attr so pre-trace pickles/subclasses still read it
    trace = None

    def __init__(self, prompt_len, max_new, stall_timeout_s=60.0):
        self.id = next(_stream_ids)
        self.prompt_len = int(prompt_len)
        self.max_new = int(max_new)
        self.stall_timeout_s = float(stall_timeout_s)
        self.finish_reason = None
        self.t_submit = time.monotonic()
        # one producer (the dispatch thread), one consumer, unbounded:
        # SimpleQueue's put and get are C and wake the reader straight
        # into tokens(); queue.Queue's bounded-queue bookkeeping
        # (not_full, unfinished_tasks) nobody read
        self._q = queue.SimpleQueue()
        self._tokens = []
        self._done = threading.Event()
        self._cancelled = threading.Event()
        self._error = None

    # -- caller surface --------------------------------------------------
    @property
    def cancelled(self):
        return self._cancelled.is_set()

    @property
    def done(self):
        return self._done.is_set()

    def cancel(self):
        """Stop generating for this request (client went away)."""
        self._cancelled.set()

    def tokens(self, timeout=None):
        """Generator yielding token ids as the engine produces them.
        ``timeout`` bounds the wait for EACH token (default: the
        engine's request timeout); a stalled engine raises
        ``TimeoutError``, a failed request raises its error.

        However it ends, the generator records one ``decode.stream.read``
        span from the thread that ran it (the reader's side of the
        stream, where ``decode.stream`` is the engine's): ``t0`` its
        first ``get``, ``t1`` its end, and as fields sums over the items
        it took (the tokens and the one end): ``wake_s``, from the later
        of an item's put and the reader's ``get`` to the ``get``'s
        return (the item was ready AND its reader was waiting, and the
        reader still did not run: scheduling and the GIL), its largest
        term ``wake_max_s`` at item ``wake_max_index``; ``consume_s``,
        from a token's yield to the consumer's next ``next()`` (or its
        ``close()``); ``cpu_s``, this thread's CPU over the span;
        ``tokens``; ``end`` (``done`` / ``err`` / ``timeout`` /
        ``closed``, the consumer left). ``wake_s + consume_s`` + the
        reader's waits on an empty queue = ``t1 - t0``. With telemetry
        off no clock is read here and nothing is recorded."""
        wait = self.stall_timeout_s if timeout is None else float(timeout)
        timed = obs.enabled()
        clock = time.monotonic
        n, end = 0, "closed"
        wake = wake_max = consume = 0.0
        wake_at = None
        if timed:
            cpu0 = time.thread_time()
            t0 = t_wait = t_got = clock()
        try:
            while True:
                try:
                    kind, val, t_put = self._q.get(timeout=wait)
                except queue.Empty:
                    end = "timeout"
                    # what every thread was inside when the reader gave
                    # up: the engine's phase, or no engine span at all
                    obs.event("stream_stall", source="serving",
                              request=self.id, tokens=len(self._tokens),
                              waited_s=wait, active=obs.active_spans())
                    raise TimeoutError(
                        "no token for %.1fs (generated %d so far)"
                        % (wait, len(self._tokens)))
                if timed:
                    t_got = clock()
                    w = t_got - (t_put if t_put > t_wait else t_wait)
                    wake += w
                    if w > wake_max:
                        wake_max, wake_at = w, n
                if kind == "tok":
                    n += 1
                    yield val
                    if timed:
                        t_wait = clock()
                        consume += t_wait - t_got
                elif kind == "err":
                    end = "err"
                    raise val
                else:  # done
                    end = "done"
                    return
        finally:
            if timed:
                t1 = clock()
                if end == "closed":
                    # left at a yield: the consumer held the last token
                    # until it closed the generator
                    consume += t1 - t_got
                obs.record_span(
                    "decode.stream.read", t0, t1, request=self.id,
                    tokens=n, end=end, wake_s=wake, wake_max_s=wake_max,
                    wake_max_index=wake_at, consume_s=consume,
                    cpu_s=time.thread_time() - cpu0)

    def result(self, timeout=None):
        """Block until generation finishes; returns the full token
        list (raises the request's error if it failed)."""
        wait = self.stall_timeout_s if timeout is None else timeout
        if not self._done.wait(wait):
            raise TimeoutError(
                "generation not done after %.1fs" % float(wait))
        if self._error is not None:
            raise self._error
        return list(self._tokens)

    def so_far(self):
        """Tokens generated so far (snapshot, no wait)."""
        return list(self._tokens)

    # -- engine surface --------------------------------------------------
    def _emit(self, tok):
        self._tokens.append(tok)
        # beside each item the time of its put: the reader's side of
        # ``decode.stream.read`` (see tokens())
        self._q.put(("tok", tok, time.monotonic()))

    def _finish(self, reason):
        self.finish_reason = reason
        self._done.set()
        self._q.put(("done", reason, time.monotonic()))

    def _fail(self, exc):
        self._error = exc
        self.finish_reason = "error"
        self._done.set()
        self._q.put(("err", exc, time.monotonic()))


class _Request:
    __slots__ = ("prompt", "plen", "bucket", "max_new", "eos_id",
                 "deadline", "handle", "handoff", "tenant", "priority",
                 "trace",
                 # KV-reuse routing: "session" id (tiering), "base"
                 # (adopted rows: a KVHandoff on resume, a pool entry
                 # on a prefix hit), "start" adopted row count,
                 # "suffix"/"sbucket" the delta-prefill tail, "hist"
                 # the token-per-written-row history
                 "session", "base", "start", "suffix", "sbucket",
                 "hist",
                 # a request that carries images: "media" a list of (uint8
                 # patches padded to their bucket, (h, w)), "media_index"
                 # per prompt position the media row it takes or -1
                 "media", "media_index")


class _Slot:
    __slots__ = ("handle", "remaining", "eos_id", "t_prefill",
                 "trace", "t_last", "session", "hist")

    def __init__(self, handle, remaining, eos_id, trace=None,
                 session=None, hist=None):
        self.handle = handle
        self.remaining = remaining
        self.eos_id = eos_id
        self.t_prefill = time.monotonic()
        # sampled TraceContext of the span that filled this slot; the
        # per-token spans and the retire summary parent to it
        self.trace = trace
        self.t_last = self.t_prefill
        # tiering: session id to hibernate under at retire, plus the
        # token history whose rows the slot held at admission (the
        # emitted tokens extend it — see _hibernate)
        self.session = session
        self.hist = hist


class _Fill:
    """A fill in progress: a prompt that goes into its slot a UNIT a turn of
    the dispatch loop, so that the live streams get a step between two
    units: a long prompt a chunk a turn (``DecodeModel.build_chunk``;
    ``chunked``), and before its rows, for a request that carries images,
    the encoder's program an image a turn (``DecodeModel.encoder``;
    ``image`` the next one to run, ``media`` the device buffer their rows are
    gathered in, ``media_at`` the row the next image's go to). A fill that is
    not chunked ends in ONE unit of its bucket's program. ``at`` is the row
    the next chunk starts at (the prompt's length once every row is out);
    ``state`` the sequence's state so far, device arrays
    that the chunk program consumes and hands back, OUTSIDE the
    :class:`SlotCache` (the step program reads and writes every slot's
    buffers, live or not: a half-built state must not sit in one); ``nxt``
    the last dispatched chunk's token, still on the device. ``ctx`` is the
    context of the request's ``decode.prefill`` span, which runs from the
    first chunk to the seat, and ``elapsed`` its time so far: what
    ``_seat`` and ``_observe_prefill`` ask of a span."""

    __slots__ = ("req", "slot", "at", "state", "nxt", "t0", "ctx",
                 "chunked", "media", "media_at", "image")

    def __init__(self, req, slot, state, ctx, chunked=True, media=None):
        self.req, self.slot, self.state, self.ctx = req, slot, state, ctx
        self.chunked, self.media = chunked, media
        self.at = self.media_at = self.image = 0
        self.nxt = None
        self.t0 = time.monotonic()

    def elapsed(self):
        return time.monotonic() - self.t0


class DecodeEngine:
    """Continuous-batching decode engine over a prefill/step program
    pair. The model hands over its builders and the declaration of the
    state it carries (``cfg.decode_model(cache_len, kv_dtype)``, a
    :class:`~paddle_tpu.models.decode_utils.DecodeModel`).

    ::

        eng = DecodeEngine(cfg, scope=trained_scope, slots=8,
                           cache_len=128, eos_id=2, name="gpt")
        eng.warmup()
        for tok in eng.submit(prompt_ids, max_new=64).tokens():
            ...

    ``scope`` is any name->array mapping holding the trained params
    (a ``fluid.Scope``, ``global_scope()`` after training, or a plain
    dict); :meth:`from_dir` loads a ``save_persistables`` /
    ``save_inference_model`` directory. Params are device_put ONCE and
    shared by every program (prefill buckets + step), not duplicated
    per predictor. ``adopt_params=True`` takes the scope's device arrays
    as the engine's own instead of copying each through the host: for a
    caller that made them on the device and hands them over (it must not
    donate or change them afterwards).

    A model that carries ``fixed`` state (a state-space layer) or a
    ``ring`` (a window attention layer) cannot be combined with what
    cuts, shares, quantises or ships state row by row: ``prefix_pool``,
    ``session_tier``, ``kv_dtype="int8"``, a
    ``draft`` (block verify with roll-back), ``role="decode"`` /
    :meth:`submit_prefilled` (the KV wire). Each is refused at
    construction."""

    engine_kind = "decode"

    def __init__(self, cfg, scope, slots=4, cache_len=64,
                 prompt_buckets=None, eos_id=None, queue_capacity=64,
                 default_max_new=32, default_deadline_ms=None,
                 request_timeout_s=60.0, name="default",
                 auto_start=True,
                 kv_dtype="fp32", role="colocated",
                 draft=None, prefix_pool=None, session_tier=None,
                 adopt_params=False):
        import jax

        import paddle_tpu.fluid as fluid
        from ..fluid.inference import Predictor

        if kv_dtype not in ("fp32", "int8"):
            raise ValueError("kv_dtype must be 'fp32' or 'int8', got %r"
                             % (kv_dtype,))
        if role not in ("colocated", "decode"):
            raise ValueError("role must be 'colocated' or 'decode', "
                             "got %r" % (role,))
        if draft is not None and kv_dtype != "fp32":
            raise ValueError(
                "speculative decoding needs an fp32-resident cache "
                "(the verify program scores the raw fp32 rows); drop "
                "the draft or use kv_dtype='fp32'")
        if role == "decode" and (prefix_pool is not None
                                 or session_tier is not None):
            raise ValueError(
                "prefix_pool/session_tier need the delta-prefill "
                "program a pure decode-role replica does not build — "
                "attach them to the router's prefill side instead")
        # the model's own declaration: builders and per-slot state
        model = cfg.decode_model(int(cache_len), kv_dtype)
        for feature, on in (
                ("prefix_pool (prefix reuse)", prefix_pool is not None),
                ("session_tier (hibernation + delta prefill)",
                 session_tier is not None),
                ("kv_dtype='int8'", kv_dtype == "int8"),
                ("draft (speculative verify with roll-back)",
                 draft is not None),
                ("role='decode' (KV hand-over on the wire)",
                 role == "decode")):
            if on:
                require_rows_only(model, feature)
        self._model = model
        self._jax = jax
        self.cfg = cfg
        self.name = str(name)
        self.slots = int(slots)
        self.cache_len = int(cache_len)
        self.kv_dtype = str(kv_dtype)
        self.role = str(role)
        self.eos_id = eos_id
        self.default_max_new = int(default_max_new)
        self._default_deadline_ms = default_deadline_ms
        self.request_timeout_s = float(request_timeout_s)
        if prompt_buckets is None:
            prompt_buckets = default_prompt_buckets(self.cache_len)
        self.prompt_buckets = tuple(sorted({int(b) for b in prompt_buckets}))
        if not self.prompt_buckets or self.prompt_buckets[0] < 1:
            raise ValueError("prompt_buckets must be positive ints")
        if self.prompt_buckets[-1] > self.cache_len:
            raise ValueError(
                "largest prompt bucket (%d) exceeds cache_len (%d)"
                % (self.prompt_buckets[-1], self.cache_len))

        self._prefix_pool = prefix_pool
        self._session_tier = session_tier
        self._draft = draft

        # -- build the program pair (never touching the caller's
        # default_main_program) and share ONE device param set ---------
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            step_vars = model.build_step(cfg, self.cache_len)
            step_prog = fluid.default_main_program()
        prefill = {}
        if self.role != "decode":  # a pure decode replica never prefills
            for b in self.prompt_buckets:
                with fluid.program_guard(fluid.Program(), fluid.Program()):
                    pv = model.build_prefill(cfg, b, self.cache_len)
                    prefill[b] = (fluid.default_main_program(), pv)
        # the chunk program of a model whose prefill can continue: a
        # long prompt then fills its slot a chunk a turn beside live
        # streams (_chunked); the bucket programs stay for the rest. No
        # bucket longer than a chunk: nothing would ever be cut
        chunk = None
        if (prefill and model.build_chunk is not None
                and self.prompt_buckets[-1] > model.chunk_rows):
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                cv = model.build_chunk(cfg, model.chunk_rows, self.cache_len)
                chunk = (fluid.default_main_program(), cv)
        # the encoder's programs of a model whose requests may carry
        # images, one a patch bucket (DecodeModel.encoder)
        towers = {}
        if prefill and model.encoder is not None:
            for b in model.encoder.buckets:
                with fluid.program_guard(fluid.Program(), fluid.Program()):
                    tv = model.encoder.build(cfg, b)
                    towers[b] = (fluid.default_main_program(), tv)
        # delta-prefill ladder (prefix-pool hits + session resumes):
        # same bucket widths as cold prefill, suffix-sized at use
        delta = {}
        if prefix_pool is not None or session_tier is not None:
            for b in self.prompt_buckets:
                with fluid.program_guard(fluid.Program(), fluid.Program()):
                    dv = model.build_delta(cfg, b, self.cache_len)
                    delta[b] = (fluid.default_main_program(), dv)
        # block-verify program (speculative decoding): k proposals +
        # the slot's current token = a k+1 wide block per dispatch
        verify = None
        if draft is not None:
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                vv = model.build_verify(cfg, draft.k + 1, self.cache_len)
                verify = (fluid.default_main_program(), vv)
        persist = {}
        all_progs = ([step_prog] + [p for p, _ in prefill.values()]
                     + [p for p, _ in delta.values()]
                     + [p for p, _ in towers.values()]
                     + [pv[0] for pv in (chunk, verify) if pv is not None])
        for prog in all_progs:
            for v in prog.list_vars():
                if not getattr(v, "persistable", False):
                    continue
                if v.name in persist:
                    continue
                if v.name not in scope:
                    raise KeyError(
                        "param %r required by the decode programs is "
                        "missing from the given scope — train the model "
                        "or load its persistables first" % v.name)
                val = scope[v.name]
                if adopt_params and isinstance(val, jax.Array):
                    # handed over as owned: no second copy of weights
                    # that were made on the device
                    persist[v.name] = val
                    continue
                # snapshot through the host: device_put on a committed
                # jax array is a no-op, and sharing the training
                # executor's buffers would let its donating step
                # invalidate them under this engine mid-serve
                persist[v.name] = jax.device_put(np.asarray(val))
        if _conc._on and not adopt_params:
            # the copy above breaks aliasing with the training executor's
            # donated buffers — register it so the donation registry can
            # prove (not assume) no cross-program alias survives
            _dataflow.note_capture(scope, persist,
                                   "decode-engine %r" % self.name,
                                   snapshot=True)
        self._params = persist
        self._step_vars = step_vars
        # the feeds every step and prefill program has beside the state
        self._tok_name, self._pos_name = step_vars["feed_names"][:2]
        # each program has a module name of its own in a device trace
        # (jit_fwd_decode_step, jit_fwd_prefill_<bucket>, ...); every
        # name keeps "fwd"
        self._step_pred = Predictor(
            step_prog, step_vars["feed_names"], step_vars["fetch_vars"],
            scope=persist, name="decode_step",
            donate_feeds=step_vars["cache_feed_names"])
        self._step_pred.ledger_tag = "decode.step:%s" % self.name
        self._prefill_preds = {}
        self._prefill_vars = {}
        for b, (prog, pv) in prefill.items():
            self._prefill_preds[b] = Predictor(
                prog, pv["feed_names"], pv["fetch_vars"], scope=persist,
                name="prefill_%d" % b)
            self._prefill_preds[b].ledger_tag = (
                "decode.prefill:%s" % self.name)
            self._prefill_vars[b] = pv
        # jit_fwd_chunk_<rows>: not a name that holds "fwd_prefill_"
        self._chunk_pred = self._chunk_vars = self._chunk_zeros = None
        if chunk is not None:
            prog, self._chunk_vars = chunk
            self._chunk_pred = Predictor(
                prog, self._chunk_vars["feed_names"],
                self._chunk_vars["fetch_vars"], scope=persist,
                name="chunk_%d" % model.chunk_rows,
                donate_feeds=self._chunk_vars["cache_feed_names"])
            self._chunk_pred.ledger_tag = (
                "decode.prefill.chunk:%s" % self.name)
            # what a sequence carries before its first chunk: one dispatch
            self._chunk_zeros = jax.jit(lambda: [
                jax.numpy.zeros((1,) + tuple(e.shape), e.dtype)
                for e in model.state])
        # jit_fwd_tower_<patches>; the rows of a request's images are
        # gathered in one device buffer (a fresh one a request: the write
        # donates it), a text-only request of such a model feeds a blank
        # one that is never written
        self._tower_preds, self._tower_vars = {}, {}
        self._media_blank = self._media_zeros = self._media_write = None
        for b, (prog, tv) in towers.items():
            self._tower_preds[b] = Predictor(
                prog, tv["feed_names"], tv["fetch_vars"], scope=persist,
                name="tower_%d" % b)
            self._tower_preds[b].ledger_tag = "decode.tower:%s" % self.name
            self._tower_vars[b] = tv
        if towers:
            enc, jnp = model.encoder, jax.numpy
            dtype = towers[enc.buckets[0]][1]["fetch_vars"][0].dtype
            self._media_zeros = jax.jit(lambda: jnp.zeros(
                (enc.buffer_rows, enc.width), dtype))
            self._media_blank = self._media_zeros()
            self._media_write = jax.jit(
                lambda buf, rows, at: jax.lax.dynamic_update_slice(
                    buf, rows.astype(buf.dtype), (at, 0)), donate_argnums=0)
        self._delta_preds = {}
        for b, (prog, dv) in delta.items():
            self._delta_preds[b] = Predictor(
                prog, dv["feed_names"], dv["fetch_vars"], scope=persist,
                name="delta_%d" % b)
            self._delta_preds[b].ledger_tag = (
                "decode.delta_prefill:%s" % self.name)
        self._verify_pred = self._verify_vars = None
        if verify is not None:
            prog, vv = verify
            self._verify_vars = vv
            self._verify_pred = Predictor(
                prog, vv["feed_names"], vv["fetch_vars"], scope=persist,
                name="verify_block",
                donate_feeds=vv["cache_feed_names"])
            self._verify_pred.ledger_tag = "decode.verify:%s" % self.name

        # -- the persistent slot cache + host-side slot state ----------
        self._cache = SlotCache(jax, model, self.slots)
        for kind in ("rows", "fixed", "ring"):
            obs.set_gauge("serving.decode.state_bytes_%s.%s"
                          % (kind, self.name), self._cache.nbytes(kind))
        self._tok = np.zeros((self.slots, 1), np.int64)
        self._pos = np.zeros((self.slots, 1), np.int64)
        self._slots = [None] * self.slots
        # deliveries decided and not yet handed to their streams, in
        # order: (slot state, slot, token or None, end reason or None,
        # error); and what is left of the step that produced them
        self._outbox = []
        self._spent = None
        # the fill in progress (_Fill), at most one: its slot is held,
        # neither free nor live, and nothing else is admitted meanwhile
        self._fill = None

        self._q = queue.Queue(maxsize=int(queue_capacity))
        self._stop_event = threading.Event()
        self._abort = False
        self._closed = False
        self._admit_lock = _conc.named_lock("serving.decode.admit")
        self._stats_lock = _conc.named_lock("serving.decode.stats")
        self._stats = collections.Counter()
        # where the dispatch thread's time went, in seconds, added from
        # the exits of its phase spans (one writer; stats() copies).
        # admit + prefill_total + dispatch + emit + release + sync + idle
        # is the loop's wall time; prefill_sync is the part of
        # prefill_total spent waiting for the device. loop_cpu is the
        # thread's CPU (time.thread_time(), read once a loop turn): what
        # of that wall time it was RUNNING
        self._phase_s = dict.fromkeys(
            ("admit_seconds", "prefill_seconds_total",
             "prefill_sync_seconds", "dispatch_seconds", "sync_seconds",
             "emit_seconds", "release_seconds", "idle_seconds",
             "loop_cpu_seconds"), 0.0)
        self._proc = "decode:%s" % self.name  # track of its trace spans
        self._rate = collections.deque(maxlen=64)  # (t_done, 1) retires
        self._thread = None
        self._owner = _conc.owner_token("decode-engine", self.name, self)
        # cost-model predictions keyed ("step",) / ("prefill", bucket),
        # computed lazily on the first TRACED request (annotation only;
        # unsampled requests never run the analyzer)
        self._cost_cache = {}
        # measured-step feed into the executable ledger ("" = program
        # has no fingerprint, stop trying)
        self._step_fp = None
        self._step_ema = None
        self._step_noted = False
        # SDC sentinel (paddle_tpu/integrity/sentinel.py): attached by
        # the disagg router (or a test); None = zero per-step overhead
        self._sentinel = None
        self._sentinel_id = self.name
        if draft is not None:
            draft.bind(self)
        if auto_start:
            self.start()

    def attach_sentinel(self, sentinel, replica=None):
        """Arm sampled step-replay SDC checking on this engine; a
        replay disagreement fails the step BEFORE any token is emitted
        (streams migrate and regenerate — a lying step never serves).
        ``replica`` names this engine in the sentinel's vote protocol
        (defaults to the engine name)."""
        self._sentinel = sentinel
        self._sentinel_id = str(replica) if replica is not None \
            else self.name
        if sentinel is not None:
            sentinel.register(self._sentinel_id, self.sentinel_replay)
        return self

    def sentinel_replay(self, feeds):
        """Re-dispatch the step program on arbitrary feeds (the
        cross-replica vote path — peers re-run a suspect's feeds).
        The step consumes its cache feeds, so it runs on copies of
        them: ``feeds`` stay valid for the next peer, and this never
        touches this engine's resident cache."""
        jnp = self._jax.numpy
        feeds = dict(feeds)
        for n in self._step_pred.donate_feeds:
            feeds[n] = jnp.copy(feeds[n])
        return self._step_pred.run(feeds, return_numpy=False)

    # -- construction helpers -------------------------------------------
    @classmethod
    def from_dir(cls, cfg, dirname, filename=None, **kw):
        """Build from a ``save_persistables`` / ``save_params`` /
        ``save_inference_model`` directory (the ``.npz`` payload those
        writers produce)."""
        import os

        candidates = ([filename] if filename else
                      ["__persistables__.npz", "__params__.npz",
                       "__vars__.npz"])
        for fn in candidates:
            path = os.path.join(str(dirname), fn)
            if os.path.exists(path):
                data = np.load(path, allow_pickle=False)
                return cls(cfg, {n: data[n] for n in data.files}, **kw)
        raise FileNotFoundError(
            "no params payload (%s) under %r" % (", ".join(candidates),
                                                 dirname))

    # -- lifecycle -------------------------------------------------------
    def start(self):
        if self._closed:
            raise EngineClosedError("engine %r is closed" % self.name)
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._loop, daemon=True,
                name="decode-dispatch-%s" % self.name)
            _conc.track_thread(self._thread, self._owner)
            self._thread.start()
        return self

    def stop(self, drain=True, timeout=30.0):
        """Stop admitting work. ``drain=True`` finishes every live slot,
        the fill in progress and every queued request first;
        ``drain=False`` fails them with :class:`EngineClosedError`. Once
        the dispatch thread has ended the slots' state is freed on the
        device. Idempotent."""
        with self._admit_lock:
            self._closed = True
        if not drain:
            self._abort = True
        self._stop_event.set()
        if self._thread is not None and self._thread.is_alive():
            self._thread.join(timeout=max(0.1, float(timeout)))
        if self._thread is None or not self._thread.is_alive():
            self._flush()  # what a thread that died still owed
            self._cache.release()  # nothing runs on the slots' state now
        while True:  # no thread (or it died): fail leftovers loudly
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            req.handle._fail(EngineClosedError(
                "engine %r stopped before prefill" % self.name))
        if self._fill is not None:
            self._end_fill(EngineClosedError(
                "engine %r stopped mid-prefill" % self.name))
        for i, s in enumerate(self._slots):
            if s is not None:
                self._slots[i] = None
                s.handle._fail(EngineClosedError(
                    "engine %r stopped mid-generation" % self.name))
        # a dispatch thread alive past stop() is a leak (violation when
        # the lock sanitizer is armed). The grace window must outlast an
        # in-flight jit trace+compile — chaos kill() joins for only
        # 0.2s, and a slot-composition signature miss can hold the loop
        # in compile for seconds; the poll returns the instant the
        # thread exits, so clean shutdowns never wait.
        _conc.check_stopped(self._owner, grace=10.0)
        obs.event("engine_stop", source="serving", count=False,
                  model=self.name, engine="decode", drained=bool(drain))

    @property
    def media_encoder(self):
        """The model's :class:`~paddle_tpu.models.decode_utils.MediaEncoder`
        (None: requests carry token ids alone)."""
        return self._model.encoder

    # -- admission -------------------------------------------------------
    def _bucket_for(self, plen):
        for b in self.prompt_buckets:
            if b >= plen:
                return b
        return None

    def _check_media(self, prompt, media, session):
        """A request's images against the model's encoder and the prompt:
        -> (media as a list of (patches padded to their bucket (1, bucket,
        patch width), (h, w)), per position the media row it takes or -1).
        Refused in words: images for a model with no encoder, or together
        with what re-runs or ships a prompt as ids alone; a grid the encoder
        does not take (``MediaEncoder.check_grid``) or a patch array that is
        not the grid's; a prompt whose marked positions are not as many as
        the rows the images give."""
        enc, media = self._model.encoder, list(media or ())
        if enc is None:
            raise ValueError(
                "model %r takes no images: it declares no encoder"
                % self.name)
        for feature, on in (
                ("a prefix pool", self._prefix_pool is not None),
                ("a session", session is not None
                 and self._session_tier is not None),
                ("a draft", self._draft is not None)):
            if media and on:
                raise ValueError(
                    "a request with images cannot go through %s: it keeps or "
                    "re-runs a prompt as token ids alone, and a media "
                    "position's row is no token's" % feature)
        if len(media) > enc.max_images:
            raise ValueError("%d images in one request; at most %d"
                             % (len(media), enc.max_images))
        rows = 0
        for k, (patches, (h, w)) in enumerate(media):
            try:
                enc.check_grid(int(h), int(w))
            except ValueError as e:
                raise ValueError("image %d: %s" % (k, e))
            n = int(h) * int(w)
            if (tuple(patches.shape) != (n, enc.patch_width)
                    or patches.dtype != np.uint8):
                raise ValueError(
                    "image %d: patches %s %s are not uint8 (%d, %d)"
                    % (k, patches.dtype, tuple(patches.shape), n,
                       enc.patch_width))
            # padded to its patch bucket here, on the caller's thread: the
            # dispatch loop only hands it over
            fed = np.zeros((1, enc.bucket_for(n), enc.patch_width), np.uint8)
            fed[0, :n] = patches
            media[k] = (fed, (int(h), int(w)))
            rows += enc.rows_of(n)
        marked = prompt == enc.media_id
        if int(marked.sum()) != rows:
            raise ValueError(
                "the prompt marks %d media positions (token id %d) and the "
                "%d images give %d rows" % (int(marked.sum()), enc.media_id,
                                            len(media), rows))
        index = np.where(marked, np.cumsum(marked) - 1, -1).astype(np.int32)
        return media, index

    def _route_request(self, prompt, plen, h):
        """Build a partially-filled :class:`_Request` routed either
        through a resumed session handoff ``h`` (adopt ``h.plen`` rows,
        delta-prefill ``[h.next_token] + prompt``) or the cold path.
        A resume whose geometry no longer fits a delta pass falls back
        to cold-prefilling the full transcript."""
        req = _Request()
        req.base = None
        req.start = 0
        req.suffix = None
        req.sbucket = None
        if h is not None:
            suffix = np.concatenate(
                [[np.int64(h.next_token)], prompt]).astype(np.int64)
            sbucket = self._bucket_for(len(suffix))
            start = int(h.plen)
            expect = (self.cfg.num_layers, self.cache_len,
                      self.cfg.hidden)
            if (tuple(h.shape) == expect and sbucket is not None
                    and start + sbucket <= self.cache_len):
                req.base = h
                req.start = start
                req.suffix = suffix
                req.sbucket = sbucket
                req.prompt = prompt
                req.plen = plen
                req.bucket = None
                req.hist = np.concatenate(
                    [np.asarray(h.prompt, np.int64), suffix])
                self._bump("resumed")
                return req
            # transcript no longer delta-fits: replay it cold
            prompt = np.concatenate(
                [np.asarray(h.prompt, np.int64), suffix])
            plen = int(prompt.size)
        bucket = self._bucket_for(plen)
        if bucket is None:
            raise ValueError(
                "prompt length %d exceeds the largest prompt bucket "
                "(%d) — raise cache_len/prompt_buckets"
                % (plen, self.prompt_buckets[-1]))
        req.prompt = prompt
        req.plen = plen
        req.bucket = bucket
        req.hist = prompt
        return req

    def submit(self, prompt, max_new=None, eos_id=None, deadline_ms=None,
               tenant=None, priority=None, trace_ctx=None, session=None,
               media=None):
        """Enqueue one generation request; returns a
        :class:`DecodeStream`. Raises :class:`ShedError` when the queue
        is full, :class:`EngineClosedError` after ``stop()``, and
        ``ValueError`` for prompts that cannot fit the ladder.
        ``tenant``/``priority`` are carried for observability — the
        disagg router schedules on them; a lone engine records them.
        A sampled ``trace_ctx`` puts this request's queue/prefill/
        per-token spans into its distributed trace.

        ``session`` (with a ``session_tier`` attached) names a
        resumable conversation: when the stream retires, the slot's KV
        rows hibernate to host RAM under that id, and a later submit
        with the same id adopts them back and delta-prefills only the
        new ``prompt`` tokens (the continuation — NOT the transcript
        so far, which the tier already holds). A first-time or evicted
        session cold-prefills ``prompt`` as usual."""
        if self._closed:
            raise EngineClosedError(
                "engine %r is draining/stopped" % self.name)
        if self.role == "decode":
            raise RuntimeError(
                "engine %r is a decode-role (step-only) replica: it "
                "builds no prefill programs — hand it a prefilled KV "
                "cache via submit_prefilled()" % self.name)
        prompt = np.asarray(prompt, dtype=np.int64).reshape(-1)
        plen = int(prompt.shape[0])
        if plen < 1:
            raise ValueError("empty prompt")
        if prompt.min() < 0 or prompt.max() >= self.cfg.vocab:
            raise ValueError(
                "prompt token out of range [0, %d)" % self.cfg.vocab)
        session = None if session is None else str(session)
        media_index = None
        if media or self._model.encoder is not None:
            media, media_index = self._check_media(prompt, media, session)
        h = None
        if session is not None and self._session_tier is not None:
            h = self._session_tier.resume(session)
        try:
            req = self._route_request(prompt, plen, h)
            max_new = (self.default_max_new if max_new is None
                       else int(max_new))
            if max_new < 1:
                raise ValueError("max_new must be >= 1")
            total = (req.start + len(req.suffix) if req.base is not None
                     else req.plen)
            if total + max_new - 1 > self.cache_len:
                raise ValueError(
                    "context %d + max_new %d - 1 exceeds cache_len %d"
                    % (total, max_new, self.cache_len))
        except Exception:
            if h is not None:
                # a failed resume must not lose the hibernated session
                self._session_tier.hibernate(session, h)
            raise
        req.session = session
        req.media, req.media_index = media or None, media_index
        req.max_new = max_new
        req.eos_id = self.eos_id if eos_id is None else eos_id
        req.handoff = None
        req.tenant = tenant
        req.priority = priority
        if deadline_ms is None:
            deadline_ms = self._default_deadline_ms
        req.deadline = (time.monotonic() + float(deadline_ms) / 1000.0
                        if deadline_ms is not None else None)
        sampled = trace_ctx is not None and trace_ctx.sampled
        req.trace = trace_ctx if sampled else None
        req.handle = DecodeStream(
            plen, max_new, stall_timeout_s=self.request_timeout_s)
        req.handle.tenant = tenant
        req.handle.priority = priority
        req.handle.trace = req.trace
        try:
            with self._admit_lock:
                if self._closed:
                    raise EngineClosedError(
                        "engine %r is draining/stopped" % self.name)
                self._q.put_nowait(req)
        except EngineClosedError:
            if h is not None:
                self._session_tier.hibernate(session, h)
            raise
        except queue.Full:
            self._bump("shed")
            if h is not None:
                self._session_tier.hibernate(session, h)
            obs.event("shed", source="serving", model=self.name,
                      engine="decode", prompt_len=plen,
                      queue_capacity=self._q.maxsize)
            raise ShedError(
                "decode queue full (%d) for model %r — request shed"
                % (self._q.maxsize, self.name),
                model=self.name, retry_after=self.retry_after_hint())
        self._bump("requests")
        obs.set_gauge("serving.queue_depth.%s" % self.name,
                      self._q.qsize())
        return req.handle

    def generate(self, prompt, max_new=None, eos_id=None,
                 deadline_ms=None, timeout=None):
        """Synchronous submit + wait; returns the full token list."""
        h = self.submit(prompt, max_new=max_new, eos_id=eos_id,
                        deadline_ms=deadline_ms)
        return h.result(
            timeout if timeout is not None else self.request_timeout_s)

    def submit_prefilled(self, handoff, max_new=None, eos_id=None,
                         deadline_ms=None, tenant=None, priority=None,
                         trace_ctx=None, session=None):
        """Enqueue a generation whose prefill already happened on
        another replica: ``handoff`` is a
        :class:`~paddle_tpu.serving.disagg.kv_wire.KVHandoff` whose KV
        pair is adopted into a free slot (no prefill program runs here
        — works on ``role="decode"`` replicas). The stream's first
        token is the handoff's ``next_token``; ``max_new`` counts it,
        matching :meth:`submit` semantics, so a handoff at ``plen``
        with ``max_new`` N delivers N tokens total."""
        if self._closed:
            raise EngineClosedError(
                "engine %r is draining/stopped" % self.name)
        require_rows_only(self._model, "submit_prefilled (KV hand-over on "
                          "the wire)")
        expect = (self.cfg.num_layers, self.cache_len, self.cfg.hidden)
        if tuple(handoff.shape) != expect:
            raise ValueError(
                "handoff cache shape %r does not match this engine's "
                "geometry %r" % (tuple(handoff.shape), expect))
        plen = int(handoff.plen)
        if plen < 1 or plen > self.cache_len:
            raise ValueError("handoff plen %d outside [1, cache_len=%d]"
                             % (plen, self.cache_len))
        max_new = self.default_max_new if max_new is None else int(max_new)
        if max_new < 1:
            raise ValueError("max_new must be >= 1")
        if plen + max_new - 1 > self.cache_len:
            raise ValueError(
                "handoff plen %d + max_new %d - 1 exceeds cache_len %d"
                % (plen, max_new, self.cache_len))
        req = _Request()
        req.prompt = np.asarray(handoff.prompt, np.int64).reshape(-1)
        req.plen = plen
        req.bucket = None
        req.max_new = max_new
        req.eos_id = self.eos_id if eos_id is None else eos_id
        req.handoff = handoff
        req.media = req.media_index = None
        req.session = None if session is None else str(session)
        req.base = None
        req.start = 0
        req.suffix = None
        req.sbucket = None
        req.hist = req.prompt
        req.tenant = tenant
        req.priority = priority
        if deadline_ms is None:
            deadline_ms = self._default_deadline_ms
        req.deadline = (time.monotonic() + float(deadline_ms) / 1000.0
                        if deadline_ms is not None else None)
        if trace_ctx is None:
            # the handoff's embedded context keeps the prefill-side
            # trace alive across a transport that dropped the kwarg
            trace_ctx = getattr(handoff, "trace", None)
        sampled = trace_ctx is not None and trace_ctx.sampled
        req.trace = trace_ctx if sampled else None
        req.handle = DecodeStream(
            plen, max_new, stall_timeout_s=self.request_timeout_s)
        req.handle.tenant = tenant
        req.handle.priority = priority
        req.handle.trace = req.trace
        try:
            with self._admit_lock:
                if self._closed:
                    raise EngineClosedError(
                        "engine %r is draining/stopped" % self.name)
                self._q.put_nowait(req)
        except queue.Full:
            self._bump("shed")
            obs.event("shed", source="serving", model=self.name,
                      engine="decode", prompt_len=plen, handoff=True,
                      queue_capacity=self._q.maxsize)
            raise ShedError(
                "decode queue full (%d) for model %r — handoff shed"
                % (self._q.maxsize, self.name),
                model=self.name, retry_after=self.retry_after_hint())
        self._bump("requests")
        obs.set_gauge("serving.queue_depth.%s" % self.name,
                      self._q.qsize())
        return req.handle

    # -- admission checks before warmup ----------------------------------
    def check_hbm_budget(self, budget_bytes=None):
        """Price params + the persistent slot cache + the step
        program's transient peak with the static liveness analyzer,
        BEFORE any warmup compile. The cache is priced ONCE: its feeds
        are ``resident_names`` (live across the whole decode region,
        where ordinary activations die at their last reader) and the
        fetches, which the donated feeds' buffers are updated into, are
        ``alias_names`` and cost nothing. The dtype of the feeds drives
        the bytes: int8 residency costs 1 byte/element where fp32 cost
        4, plus the per-row fp32 scale planes and the dequantized fp32
        layer the program holds while it attends — the slot multiplier
        disagg banks on. ``budget_bytes=None`` resolves the device
        capacity from the analyzer's device table; unknown capacity is
        a no-op. Raises ``ProgramVerifyError`` when the engine cannot
        fit."""
        from ..analysis import costs as _costs, memory as _memory
        from ..analysis.diagnostics import ProgramVerifyError
        from ..fluid.executor import _device_kind

        if budget_bytes is None:
            profile = _costs.device_profile(_device_kind())
            budget_bytes = profile.hbm_bytes if profile else None
        if not budget_bytes:
            return None
        # co-resident KV-reuse state eats budget before the step does:
        # an hbm-placed prefix pool reserves its full capacity, a bound
        # draft its params + slot buffer pair
        overhead = 0
        if self._prefix_pool is not None:
            overhead += self._prefix_pool.hbm_bytes()
        if self._draft is not None:
            overhead += self._draft.resident_bytes()
        budget_bytes = budget_bytes - overhead
        pred = self._step_pred
        est = _memory.estimate(
            pred.program, feed_specs=self._step_specs(),
            state_specs=pred._state, fetch_names=pred.fetch_names,
            state_names=set(pred._state), default_dim=self.slots,
            resident_names=pred.donate_feeds,
            alias_names=pred.fetch_names[1:])
        obs.set_gauge(
            "serving.predicted_peak_hbm.%s" % self.name, est.peak_bytes)
        if est.peak_bytes > budget_bytes:
            obs.event("bucket_rejected", source="serving",
                      model=self.name, engine="decode",
                      budget_bytes=int(budget_bytes))
            raise ProgramVerifyError(
                "predicted-oom: decode engine %r needs %.2f MB "
                "(params %.2f MB + resident slot cache + step peak at op "
                "%s '%s') but the HBM budget is %.2f MB — shrink "
                "slots/cache_len or shard the model"
                % (self.name, est.peak_bytes / 1e6,
                   est.param_bytes / 1e6, est.peak_op_index,
                   est.peak_op_type, budget_bytes / 1e6))
        return est

    def check_ladder(self):
        """Lint the (slots, cache_len, prompt-buckets) ladder's
        compiled-program count against the shape-vocabulary budget;
        returns the findings (also recorded as events)."""
        from ..analysis import tpu_lint

        report = tpu_lint.lint_decode_ladder(
            self.prompt_buckets, slot_counts=(self.slots,),
            cache_lens=(self.cache_len,),
            kv_dtypes=(self.kv_dtype,),
            delta_buckets=tuple(sorted(self._delta_preds)),
            spec_blocks=((self._draft.k + 1,)
                         if self._draft is not None else ()),
            draft_buckets=(tuple(self._draft._buckets)
                           if self._draft is not None else ()))
        for d in report.findings:
            obs.event("decode_ladder_lint", source="serving",
                      model=self.name, message=d.message[:200])
        return report.findings

    def warmup(self, check_hbm=True):
        """Pre-build the step program, every prompt-bucket prefill and the
        chunk program of a model that has one through the compile-cache
        disk tier (zero ``compile_start`` on a restarted server). Returns
        the per-program report."""
        if check_hbm:
            self.check_hbm_budget()
        self.check_ladder()
        report = []
        # warm() only compiles: the signature is described, not fed
        source = self._step_pred.warm(self._step_specs())
        report.append({"program": "step", "slots": self.slots,
                       "cache_len": self.cache_len,
                       "kv_dtype": self.kv_dtype, "source": source})
        for b in sorted(self._prefill_preds):
            source = self._prefill_preds[b].warm(self._prefill_feeds(
                np.zeros((1, b), np.int64), 1, b))
            report.append({"program": "prefill", "bucket": b,
                           "source": source})
        if self._chunk_pred is not None:
            source = self._chunk_pred.warm(self._chunk_feeds(
                np.zeros((1, self._model.chunk_rows), np.int64), 1, 0,
                self._jax.eval_shape(self._chunk_zeros)))
            self._jax.block_until_ready(self._chunk_zeros())
            report.append({"program": "chunk",
                           "rows": self._model.chunk_rows, "source": source})
        enc = self._model.encoder
        for b in sorted(self._tower_preds):
            names = self._tower_vars[b]["feed_names"]
            source = self._tower_preds[b].warm({
                names[0]: np.zeros((1, b, enc.patch_width), np.uint8),
                names[1]: np.ones((1, 2), np.int64)})
            # the write of a bucket's rows into a request's buffer
            self._jax.block_until_ready(self._media_write(
                self._media_zeros(), self._jax.numpy.zeros(
                    (enc.rows_of(b), enc.width), self._media_blank.dtype),
                np.int32(0)))
            report.append({"program": "tower", "patches": b,
                           "source": source})
        for b in sorted(self._delta_preds):
            cache1 = (1, self.cfg.num_layers, self.cache_len,
                      self.cfg.hidden)
            source = self._delta_preds[b].warm({
                "gpt_dpre_ids": np.zeros((1, b), np.int64),
                "gpt_dpre_len": np.ones((1, 1), np.int64),
                "gpt_dpre_start": np.zeros((1, 1), np.int64),
                "gpt_dpre_k": np.zeros(cache1, np.float32),
                "gpt_dpre_v": np.zeros(cache1, np.float32)})
            report.append({"program": "delta_prefill", "bucket": b,
                           "source": source})
        if self._verify_pred is not None:
            blk = self._draft.k + 1
            source = self._verify_pred.warm(dict(
                self._cache.feeds(self._verify_vars["cache_feed_names"]),
                gpt_vrf_tok=np.zeros((self.slots, blk), np.int64),
                gpt_vrf_pos=self._pos))
            report.append({"program": "verify", "block": blk,
                           "source": source})
            report.extend(self._draft.warmup())
        obs.event(
            "warmup", source="serving", count=False, model=self.name,
            engine="decode", engines=len(report),
            compiled=sum(1 for r in report if r["source"] == "compile"),
            disk_warm=sum(1 for r in report if r["source"] == "disk"))
        return report

    # -- dispatch loop ---------------------------------------------------
    def _loop(self):
        """The dispatch thread. The loop is software-pipelined by one
        step: after the sync of step n its tokens are on the host and
        are *decided* (next ``_tok`` / ``_pos``, which slots finish; the
        deliveries wait in ``_outbox``), freed slots are refilled
        (admit), step n+1 is dispatched, and only then is step n
        *delivered* to its streams (:meth:`_flush`), so the threads that
        delivery wakes run while the device runs step n+1. Whatever
        fails, retires or abandons streams, and the loop before it idles
        or returns, flushes the outbox first: a stream sees its tokens in
        order, then exactly one end.

        While a fill is in progress (:class:`_Fill`: a long prompt of a
        model that declares a chunk program, admitted beside live streams;
        or a request that carries images) a turn is: ONE unit dispatched
        with no host sync (an image through the encoder, or a chunk, or
        the bucket's whole program), then the step,
        then the delivery as above, so the live streams wait a unit and
        not the whole prompt for their next token. The turn after the last
        unit seats the request (its state into the slot, its first
        token) before anything else is admitted."""
        phase = self._phase_s
        cpu = time.thread_time()
        while True:
            now = time.thread_time()
            phase["loop_cpu_seconds"] += now - cpu
            cpu = now
            filled = phase["prefill_seconds_total"]
            with obs.span("decode.loop.admit") as sp:
                self._sweep_cancelled()
                self._admit()
            # self time: the slot fills it ran have their own total
            phase["admit_seconds"] += sp.seconds - (
                phase["prefill_seconds_total"] - filled)
            if self._abort:
                self._fail_all()
                return
            if self._fill is not None:
                self._fill_unit()
            live = sum(1 for s in self._slots if s is not None)
            if live == 0:
                self._flush()
                if self._fill is not None:
                    continue  # nobody to step for: the next chunk, or the seat
                if self._stop_event.is_set() and self._q.empty():
                    return
                self._idle()
                continue
            if self._draft is not None:
                # the draft + verify block keeps its own order: every
                # token is delivered before the next dispatch
                self._spec_step()
                self._flush()
            else:
                self._step()

    def _idle(self):
        """No slot is live: poll until a request is queued (or the engine
        stops). One span per idle stretch, so an idle engine does not
        fill the span ring; with no live slot and an empty queue a sweep
        and an admission have nothing to do."""
        if _conc._on:
            _conc.note_blocking("time.sleep(idle)")
        with obs.span("decode.loop.idle") as sp:
            while (self._q.empty() and not self._abort
                   and not self._stop_event.is_set()):
                time.sleep(0.002)
        self._phase_s["idle_seconds"] += sp.seconds

    def _fail_all(self):
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            req.handle._fail(EngineClosedError(
                "engine %r stopped before prefill" % self.name))
        if self._fill is not None:
            self._end_fill(EngineClosedError(
                "engine %r stopped mid-prefill" % self.name))
        self._fail_live(EngineClosedError(
            "engine %r stopped mid-generation" % self.name))

    def _sweep_cancelled(self):
        for i, s in enumerate(self._slots):
            if s is not None and s.handle.cancelled:
                self._retire(i, "cancelled")
        f = self._fill
        if f is None:
            return
        # a fill in progress is looked at between its chunks, as a queued
        # request is before its prefill: no chip time for an answer nobody
        # is waiting for
        if f.req.handle.cancelled:
            self._end_fill()
            return
        late = self._deadline_error(f.req, "between chunks of its prefill")
        if late is not None:
            self._end_fill(late)

    def _deadline_error(self, req, where):
        """The error ``req`` fails with if its deadline has passed (counted
        and reported here: it is shed BEFORE more chip time goes into an
        answer nobody is waiting for), else None."""
        now = time.monotonic()
        if req.deadline is None or now <= req.deadline:
            return None
        self._bump("deadline_miss")
        waited_ms = round(1000 * (now - req.handle.t_submit), 3)
        obs.event("deadline_miss", source="serving", model=self.name,
                  engine="decode", waited_ms=waited_ms)
        return DeadlineExceededError(
            "deadline expired after %s ms %s (model %r)"
            % (waited_ms, where, self.name))

    def _admit(self):
        """Prefill queued requests into free slots. A fill in progress
        (:class:`_Fill`) is seated first, once its last chunk is out;
        while it has chunks to go nothing else is admitted, and the
        request that opens one ends the round: further free slots wait
        their turn, as they wait behind one another's whole programs."""
        if self._fill is not None:
            if self._fill.at < self._fill.req.plen:
                return
            self._seat_fill()
        for i in range(self.slots):
            if self._slots[i] is not None:
                continue
            req = None
            while req is None:
                try:
                    req = self._q.get_nowait()
                except queue.Empty:
                    obs.set_gauge(
                        "serving.queue_depth.%s" % self.name,
                        self._q.qsize())
                    return
                if req.handle.cancelled:
                    req.handle._finish("cancelled")
                    self._bump("cancelled")
                    req = None
                    continue
                late = self._deadline_error(req, "in decode queue")
                if late is not None:
                    req.handle._fail(late)
                    req = None
            self._fill_slot(i, req)
            if self._fill is not None:
                break
        obs.set_gauge("serving.queue_depth.%s" % self.name,
                      self._q.qsize())

    def _fill_slot(self, slot, req):
        """Route one admitted request onto its cheapest fill path (remote
        handoff adopt, session-resume delta, prefix-pool full-hit adopt,
        prefix-pool delta, or cold prefill) and run it inside the
        request's fill span; a cold prefill that goes in chunks
        (:meth:`_chunked`) is only opened here, and runs a chunk a turn of
        the loop. The finished ``decode.queue`` wait
        (``t_submit`` to now) is recorded where the request leaves the
        queue; a sampled request's spans also go to its JSONL trace,
        from the same exits."""
        name, ctx, bucket = "decode.prefill", None, None
        if req.handoff is not None:
            # the adopt span parents to the PREFILL side's span when the
            # handoff carries one — that's the cross-process flow arrow
            fill, path = self._adopt, "handoff"
            name, ctx = "decode.adopt", getattr(req.handoff, "trace", None)
        elif req.base is not None:  # session resume (handoff from tier)
            fill, path, bucket = self._delta_prefill, "resume", req.sbucket
        else:
            fill, path, bucket = self._prefill, "cold", req.bucket
            entry = (self._prefix_pool.lookup(req.prompt)
                     if self._prefix_pool is not None else None)
            if entry is not None and self._entry_fits(entry, req):
                req.base = entry
                req.start = entry.plen
                if entry.plen == req.plen:
                    fill, path, bucket = self._adopt_prefix, "pool", None
                else:
                    req.suffix = req.prompt[entry.plen:]
                    req.sbucket = self._bucket_for(len(req.suffix))
                    fill, path, bucket = (self._delta_prefill, "delta",
                                          req.sbucket)
        qctx = obs.record_span(
            "decode.queue", req.handle.t_submit, time.monotonic(),
            ctx=req.trace, proc=self._proc, request=req.handle.id,
            tenant=req.tenant)
        if ctx is None or not ctx.sampled:
            ctx = qctx
        chunked = path == "cold" and self._chunked(req)
        if chunked or (path == "cold" and req.media):
            if ctx is not None and ctx.sampled:
                ctx = ctx.child()  # as a span's entry does
            self._fill = _Fill(
                req, slot, self._chunk_zeros() if chunked else None, ctx,
                chunked=chunked,
                media=self._media_zeros() if req.media else None)
            return
        fields = {}
        if ctx is not None and path == "cold":
            # cost-model annotation of a sampled request's trace only: an
            # unsampled request never runs the analyzer
            fields["predicted_s"] = self._predicted_s("prefill", bucket)
        with obs.span(name, ctx=ctx, proc=self._proc,
                      request=req.handle.id, slot=slot, bucket=bucket,
                      plen=req.plen, path=path, **fields) as sp:
            fill(slot, req, sp)
        self._phase_s["prefill_seconds_total"] += sp.seconds

    def _entry_fits(self, entry, req):
        """A pool entry is adoptable when its geometry matches this
        engine, a FULL hit knows its first token, and a partial hit's
        suffix fits a delta bucket without the block write running off
        the cache edge (dynamic_update_slice clamps — never risk it)."""
        if tuple(np.asarray(entry.k).shape) != (
                self.cfg.num_layers, self.cache_len, self.cfg.hidden):
            return False
        if entry.plen > req.plen:
            return False
        if entry.plen == req.plen:
            return entry.next_token is not None
        sbucket = self._bucket_for(req.plen - entry.plen)
        return (sbucket is not None
                and entry.plen + sbucket <= self.cache_len)

    def _first_token(self, nxt):
        """Wait on the host for the token a fill program produced."""
        with obs.span("decode.prefill.sync") as sp:
            tok = int(np.asarray(nxt)[0, 0])
        self._phase_s["prefill_sync_seconds"] += sp.seconds
        return tok

    def _seat(self, slot, req, sp, tok, pos):
        """The request takes the slot: host-side slot state, and its
        first token to the stream."""
        self._tok[slot, 0] = tok
        self._pos[slot, 0] = pos
        self._slots[slot] = _Slot(req.handle, req.max_new, req.eos_id,
                                  trace=sp.ctx, session=req.session,
                                  hist=req.hist)
        self._draft_fill(slot, req.hist)
        self._bump("tokens")
        self._emit(slot, tok)
        self._gauges()

    def _observe_prefill(self, req, sp):
        obs.observe("serving.decode.prefill_seconds", sp.elapsed())
        obs.observe("serving.decode.ttft_seconds",
                    time.monotonic() - req.handle.t_submit)

    def _prefill_feeds(self, ids, plen, bucket, media=None, index=None):
        """A prefill program's two feeds under its own names; and, for a
        model with an encoder, the media buffer and per position the row it
        takes (a text-only request: the blank buffer, -1 everywhere)."""
        pv = self._prefill_vars[bucket]
        names = pv["feed_names"]
        feeds = {names[0]: ids, names[1]: np.asarray([[plen]], np.int64)}
        self._media_feeds(feeds, pv, ids.shape[1], media, index)
        return feeds

    def _media_feeds(self, feeds, pv, rows, media, index):
        if not pv.get("media_feed_names"):
            return
        at = np.full((1, rows), -1, np.int32)
        if index is not None:
            at[0, :len(index)] = index
        buf, ix = pv["media_feed_names"]
        feeds[buf] = self._media_blank if media is None else media
        feeds[ix] = at

    def _prefill_error(self, e):
        """Count and report a prefill program's dispatch that raised."""
        self._bump("prefill_errors")
        obs.event("prefill_error", source="serving", model=self.name,
                  error="%s: %s" % (type(e).__name__, str(e)[:200]))

    @staticmethod
    def _bucket_ids(req):
        """The prompt right-padded to its bucket, (1, bucket) int64."""
        ids = np.zeros((1, req.bucket), np.int64)
        ids[0, :req.plen] = req.prompt
        return ids

    def _prefill(self, slot, req, sp):
        ids = self._bucket_ids(req)
        try:
            if _conc._on:
                _conc.note_blocking("device.dispatch")
            nxt, *state = self._prefill_preds[req.bucket].run(
                self._prefill_feeds(ids, req.plen, req.bucket),
                return_numpy=False)
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            sp.note(error=type(e).__name__)
            self._prefill_error(e)
            req.handle._fail(e)
            return
        self._bump("prefill_rows_computed", req.bucket)
        self._bump("prefills")
        self._seat_prefilled(slot, req, sp, nxt, state)

    def _seat_prefilled(self, slot, req, sp, nxt, state):
        """What every cold fill ends in, the bucket program's and the last
        chunk's alike: the state into the slot, the wait for the first
        token, the prompt's rows banked, the seat."""
        if self.kv_dtype == "int8":
            # the prefill program stays fp32; quantize per row on the
            # way into the resident buffers (same codec as the wire)
            from .disagg import kv_wire

            k1, v1 = state
            kq, ks = kv_wire.quantize_rows(np.asarray(k1)[0])
            vq, vs = kv_wire.quantize_rows(np.asarray(v1)[0])
            self._cache.write_slot(slot, kq[None], vq[None],
                                   ks[None], vs[None])
        else:
            self._cache.write_slot(slot, *state)
        tok = self._first_token(nxt)
        if self._prefix_pool is not None:
            # bank this prompt's rows (fp32, pre-residency) so the
            # next shared-prefix request adopts instead of recomputing
            k1, v1 = state
            try:
                self._prefix_pool.put(req.prompt, np.asarray(k1),
                                      np.asarray(v1), next_token=tok)
            except Exception:  # noqa: BLE001 — caching is best-effort
                self._bump("prefix_insert_errors")
        self._observe_prefill(req, sp)
        # the prompt's real rows, and those of them an encoder made
        self._bump("fill_rows", req.plen)
        if req.media_index is not None:
            self._bump("media_rows", int((req.media_index >= 0).sum()))
        self._seat(slot, req, sp, tok, req.plen)

    # -- a fill in chunks --------------------------------------------------
    def _chunked(self, req):
        """Whether a cold fill goes in chunks, from what the engine sees:
        the model declared a chunk program; a slot is live (with none there
        is nobody to stall, and the one-shot bucket program is the cheaper
        fill: no carried rows in and out); the prompt's bucket is longer
        than a chunk (else there is nothing to cut); every chunk's rows lie
        inside the cache (a block write past its end would be clamped onto
        earlier rows)."""
        rows = self._model.chunk_rows
        return (self._chunk_pred is not None
                and any(s is not None for s in self._slots)
                and req.bucket > rows
                and -(-req.plen // rows) * rows <= self.cache_len)

    def _chunk_feeds(self, ids, n, start, state, media=None, index=None):
        """The chunk program's feeds under its own names: the ids, the
        chunk's real tokens, the row of its first position, the state (and
        :meth:`_media_feeds`, ``index`` the chunk's own positions')."""
        names = self._chunk_vars["feed_names"]
        feeds = {names[0]: ids, names[1]: np.asarray([[n]], np.int64),
                 names[2]: np.asarray([[start]], np.int64)}
        feeds.update(zip(self._chunk_vars["cache_feed_names"], state))
        self._media_feeds(feeds, self._chunk_vars, ids.shape[1], media, index)
        return feeds

    def _fill_unit(self):
        """The next unit of the fill in progress: its next image through the
        encoder, then its rows a chunk a turn, or (a fill that is not
        chunked) its bucket's program once."""
        f = self._fill
        if f.image < len(f.req.media or ()):
            self._fill_tower()
        elif f.chunked:
            self._fill_chunk()
        else:
            self._fill_bucket()

    def _dispatch_unit(self, sp, dispatch):
        """One unit of the fill in progress: ``dispatch()`` inside its span
        ``sp``, not waited for. A dispatch that raises ends the request as a
        failed prefill does. -> whether it went out."""
        try:
            with sp:
                if _conc._on:
                    _conc.note_blocking("device.dispatch")
                dispatch()
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            self._prefill_error(e)
            self._end_fill(e)
            return False
        finally:
            self._phase_s["prefill_seconds_total"] += sp.seconds
        return True

    def _fill_tower(self):
        """Dispatch the encoder over the fill's next image (padded to its
        patch bucket at submit) and the write of its rows into the request's
        buffer; wait for neither."""
        f, enc = self._fill, self._model.encoder
        fed, (h, w) = f.req.media[f.image]
        n, b = h * w, fed.shape[1]
        names = self._tower_vars[b]["feed_names"]

        def dispatch():
            rows, = self._tower_preds[b].run(
                {names[0]: fed, names[1]: np.asarray([[h, w]], np.int64)},
                return_numpy=False)
            f.media = self._media_write(f.media, rows, np.int32(f.media_at))

        if not self._dispatch_unit(obs.span(
                "serving.decode.tower", ctx=f.ctx, proc=self._proc,
                request=f.req.handle.id, image=f.image, patches=n, bucket=b),
                dispatch):
            return
        f.media_at += enc.rows_of(n)
        f.image += 1
        self._bump("tower_runs")
        self._bump("media_images")
        self._bump("media_patches", n)
        self._bump("tower_pad_patches", b - n)

    def _fill_bucket(self):
        """The whole of a fill that is not chunked, as one unit: its
        bucket's program dispatched with the request's media rows, not
        waited for; the next turn seats it."""
        f = self._fill
        req = f.req

        def dispatch():
            f.nxt, *f.state = self._prefill_preds[req.bucket].run(
                self._prefill_feeds(self._bucket_ids(req), req.plen,
                                    req.bucket, f.media, req.media_index),
                return_numpy=False)

        if not self._dispatch_unit(obs.span(
                "decode.prefill.bucket", ctx=f.ctx, proc=self._proc,
                request=req.handle.id, slot=f.slot, bucket=req.bucket),
                dispatch):
            return
        f.at = req.plen
        self._bump("prefill_rows_computed", req.bucket)
        self._bump("prefills")

    def _fill_chunk(self):
        """Dispatch the next chunk of the fill in progress and do not wait
        for it: the step that follows queues behind it on the device. A
        dispatch that raises ends the request as a failed prefill does."""
        f = self._fill
        req, rows = f.req, self._model.chunk_rows
        n = min(rows, req.plen - f.at)
        ids = np.zeros((1, rows), np.int64)
        ids[0, :n] = req.prompt[f.at:f.at + n]
        feeds = self._chunk_feeds(
            ids, n, f.at, f.state, f.media,
            None if req.media_index is None
            else req.media_index[f.at:f.at + n])
        f.state = None  # consumed by the dispatch, whatever comes of it

        def dispatch():
            f.nxt, *f.state = self._chunk_pred.run(feeds, return_numpy=False)

        if not self._dispatch_unit(obs.span(
                "decode.prefill.chunk", ctx=f.ctx, proc=self._proc,
                request=req.handle.id, slot=f.slot, start=f.at, rows=n),
                dispatch):
            return
        f.at += n
        self._bump("fill_chunks")
        self._bump("prefill_rows_chunked", rows)

    def _fill_span(self, f, **fields):
        """The request's ``decode.prefill`` span, from the record's opening
        to now: into the ring and, for a sampled request, its trace, under
        the context the chunks' spans were children of."""
        t1 = time.monotonic()
        fields.update(proc=self._proc, request=f.req.handle.id, slot=f.slot,
                      bucket=f.req.bucket, plen=f.req.plen,
                      path="chunked" if f.chunked else "media",
                      chunks=(-(-f.at // self._model.chunk_rows)
                              if f.chunked else 0), images=f.image)
        obs.record_span("decode.prefill", f.t0, t1, **fields)
        if f.ctx is not None and f.ctx.sampled:
            obs.export_span("decode.prefill", f.ctx,
                            time.time() - (t1 - f.t0), t1 - f.t0, fields)

    def _seat_fill(self):
        """The last chunk is out: close the record and seat its request.
        The step that went out after that chunk has been waited for, so the
        first token is on its way to the host already."""
        f, self._fill = self._fill, None
        with obs.span("decode.prefill.seat") as sp:
            if f.chunked:
                self._bump("chunked_fills")
            self._seat_prefilled(f.slot, f.req, f, f.nxt, f.state)
        self._phase_s["prefill_seconds_total"] += sp.seconds
        self._fill_span(f)

    def _end_fill(self, error=None):
        """Close the record with no seat: its carried arrays are dropped,
        its slot is free again, and its request ends once: cancelled, or
        failed with ``error``."""
        f, self._fill = self._fill, None
        if error is None:
            f.req.handle._finish("cancelled")
            self._bump("cancelled")
            self._fill_span(f, end="cancelled")
        else:
            f.req.handle._fail(error)
            self._fill_span(f, error=type(error).__name__)

    def _adopt_prefix(self, slot, req, sp):
        """FULL prefix-pool hit: the pool holds rows for the whole
        prompt AND the greedy token after it — adopt and emit with no
        program dispatch at all (zero prefill FLOPs)."""
        entry = req.base
        kd, vd = entry.dense()
        if self.kv_dtype == "int8":
            from .disagg import kv_wire

            if entry.store_dtype == "int8":
                kq, ks = np.asarray(entry.k), np.asarray(entry.k_scales)
                vq, vs = np.asarray(entry.v), np.asarray(entry.v_scales)
            else:
                kq, ks = kv_wire.quantize_rows(kd)
                vq, vs = kv_wire.quantize_rows(vd)
            self._cache.write_slot(slot, kq[None], vq[None],
                                   ks[None], vs[None])
        else:
            self._cache.write_slot(slot, kd[None], vd[None])
        self._bump("prefix_full_hits")
        self._bump("prefill_rows_saved", entry.plen)
        self._observe_prefill(req, sp)
        self._seat(slot, req, sp, int(entry.next_token), req.plen)

    def _delta_prefill(self, slot, req, sp):
        """Adopt ``req.start`` base rows (a prefix-pool entry or a
        hibernated session's handoff) and run the delta-prefill program
        over only the suffix — prefill FLOPs proportional to the
        unshared tail. The base rows feed the program in fp32; int8-
        resident engines requantize the returned cache, which is
        bit-stable on untouched rows (idempotent codec)."""
        base = req.base
        suffix = np.asarray(req.suffix, np.int64).reshape(-1)
        slen = int(suffix.size)
        ids = np.zeros((1, req.sbucket), np.int64)
        ids[0, :slen] = suffix
        try:
            # a hibernated handoff is verified against its sealed
            # digest before any row lands in a slot (same contract as
            # _adopt); pool entries live in-process — their digest is
            # the lookup key, not a seal, and they carry no verify()
            if (getattr(base, "digest", None) is not None
                    and callable(getattr(base, "verify", None))):
                base.verify()
            kd, vd = base.dense()
            if _conc._on:
                _conc.note_blocking("device.dispatch")
            nxt, k1, v1 = self._delta_preds[req.sbucket].run(
                {"gpt_dpre_ids": ids,
                 "gpt_dpre_len": np.asarray([[slen]], np.int64),
                 "gpt_dpre_start": np.asarray([[req.start]], np.int64),
                 "gpt_dpre_k": kd[None], "gpt_dpre_v": vd[None]},
                return_numpy=False)
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            sp.note(error=type(e).__name__)
            self._bump("delta_errors")
            obs.event("delta_error", source="serving", model=self.name,
                      error="%s: %s" % (type(e).__name__, str(e)[:200]))
            req.handle._fail(e)
            return
        if self.kv_dtype == "int8":
            from .disagg import kv_wire

            kq, ks = kv_wire.quantize_rows(np.asarray(k1)[0])
            vq, vs = kv_wire.quantize_rows(np.asarray(v1)[0])
            self._cache.write_slot(slot, kq[None], vq[None],
                                   ks[None], vs[None])
        else:
            self._cache.write_slot(slot, k1, v1)
        tok = self._first_token(nxt)
        self._bump("delta_prefills")
        self._bump("prefill_rows_computed", req.sbucket)
        self._bump("prefill_rows_saved", req.start)
        if self._prefix_pool is not None and req.session is None:
            # extend the pool's coverage to the full prompt (resumed
            # sessions skip this: transcripts are not shared prefixes)
            try:
                self._prefix_pool.put(req.prompt, np.asarray(k1),
                                      np.asarray(v1), next_token=tok)
            except Exception:  # noqa: BLE001 — caching is best-effort
                self._bump("prefix_insert_errors")
        self._observe_prefill(req, sp)
        self._seat(slot, req, sp, tok, req.start + slen)

    def _draft_fill(self, slot, hist):
        """Mirror a freshly filled slot into the draft's cache (the
        draft prefills the same token history). Draft staleness can
        only cost acceptance, never correctness — so a draft prefill
        failure downgrades the slot to effectively non-speculative
        instead of failing the stream."""
        if self._draft is None:
            return
        try:
            self._draft.prefill_slot(slot, hist)
        except Exception as e:  # noqa: BLE001 — speculation is optional
            self._bump("draft_fill_errors")
            obs.event("draft_fill_error", source="serving",
                      model=self.name,
                      error="%s: %s" % (type(e).__name__, str(e)[:200]))

    def _adopt(self, slot, req, sp):
        """Install a remote prefill's :class:`KVHandoff` into a slot —
        the decode half of the disaggregated handoff. An int8 handoff
        whose block is the hidden width drops payload+scales straight
        into an int8-resident engine (no requantize); every other
        combination goes through fp32."""
        h = req.handoff
        sp.note(wire_dtype=h.wire_dtype, wire_bytes=h.wire_bytes())
        try:
            # digest check FIRST: a corrupted handoff must fail the
            # inner stream here (the router's migration path then
            # re-prefills) — never install garbage into a slot
            if getattr(h, "digest", None) is not None:
                h.verify()
            if self.kv_dtype == "int8":
                if h.wire_dtype == "int8":
                    kq, ks = np.asarray(h.k, np.int8), h.k_scales
                    vq, vs = np.asarray(h.v, np.int8), h.v_scales
                else:
                    from .disagg import kv_wire

                    kd, vd = h.dense()
                    kq, ks = kv_wire.quantize_rows(kd)
                    vq, vs = kv_wire.quantize_rows(vd)
                self._cache.write_slot(slot, kq[None], vq[None],
                    np.asarray(ks, np.float32)[None],
                    np.asarray(vs, np.float32)[None])
            else:
                kd, vd = h.dense()
                self._cache.write_slot(slot, kd[None], vd[None])
        except Exception as e:  # noqa: BLE001 — fail the request, not the loop
            sp.note(error=type(e).__name__)
            self._bump("adopt_errors")
            from ..integrity.digest import IntegrityError
            if isinstance(e, IntegrityError):
                obs.inc("integrity.handoff_digest_mismatch")
                obs.event("integrity_violation", source="serving",
                          model=self.name, check="kv_handoff",
                          op="adopt", tensor=e.tensor,
                          error=str(e)[:200])
            obs.event("adopt_error", source="serving", model=self.name,
                      error="%s: %s" % (type(e).__name__, str(e)[:200]))
            req.handle._fail(e)
            return
        obs.observe("serving.disagg.adopt_seconds", sp.elapsed())
        self._bump("adopts")
        self._seat(slot, req, sp, int(h.next_token), req.plen)

    def _decide(self, slot, tok):
        """What the token ``tok`` means for ``slot``, settled on the
        slot's side alone: no stream handle is touched and no thread is
        woken. A sequence that finishes (EOS or length) leaves its slot
        HERE, so the slot is refilled before the next step goes out, and
        whatever its retirement reads of the cache is read here too,
        before that step consumes the buffers. Returns the delivery
        :meth:`_deliver` owes the stream."""
        s = self._slots[slot]
        s.remaining -= 1
        reason = None
        if s.eos_id is not None and tok == s.eos_id:
            reason = "eos"
        elif s.remaining <= 0:
            reason = "length"
        if reason is not None:
            self._vacate(slot, last=tok)
        return (s, slot, tok, reason, None)

    def _deliver(self, delivery):
        """Hand one delivery to its stream: the token (which wakes the
        stream's reader), then, where the sequence ended, its one
        ``done`` or ``err`` with the request's span and totals."""
        s, slot, tok, reason, error = delivery
        if tok is not None:
            s.handle._emit(tok)
            if s.trace is not None:
                # one tiny span per generated token on a SAMPLED request:
                # dur is the inter-token gap (the per-token-p99 SLO leg)
                now = time.monotonic()
                gap = now - s.t_last
                s.t_last = now
                obs.export_span(
                    "decode.token", s.trace.child(), time.time() - gap,
                    gap, {"proc": self._proc, "slot": slot,
                          "index": len(s.handle._tokens),
                          "predicted_s": self._predicted_s("step")})
        if reason is None:
            return
        if error is not None:
            s.handle._fail(error)
        else:
            s.handle._finish(reason)
        self._bump("retired")
        if reason == "cancelled":
            self._bump("cancelled")
        now = time.monotonic()
        obs.observe("serving.decode.request_seconds",
                    now - s.handle.t_submit)
        obs.record_span(
            "decode.stream", s.t_prefill, now, ctx=s.trace,
            proc=self._proc, request=s.handle.id, slot=slot,
            reason=reason, tokens=len(s.handle._tokens))
        with self._stats_lock:
            self._rate.append((now, 1))

    def _emit(self, slot, tok):
        """Decide and deliver one token at once (a fill's first token,
        the verify block's tokens); retires the slot the SAME step when
        the sequence finishes (EOS or length)."""
        self._deliver(self._decide(slot, tok))

    def _flush(self):
        """Deliver the outbox in order, then drop what is left of the
        step whose tokens it held. From the loop this runs right after
        the NEXT step's dispatch: it is the first point at which a
        stream thread is woken, and the device is busy while they run."""
        if not self._outbox and self._spent is None:
            return
        phase = self._phase_s
        with obs.span("decode.step.emit") as sp:
            out, self._outbox = self._outbox, []
            for delivery in out:
                self._deliver(delivery)
            n = sum(tok is not None for _, _, tok, _, _ in out)
            if n:
                self._bump("tokens", n)
            self._gauges()
        phase["emit_seconds"] += sp.seconds
        if self._spent is None:
            return
        # what is left of the step dies here (its token array on the
        # device, the sentinel's replay copy): jaxlib frees device
        # buffers with the GIL released, so every stream thread the
        # delivery just woke runs before this thread has the GIL back.
        # The span stays so the phase is read as it is
        with obs.span("decode.step.release") as sp:
            self._spent = None
        phase["release_seconds"] += sp.seconds

    def _vacate(self, slot, last=None):
        """The slot's side of a retirement: the slot is free. ``last`` is
        the token that ended the sequence (EOS or length), decided but
        not yet in the stream's history: a session's rows then go to the
        tier, while the cache still holds them."""
        s = self._slots[slot]
        if (last is not None and self._session_tier is not None
                and s.session is not None):
            try:
                self._hibernate(slot, s, last)
            except Exception as e:  # noqa: BLE001 — tiering is best-effort
                self._bump("hibernate_errors")
                obs.event("hibernate_error", source="serving",
                          model=self.name, session=s.session,
                          error="%s: %s" % (type(e).__name__,
                                            str(e)[:200]))
        self._slots[slot] = None
        self._tok[slot, 0] = 0
        self._pos[slot, 0] = 0
        return s

    def _retire(self, slot, reason, error=None):
        """End a sequence that no token of its own ended (cancelled,
        failed, shut down): the slot is free now, the stream's end waits
        in the outbox behind the tokens it is still owed."""
        self._outbox.append((self._vacate(slot), slot, None, reason, error))

    def _hibernate(self, slot, s, last):
        """Encode a retiring session slot's live KV rows into the
        KVHandoff wire format and park them in the session tier.
        ``prompt`` carries the token-per-row history (admission history
        + every emitted token but the last), ``next_token`` the last
        emitted token — exactly what the resume delta-prefill consumes
        first — and ``plen`` the written row count. The emitted tokens
        are the stream's own plus ``last``, the one decided and not yet
        delivered. int8-resident engines ship payload + scales verbatim
        (no requantize), fp32 engines encode at the tier's wire dtype."""
        from .disagg import kv_wire

        emitted = np.asarray(s.handle._tokens + [last], np.int64)
        pos = int(self._pos[slot, 0])
        hist = np.concatenate([np.asarray(s.hist, np.int64),
                               emitted[:-1]])
        if hist.size != pos:
            raise ValueError(
                "slot %d history %d rows != pos %d — refusing to "
                "hibernate a misaligned session"
                % (slot, hist.size, pos))
        rows = self._cache.read_slot(slot)  # (L, T, H) per group
        if self.kv_dtype == "int8":
            h = kv_wire.encode_kv_q(*rows, int(emitted[-1]), pos, hist)
        else:
            h = kv_wire.encode_kv(
                *rows, int(emitted[-1]), pos, hist,
                wire_dtype=self._session_tier.wire_dtype)
        self._session_tier.hibernate(s.session, h)
        self._bump("hibernated")

    def _step_specs(self):
        """The step program's feed signature, described (for a compile
        or a cost estimate: nothing is allocated or read)."""
        sds = self._jax.ShapeDtypeStruct
        specs = {self._tok_name: sds(self._tok.shape, self._tok.dtype),
                 self._pos_name: sds(self._pos.shape, self._pos.dtype)}
        specs.update(zip(self._step_vars["cache_feed_names"],
                         self._cache.specs))
        return specs

    def _fail_live(self, error):
        """Fail every live stream, each after the tokens it is still
        owed: nothing stays undelivered behind a failure."""
        for i, s in enumerate(self._slots):
            if s is not None:
                self._retire(i, "error", error=error)
        self._flush()

    def _dispatch_failed(self, error):
        """A step or verify dispatch raised: deliver the previous step's
        tokens, fail every live stream and keep serving (if the dispatch
        had consumed the donated cache, :meth:`SlotCache.run` has
        already replaced it)."""
        self._bump("step_errors")
        obs.event("step_error", source="serving", model=self.name,
                  error="%s: %s" % (type(error).__name__,
                                    str(error)[:200]))
        self._fail_live(error)

    def _run_on_cache(self, pred, names, feeds):
        """:meth:`SlotCache.run`, counting a run that worked on a copy
        of the cache."""
        outs, in_place = self._cache.run(pred, names, feeds)
        if not in_place:
            self._bump("cache_copy_steps")
        return outs

    def _step(self):
        """One turn of the pipelined loop: dispatch this step, deliver
        the step before it (its tokens wait in the outbox since their
        decide) while the device runs this one, sync, decide."""
        phase = self._phase_s
        names = self._step_vars["cache_feed_names"]
        # the SDC sample is decided BEFORE dispatch: the step consumes
        # its cache feeds, so what the sentinel re-dispatches on a
        # sampled replay is a device copy taken now (_tok/_pos mutate at
        # the decide, so they are copied too). Sampled steps only.
        replay = None
        if (self._sentinel is not None
                and self._sentinel.sample(self._sentinel_id)):
            replay = dict(zip(names, self._cache.snapshot()))
            replay[self._tok_name] = self._tok.copy()
            replay[self._pos_name] = self._pos.copy()
            self._bump("cache_copy_steps")
        t0 = time.monotonic()
        sp = obs.span("decode.step.dispatch")
        try:
            with sp:
                # chaos site: a 'slow' clause stalls the step in place
                # (it shows up in step_seconds + the ledger, the
                # autopilot drill's seeded degradation); an exception
                # clause flows to the step_error path below like a real
                # device fault
                R.fault_check("dispatch")
                if _conc._on:
                    _conc.note_blocking("device.dispatch")
                outs = self._run_on_cache(
                    self._step_pred, names,
                    {self._tok_name: self._tok,
                     self._pos_name: self._pos})
        except Exception as e:  # noqa: BLE001 — fail the slots, not the loop
            self._dispatch_failed(e)
            return
        finally:
            phase["dispatch_seconds"] += sp.seconds
        if self._outbox:
            # the step went out with the last one's tokens undelivered
            self._bump("steps_ahead")
        # the first wake-up of a stream thread since the last sync: the
        # other threads' work per token now overlaps the device's step
        self._flush()
        with obs.span("decode.step.sync") as sp:
            nxt_np = np.asarray(outs[0])
        phase["sync_seconds"] += sp.seconds
        with obs.span("decode.step.decide") as sp:
            # the step's latency on the host, from its enqueue to its
            # tokens (the delivery of the step before lies inside it;
            # what the ledger's drift score and the autopilot's
            # calibration read as the measured step time)
            dt = time.monotonic() - t0
            obs.observe("serving.decode.step_seconds", dt)
            self._note_step_measured(dt)
            self._bump("steps")
            live = [i for i, s in enumerate(self._slots) if s is not None]
            if self._model.step_counters is not None:
                # what the step program counted on the device (its fetch
                # after the state), into the lifetime counters
                for key, n in self._model.step_counters(
                        np.asarray(outs[1 + len(names)]),
                        len(live)).items():
                    self._bump(key, int(n))
            sound = replay is None or self._sentinel.replay_check(
                self._sentinel_id,
                lambda: self.sentinel_replay(replay), outs, feeds=replay)
            if sound:
                toks = nxt_np[live, 0]
                self._pos[live, 0] += 1
                self._tok[live, 0] = toks
                self._outbox.extend(map(self._decide, live, toks.tolist()))
        phase["emit_seconds"] += sp.seconds
        if not sound:
            # the step disagreed with its own replay: retire every live
            # slot BEFORE its tokens are decided so a possibly-corrupted
            # token is never delivered; the streams migrate and
            # regenerate on a healthy replica while the sentinel's
            # cross-replica vote adjudicates this one
            from ..integrity.digest import IntegrityError
            self._bump("sdc_disagree")
            self._fail_live(IntegrityError(
                "SDC replay disagreement on decode replica %r — "
                "withholding this step's tokens" % (self._sentinel_id,)))
            return
        # kept until the flush after the next dispatch drops it
        self._spent = (outs, nxt_np, replay)

    def _spec_step(self):
        """One speculative iteration: ``k`` draft proposals per slot,
        ONE target verify dispatch over the ``k + 1`` block, emit the
        longest prefix matching the target's own greedy picks plus the
        correction/bonus token. Every emitted token is the target's
        argmax — bit-exact with :meth:`_step` by construction. Any
        live slot without ``k + 1`` rows of cache headroom demotes the
        whole iteration to the plain step (mirrored into the draft so
        its cache stays gapless)."""
        k = self._draft.k
        blk = k + 1
        live = [i for i, s in enumerate(self._slots) if s is not None]
        if any(int(self._pos[i, 0]) + blk > self.cache_len
               for i in live):
            # cache-edge fallback: single-token step, draft mirrored
            self._bump("spec_fallback_steps")
            try:
                self._draft.sync_step(self._tok, self._pos)
            except Exception:  # noqa: BLE001 — speculation is optional
                self._bump("draft_step_errors")
            self._step()
            return
        phase = self._phase_s
        with obs.span("decode.step.dispatch", spec=True) as sp:
            failed = None
            try:
                proposals = self._draft.propose(self._tok, self._pos)
            except Exception as e:  # noqa: BLE001 — draft down ≠ engine down
                proposals, failed = None, e
            if proposals is not None:
                try:
                    R.fault_check("dispatch")
                    if _conc._on:
                        _conc.note_blocking("device.dispatch")
                    y = self._run_on_cache(
                        self._verify_pred,
                        self._verify_vars["cache_feed_names"],
                        {"gpt_vrf_tok": np.concatenate(
                            [self._tok, proposals], axis=1),
                         "gpt_vrf_pos": self._pos})[0]
                except Exception as e:  # noqa: BLE001 — fail the slots, not the loop
                    failed = e
        phase["dispatch_seconds"] += sp.seconds
        if proposals is None:
            self._bump("draft_step_errors")
            obs.event("draft_step_error", source="serving",
                      model=self.name,
                      error="%s: %s" % (type(failed).__name__,
                                        str(failed)[:200]))
            self._step()
            return
        if failed is not None:
            self._dispatch_failed(failed)
            return
        with obs.span("decode.step.sync", spec=True) as sp_sync:
            y = np.asarray(y)                             # (S, k+1)
        phase["sync_seconds"] += sp_sync.seconds
        obs.observe("serving.spec.round_seconds",
                    sp.seconds + sp_sync.seconds)
        with obs.span("decode.step.emit", spec=True) as sp:
            accepted = self._emit_block(live, k, proposals, y)
        phase["emit_seconds"] += sp.seconds
        self._bump("spec_rounds")
        self._bump("spec_proposed", k * len(live))
        self._bump("spec_accepted", accepted)
        with self._stats_lock:
            proposed = self._stats["spec_proposed"]
            acc = self._stats["spec_accepted"]
        if proposed:
            rate = acc / float(proposed)
            obs.set_gauge("serving.spec.accept_rate", rate)
            obs.set_gauge("serving.spec.accept_rate.%s" % self.name,
                          rate)

    def _emit_block(self, live, k, proposals, y):
        """Emit, per live slot, the longest prefix of the draft's
        proposals matching the target's picks plus the correction/bonus
        token; returns the number of accepted proposals."""
        accepted = n = 0
        for i in live:
            m = 0
            while m < k and proposals[i, m] == y[i, m]:
                m += 1
            accepted += m
            for j in range(m + 1):
                if self._slots[i] is None:
                    break  # EOS/length retired the slot mid-block
                tok = int(y[i, j])
                self._pos[i, 0] += 1
                self._tok[i, 0] = tok
                self._emit(i, tok)
                n += 1
        self._bump("tokens", n)
        self._gauges()
        return accepted

    def _note_step_measured(self, dt):
        """Feed the measured step time into the executable ledger
        (EMA-smoothed) so drift scoring and device auto-calibration see
        live serving numbers, not only bench runs. Best-effort: the
        ledger must never fail a step."""
        try:
            if self._step_fp is None:
                from ..fluid import compile_cache as _cc

                self._step_fp = _cc.fingerprint_or_none(
                    self._step_pred.program) or ""
            if not self._step_fp:
                return
            ema = self._step_ema
            self._step_ema = dt if ema is None else 0.8 * ema + 0.2 * dt
            obs.get_ledger().note_measured(self._step_fp,
                                           self._step_ema)
            if not self._step_noted:
                self._step_noted = True
                self._predicted_s("step")  # pair a prediction with it
        except Exception:  # noqa: BLE001 — telemetry only
            pass

    def _predicted_s(self, kind, bucket=None):
        """Cost-model predicted seconds for one prefill of `bucket` or
        one step, cached; None when the analyzer can't price it (trace
        annotation is best-effort — never fail a request on it). The
        full prediction is also attached to the program's ledger entry,
        arming predicted-vs-measured drift for the autopilot."""
        key = (kind, bucket)
        if key in self._cost_cache:
            return self._cost_cache[key]
        val = None
        try:
            from ..analysis import costs as _costs
            from ..fluid import compile_cache as _cc

            kind_dev = getattr(self._jax.devices()[0], "device_kind",
                               None)
            if kind == "step":
                prog = self._step_pred.program
                feeds = self._step_specs()
            else:
                prog = self._prefill_preds[bucket].program
                feeds = self._prefill_feeds(
                    np.zeros((1, bucket), np.int64), 1, bucket)
            pred = _costs.predict_program(
                prog, feed_specs=feeds, is_test=True,
                device_kind=kind_dev)
            val = pred.get("predicted_step_seconds")
            fp = _cc.fingerprint_or_none(prog)
            if fp:
                obs.get_ledger().note_prediction(fp, pred)
        except Exception:  # noqa: BLE001 — annotation only
            val = None
        self._cost_cache[key] = val
        return val

    def _gauges(self):
        live = sum(1 for s in self._slots if s is not None)
        obs.set_gauge("serving.decode.slot_utilization.%s" % self.name,
                      live / float(self.slots))
        occupancy = float(self._pos.sum()) / (self.slots * self.cache_len)
        obs.set_gauge("serving.decode.cache_occupancy.%s" % self.name,
                      occupancy)

    # -- introspection ---------------------------------------------------
    def _bump(self, key, n=1):
        with self._stats_lock:
            self._stats[key] += n
        # mirror every lifecycle counter into the hub so /metrics sees
        # the same numbers stats() reports
        obs.inc("serving.decode.%s" % key, n)

    def stats(self):
        """Local lifetime counters: requests/tokens/prefills/steps/
        retired/shed/deadline_miss/cancelled/prefill_errors/
        step_errors; ``cache_copy_steps``, the steps that ran on a copy
        of the slot cache (an SDC-sampled step keeps one for its replay;
        any other count says donation did not engage);
        ``cache_reallocs``, the times a failed dispatch cost the cache;
        ``steps_ahead``, the steps dispatched while the step before's
        tokens were still undelivered (all but the first after an idle
        stretch or a failure: the loop is pipelined by one step);
        ``chunked_fills``, the cold fills that went into their slot in
        chunks beside live streams (``prefills`` counts the bucket
        programs' alone), ``fill_chunks`` the chunk programs dispatched for
        them and ``prefill_rows_chunked`` the rows those computed
        (``prefill_rows_computed``: the bucket programs' rows);
        and where the dispatch thread's time went, in seconds, in the
        loop's order: ``admit_seconds`` (self time),
        ``prefill_seconds_total`` (of it ``prefill_sync_seconds`` waiting
        for the device; a chunked fill's share is its chunks' dispatches
        and its seat, not the steps between), ``dispatch_seconds`` (step
        n+1 goes out), ``emit_seconds`` (step n's tokens handed to their
        streams, and the decide after each sync: next feeds, which slots
        finish),
        ``release_seconds`` (what is left of step n dropped; the thread
        waits for the GIL behind the streams it just woke, while the
        device runs step n+1), ``sync_seconds`` (the wait for step n+1's
        tokens), ``idle_seconds`` — the seven sum to the thread's wall
        time; ``loop_cpu_seconds``, that thread's CPU time up to its last
        loop turn (what of the seven it was running, the rest it waited:
        for the device, the GIL, a request), and
        ``process_cpu_seconds``, every thread's (``time.process_time()``
        at this call): less the loop's and the stream readers'
        (``decode.stream.read``'s ``cpu_s``) it is whatever else shares
        the process and its GIL. ``window_kernel_lowerings`` /
        ``window_banded_lowerings``: the process's counts (the hub's
        ``ops.gqa_attention.window_kernel`` / ``.window_banded``) of
        window-attention calls over a sequence longer than the window that
        lowered to the Pallas kernel for the band / to the banded blocks
        through XLA: one a window layer of each prefill program compiled."""
        with self._stats_lock:
            out = dict(self._stats)
        out.update(self._phase_s)
        out["process_cpu_seconds"] = time.process_time()
        for path in ("kernel", "banded"):
            out["window_%s_lowerings" % path] = obs.counter(
                "ops.gqa_attention.window_" + path)
        for k in ("requests", "tokens", "prefills", "adopts", "steps",
                  "retired", "shed", "deadline_miss", "cancelled",
                  "prefill_errors", "adopt_errors", "step_errors",
                  "cache_copy_steps", "steps_ahead",
                  "prefill_rows_computed", "prefill_rows_saved",
                  "chunked_fills", "fill_chunks", "prefill_rows_chunked",
                  "prefix_full_hits", "delta_prefills", "delta_errors",
                  "spec_rounds", "spec_proposed", "spec_accepted",
                  "spec_fallback_steps", "hibernated", "resumed"):
            out.setdefault(k, 0)
        out["spec_accept_rate"] = (
            out["spec_accepted"] / float(out["spec_proposed"])
            if out["spec_proposed"] else None)
        out["cache_reallocs"] = self._cache.reallocs
        out["live_slots"] = sum(1 for s in self._slots if s is not None)
        out["state_bytes_rows"] = self._cache.nbytes("rows")
        out["state_bytes_fixed"] = self._cache.nbytes("fixed")
        out["state_bytes_ring"] = self._cache.nbytes("ring")
        out["slots"] = self.slots
        out["kv_dtype"] = self.kv_dtype
        out["role"] = self.role
        return out

    def reuse_info(self):
        """KV-reuse + speculation state for ``/healthz``
        (:func:`paddle_tpu.serving.registry.info` attaches it):
        draft-model attachment, prefix-pool and session-tier stats,
        and the redundant-prefill economics counters."""
        with self._stats_lock:
            st = dict(self._stats)
        computed = st.get("prefill_rows_computed", 0)
        saved = st.get("prefill_rows_saved", 0)
        proposed = st.get("spec_proposed", 0)
        return {
            "draft": (self._draft.info()
                      if self._draft is not None else None),
            "spec_accept_rate": (
                st.get("spec_accepted", 0) / float(proposed)
                if proposed else None),
            "prefix_pool": (self._prefix_pool.stats()
                            if self._prefix_pool is not None else None),
            "session_tier": (self._session_tier.stats()
                             if self._session_tier is not None
                             else None),
            "prefill_rows_computed": computed,
            "prefill_rows_saved": saved,
            "prefill_rows_saved_pct": (
                100.0 * saved / float(saved + computed)
                if (saved + computed) else None),
        }

    def slot_bytes(self):
        """HBM bytes one slot's resident state occupies: the sum of the
        model's declaration (see :func:`kv_slot_bytes`)."""
        return self._model.slot_bytes()

    def queue_depth(self):
        return self._q.qsize()

    def drain_rate(self):
        """Requests/sec retired over the recent window (None until the
        first retire, or after 30s idle)."""
        now = time.monotonic()
        with self._stats_lock:
            pts = [(t, n) for t, n in self._rate if now - t < 30.0]
        if not pts:
            return None
        span = max(1e-3, now - min(t for t, _ in pts))
        return sum(n for _, n in pts) / span

    def retry_after_hint(self):
        """Seconds until the queue likely drains at the observed retire
        rate (the HTTP 429 ``Retry-After``). Clamped to [1, 60]."""
        rate = self.drain_rate()
        if not rate:
            return 1.0
        return min(60.0, max(1.0, (self.queue_depth() + 1) / rate))

    @property
    def closed(self):
        return self._closed
