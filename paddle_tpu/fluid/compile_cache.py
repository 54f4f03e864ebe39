"""Persistent AOT compile cache: a disk tier under the Executor's
in-memory executable LRU.

Cold compiles dominate short runs (tens of seconds against a step of
tens of milliseconds), and every new process — a TrainGuard
crash-resume, a repeated benchmark run, a re-queued job — pays them
again. This module makes the compile a one-time cost per
*machine*: after the in-memory LRU misses, the executor asks the disk
tier for the program's AOT artifact (the StableHLO module serialized
via ``jax.export``) before tracing anything; a hit deserializes in
milliseconds and emits **no** ``compile_start`` event.

Activation — either of:

- ``PADDLE_TPU_COMPILE_CACHE_DIR=/path`` in the environment, or
- :func:`activate` (``TrainGuard`` calls it to co-locate the cache with
  its checkpoint directory, see ``parallel.checkpoint.compile_cache_dir``).

This AOT tier is off by default. A disk hit runs the deserialized
``jax.export`` module, which does **not** donate its inputs
(:class:`_DiskEntry`): it is not the executable a cold process runs, so
a benchmark leaves this tier off or says which tier served each compile.
The exception is a ``Predictor`` with ``donate_feeds``: it wraps its
disk entry in a ``jax.jit`` that donates those feeds again, because a
decode step that silently ran on a copied K/V cache would double the
engine's memory.

jax's own persistent XLA compilation cache is a separate tier placed by
:func:`configure_xla_cache` — the one function in the repository that
decides its directory: ``JAX_COMPILATION_CACHE_DIR`` when the
environment sets it (then nothing is set in code), else the fixed
``<checkout>/.jax_cache``. Entry points (``chip_smoke.py``,
``benchmark/run.py`` and ``TrainGuard(compile_cache=...)``) call it;
importing the package does not.

Cache entries are content-addressed: the key hashes the program's
*structural* fingerprint (op types/slots/attrs, var shapes/dtypes —
NOT the process-local ``Program._uid``) together with the feed/fetch/
state signature, the lowering platform, the device kind, and the
jax/jaxlib versions plus a format version — an upgrade simply misses
and re-fills. Writes are atomic (unique tmp + ``os.replace``) so two
processes sharing a directory never see torn blobs; a corrupt or
unreadable entry is evicted and falls back to a normal recompile.

Programs that cannot be fingerprinted stably (e.g. ``py_func`` ops
holding Python callables) or whose export fails (unexportable custom
calls) silently skip the disk tier — the in-memory LRU still works.

Consumers: ``executor.run`` and ``_run_dataset_scan`` (training step
executables), ``fluid.inference.Predictor`` (``kind="predict"``
entries, one per feed-shape signature), and through the predictor the
serving engine's shape-bucket warmup (``paddle_tpu.serving``) — a
restarted server deserializes its whole bucket ladder instead of
compiling.

Every entry is sealed in an integrity envelope
(:mod:`paddle_tpu.integrity.envelope`): a content digest is verified
*before* ``jax.export`` deserialization, so a bitflipped blob is caught
by the digest check rather than by whatever the deserializer happens to
notice. Both failure classes share the evict-and-recompile path but are
counted separately — ``compile_cache.corrupt_digest`` (envelope check
failed) vs ``compile_cache.corrupt_deserialize`` (digest fine, decoder
rejected it; points at a format/version skew, not disk rot) — with
``compile_cache.corrupt`` as the total. Reads and writes route through
the ``load`` / ``save`` corruption fault sites
(:func:`paddle_tpu.fluid.resilience.fault_corrupt`) for chaos drills.

Telemetry (``paddle_tpu.observability``): ``compile_cache.disk_hit`` /
``disk_miss`` / ``corrupt`` / ``corrupt_digest`` /
``corrupt_deserialize`` / ``store`` / ``store_error`` counters and
``compile_cache.deserialize_seconds`` / ``serialize_seconds``
histograms.
"""
import hashlib
import os
import threading
import time
import uuid
import warnings

import numpy as np

from .. import observability as obs

__all__ = [
    "CACHE_DIR_ENV", "Unfingerprintable", "activate", "cache_dir",
    "configure_xla_cache", "enabled", "entry_key", "fingerprint_or_none",
    "has", "load", "program_fingerprint", "store",
]

CACHE_DIR_ENV = "PADDLE_TPU_COMPILE_CACHE_DIR"
# fixed, never built from a temp name, pid or time: the directory is
# part of the cache key, so one that moves never hits
_XLA_CACHE_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
# v2: entries are sealed in an integrity envelope (digest-before-
# deserialize); v1 blobs simply miss under the new keys and re-fill.
_FORMAT_VERSION = 2
_SUFFIX = ".jaxexp"
_ENTRY_KIND = "compile-cache"

_lock = threading.Lock()
_default_dir = None     # programmatic activation (TrainGuard co-location)
_warned_store = False


class Unfingerprintable(ValueError):
    """The program holds state that has no stable cross-process identity
    (a Python callable attr, an unknown attr type) — the disk tier is
    skipped for it."""


def cache_dir():
    """The active cache directory: the env var wins, then a programmatic
    :func:`activate`, else None (disk tier off)."""
    return os.environ.get(CACHE_DIR_ENV) or _default_dir


def enabled():
    return cache_dir() is not None


def activate(path):
    """Programmatically enable the disk tier at `path` (the env var, when
    set, still wins — an operator override beats code defaults). Returns
    the previously configured default. jax's own XLA cache is a separate
    tier: see :func:`configure_xla_cache`."""
    global _default_dir
    with _lock:
        prev, _default_dir = _default_dir, (
            os.path.abspath(path) if path else None)
    return prev


def configure_xla_cache():
    """Place jax's persistent XLA compilation cache and return its
    directory. With ``JAX_COMPILATION_CACHE_DIR`` in the environment jax
    has already taken the directory from there and nothing is set in
    code; otherwise the directory is the fixed ``<checkout>/.jax_cache``.
    The minimum compile time for an entry stays jax's own default (1 s)
    either way. Idempotent."""
    import jax

    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env   # jax read it at import; nothing is set here
    if jax.config.jax_compilation_cache_dir != _XLA_CACHE_DEFAULT:
        jax.config.update("jax_compilation_cache_dir", _XLA_CACHE_DEFAULT)
    return _XLA_CACHE_DEFAULT


# ---------------------------------------------------------------------------
# fingerprinting
# ---------------------------------------------------------------------------

def _stable(v):
    """A stable textual identity for an op attr / var field. Raises
    Unfingerprintable for values with no cross-process identity."""
    if v is None or isinstance(v, (bool, int, str, bytes)):
        return repr(v)
    if isinstance(v, float):
        return repr(float(v))
    if isinstance(v, (np.bool_, np.integer, np.floating)):
        return repr(v.item())
    if isinstance(v, (list, tuple)):
        return "[%s]" % ",".join(_stable(x) for x in v)
    if isinstance(v, dict):
        return "{%s}" % ",".join(
            "%s:%s" % (repr(k), _stable(v[k]))
            for k in sorted(v, key=repr))
    if isinstance(v, np.ndarray):
        return "nd(%s,%s,%s)" % (
            v.shape, v.dtype,
            hashlib.sha256(np.ascontiguousarray(v).tobytes()).hexdigest())
    if isinstance(v, np.dtype):
        return "dtype(%s)" % v
    raise Unfingerprintable(
        "attr of type %s has no stable cross-process identity"
        % type(v).__name__)


def program_fingerprint(program):
    """Content hash of the program graph: op types, input/output slot
    wiring, attrs, and var metadata across every block. Stable across
    processes (unlike ``Program._uid``); cached on the program keyed by
    its ``_version`` so repeat misses don't re-walk the graph."""
    cached = getattr(program, "_fingerprint_cache", None)
    if cached is not None and cached[0] == program._version:
        return cached[1]
    h = hashlib.sha256()
    for blk in program.blocks:
        h.update(b"blk")
        for name in sorted(blk.vars):
            v = blk.vars[name]
            h.update(("v:%s|%s|%s|%s|%s|%s\n" % (
                name, v.shape, v.dtype, v.type, int(v.persistable),
                v.lod_level)).encode())
        for op in blk.ops:
            h.update(("o:%s\n" % op.type).encode())
            for slot in sorted(op.inputs):
                h.update(("i:%s=%s\n" % (slot, op.inputs[slot])).encode())
            for slot in sorted(op.outputs):
                h.update(("u:%s=%s\n" % (slot, op.outputs[slot])).encode())
            for k in sorted(op.attrs):
                if k.startswith("_"):
                    continue  # provenance/bookkeeping, not semantics
                h.update(("a:%s=%s\n" % (k, _stable(op.attrs[k]))).encode())
    fp = h.hexdigest()
    program._fingerprint_cache = (program._version, fp)
    return fp


def fingerprint_or_none(program):
    """:func:`program_fingerprint`, degraded to None instead of raising
    — the identity key observability consumers (the executable ledger)
    use, where an unfingerprintable program just means an anonymous
    entry, never a failed step."""
    try:
        return program_fingerprint(program)
    except Exception:  # noqa: BLE001 — ledger identity is best-effort
        return None


def _device_fingerprint():
    import jax
    import jaxlib

    d = jax.devices()[0]
    return "%s|%s|jax=%s|jaxlib=%s|fmt=%d" % (
        d.platform, getattr(d, "device_kind", ""), jax.__version__,
        jaxlib.__version__, _FORMAT_VERSION)


def entry_key(program, feed_names, fetch_names, feed_sig, state_sig,
              platform, kind="step", name=None, donated=()):
    """The content-addressed disk key for one compiled specialization.
    Raises :class:`Unfingerprintable` when the program can't be hashed
    stably (caller skips the disk tier). ``name`` is the name the module
    was compiled under (``Predictor(name=)``): an artifact keeps the
    name it was exported with, so another name is another entry.
    ``donated`` is the set of feeds the caller donates
    (``Predictor(donate_feeds=)``): they are an argument of their own
    of the exported function, so another set is another entry."""
    h = hashlib.sha256()
    h.update(program_fingerprint(program).encode())
    h.update(repr((kind, platform, list(feed_names), list(fetch_names),
                   feed_sig, state_sig) + ((name,) if name else ())
                  + ((("donated",) + tuple(sorted(donated)),)
                     if donated else ())).encode())
    h.update(_device_fingerprint().encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# the disk tier
# ---------------------------------------------------------------------------

class _DiskEntry:
    """Adapter giving a deserialized ``jax.export.Exported`` the same
    call surface as an AOT-compiled executable: ``entry(state, feeds,
    rng) -> (fetches, new_state)``. Note: a deserialized call does not
    donate input buffers (export drops donation) — a minor memory/perf
    cost relative to the compile it skips."""

    __slots__ = ("_exported", "key")

    def __init__(self, exported, key):
        self._exported = exported
        self.key = key

    def __call__(self, *args):
        return self._exported.call(*args)


def _entry_path(key):
    return os.path.join(cache_dir(), key + _SUFFIX)


def has(key):
    """Whether an artifact for `key` is on disk, without deserializing
    it (and without touching the hit/miss counters) — the cheap probe
    warm-start reporting uses. False when the disk tier is off."""
    d = cache_dir()
    return d is not None and os.path.exists(_entry_path(key))


def _evict_corrupt(path, key, check, error):
    """Shared corrupt-entry path: count which check failed (the
    envelope digest vs the jax.export deserializer), event it, and
    evict so a recompile fills the entry back."""
    obs.inc("compile_cache.corrupt")
    obs.inc("compile_cache.corrupt_%s" % check)
    obs.event("compile_cache_corrupt", source="executor", count=False,
              key=key, check=check,
              error="%s: %s" % (type(error).__name__, error))
    try:
        os.remove(path)
    except OSError:
        pass


def load(key):
    """Fetch the compiled artifact for `key` from disk, or None. Hits
    verify the envelope digest, then deserialize via ``jax.export``;
    corrupt/unreadable entries are removed and treated as misses
    (recompile fills them back), counting which check caught them."""
    from ..integrity import envelope
    from .resilience import fault_corrupt

    d = cache_dir()
    if d is None:
        return None
    path = _entry_path(key)
    try:
        with open(path, "rb") as f:
            raw = fault_corrupt("load", f.read())
    except OSError:
        obs.inc("compile_cache.disk_miss")
        return None
    t0 = time.monotonic()
    try:
        blob = envelope.unseal_bytes(raw, kind=_ENTRY_KIND, path=path)
    except IOError as e:  # IntegrityError — digest caught it first
        _evict_corrupt(path, key, "digest", e)
        return None
    try:
        from jax import export as jax_export

        entry = _DiskEntry(jax_export.deserialize(blob), key)
    except Exception as e:  # noqa: BLE001 — corrupt entry == miss
        _evict_corrupt(path, key, "deserialize", e)
        return None
    dt = time.monotonic() - t0
    obs.inc("compile_cache.disk_hit")
    obs.observe("compile_cache.deserialize_seconds", dt)
    obs.event("compile_cache_hit", source="executor", count=False,
              key=key, seconds=round(dt, 6), bytes=len(blob))
    return entry


def store(key, jitted, args):
    """Serialize the jitted function's AOT lowering for `args` to disk
    under `key` (atomic tmp+rename; concurrent writers race benignly —
    last replace wins with identical content). Failures warn once and
    are otherwise ignored: the cache is an optimization, never a
    correctness dependency."""
    from ..integrity import envelope
    from .resilience import fault_corrupt

    global _warned_store
    d = cache_dir()
    if d is None:
        return False
    t0 = time.monotonic()
    try:
        from jax import export as jax_export

        blob = jax_export.export(jitted)(*args).serialize()
        sealed = fault_corrupt(
            "save", envelope.seal_bytes(blob, kind=_ENTRY_KIND))
        os.makedirs(d, exist_ok=True)
        path = _entry_path(key)
        tmp = "%s.tmp.%d.%s" % (path, os.getpid(), uuid.uuid4().hex[:8])
        with open(tmp, "wb") as f:
            f.write(sealed)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except Exception as e:  # noqa: BLE001 — never fail a step over the cache
        obs.inc("compile_cache.store_error")
        if not _warned_store:
            _warned_store = True
            warnings.warn(
                "compile cache store failed (%s: %s); this program will "
                "recompile in future processes" % (type(e).__name__, e))
        return False
    dt = time.monotonic() - t0
    obs.inc("compile_cache.store")
    obs.observe("compile_cache.serialize_seconds", dt)
    obs.event("compile_cache_store", source="executor", count=False,
              key=key, seconds=round(dt, 6), bytes=len(blob))
    return True
