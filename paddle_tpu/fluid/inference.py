"""Inference engine — TPU-native rebuild of the reference's native predictor
(ref: paddle/fluid/inference/api/analysis_predictor.cc + api_impl.cc).

The reference interprets the inference ProgramDesc op-by-op with an
analysis/optimization pass pipeline. Here the whole pruned inference program
lowers to ONE pure function that is **AOT-compiled** with `jax.jit(...).
lower(...).compile()` per feed-shape signature: first call pays the XLA
compile, every later call is a single device dispatch with params resident
in HBM (the reference's zero-copy feed/fetch maps to device-resident
weights + host feeds).

    predictor = Predictor.from_model(dirname)          # load_inference_model
    out, = predictor.run({"x": batch})

Also covers the reference's TensorRT-style engine notion: the "engine" is
the compiled XLA executable; `predictor.profile()` reports compile/run
stats.

Engines resolve through ``fluid.compile_cache``'s disk tier when it is
active (``PADDLE_TPU_COMPILE_CACHE_DIR`` or ``compile_cache.activate``):
a fresh process deserializes the AOT artifact per feed signature instead
of paying XLA — the warm-start substrate ``paddle_tpu.serving`` builds
its pre-warmed shape buckets on. ``_get_exec`` is thread-safe: concurrent
callers of one signature serialize on a per-signature lock (one compile),
while different signatures compile in parallel.
"""
import threading
import time

import numpy as np

from . import compile_cache, core
from .executor import (Executor, Scope, global_scope, _device_kind,
                       _ledger_predict, _ledger_register,
                       _publish_analysis_gauges)
from .lowering import build_step_fn
from .. import observability as obs
from ..analysis import concurrency as _conc, dataflow as _dataflow

__all__ = ["Predictor", "create_paddle_predictor"]

_DTYPE_NAMES = {}


def _dtype_name(dtype):
    """``str(dtype)``, remembered: a decode step names the dtype of every
    cache feed on every call."""
    name = _DTYPE_NAMES.get(dtype)
    if name is None:
        name = _DTYPE_NAMES[dtype] = str(dtype)
    return name


class Predictor:
    """AOT-compiled predictor over a pruned inference Program."""

    def __init__(self, program, feed_names, fetch_vars, scope=None,
                 place=None, dtype_policy=None, name=None,
                 donate_feeds=()):
        import jax

        self._jax = jax
        self.program = program
        # names this predictor's XLA module ``jit_fwd_<name>`` in a device
        # trace (``jit_fwd`` without one); part of the disk tier's key
        self.name = name
        self.feed_names = list(feed_names)
        # feeds whose buffers every run CONSUMES: the executable may
        # write its fetches into them (a decode step updating its K/V
        # cache in place), and the caller must not touch one again after
        # ``run``. An argument of the program's owner, not a user knob;
        # empty for every predictor that does not hand its fetches back
        # as the next call's feeds. The ORDER is the owner's: jax pairs
        # donated inputs with same-shaped outputs in order, so a buffer
        # is updated in place only if the feeds are listed in the order
        # of the fetches they come back as.
        self.donate_feeds = tuple(donate_feeds)
        unknown = set(self.donate_feeds) - set(self.feed_names)
        if unknown:
            raise ValueError("donate_feeds %s are not feeds of this "
                             "program" % sorted(unknown))
        self.fetch_names = [
            v.name if hasattr(v, "name") else v for v in fetch_vars
        ]
        self.place = place or core.default_place()
        scope = scope if scope is not None else global_scope()
        persist = {}
        for v in program.list_vars():
            if getattr(v, "persistable", False) and v.name in scope:
                arr = scope[v.name]
                if dtype_policy == "bfloat16" and np.issubdtype(
                    np.asarray(arr).dtype, np.floating
                ):
                    arr = jax.numpy.asarray(arr, jax.numpy.bfloat16)
                persist[v.name] = jax.device_put(arr)
        self._state = persist
        platform = "cpu" if isinstance(self.place, core.CPUPlace) else "tpu"
        self._verify(platform)
        step = build_step_fn(
            program, self.feed_names, self.fetch_names, is_test=True,
            platform=platform,
        )

        donate_names = self.donate_feeds

        def fwd(state, feeds, donated=()):
            # donate_argnums cannot pick entries of one dict, so a
            # donating predictor passes its donated feeds as an argument
            # of their own: a tuple in donate_feeds order (a dict would
            # flatten in the order of its sorted keys)
            feeds = dict(feeds)
            feeds.update(zip(donate_names, donated))
            fetches, _ = step(state, feeds, jax.random.PRNGKey(0))
            return fetches

        self._fwd = fwd
        self._platform = platform
        self._compiled = {}  # shape signature -> executable
        # executable-ledger kind for this predictor's entries; serving
        # engines overwrite it ("serving:<name>", "decode.step:<name>")
        # so the perf CLI attributes executables to their engine
        self.ledger_tag = "predict"
        self.compile_seconds = {}
        # check-then-compile must be atomic per signature: without the
        # locks, N concurrent first callers of one shape all pay (and
        # race to publish) the same XLA compile
        self._lock = threading.Lock()
        self._sig_locks = {}
        self._state_sig = tuple(sorted(
            (k, tuple(v.shape), str(v.dtype)) for k, v in persist.items()))
        # feed dtype coercion targets (mirrors Executor._prepare_feeds):
        # convert ONCE at the prepare step, never again downstream
        block = program.global_block()
        self._want_dtypes = {}
        for n in self.feed_names:
            want = None
            if block.has_var(n):
                var = block.var(n)
                if var.dtype is not None:
                    want = core.np_dtype(var.dtype)
            self._want_dtypes[n] = want

    def _verify(self, platform):
        """Static-analysis gate at construction, BEFORE the first engine
        compile: a broken saved model (dangling param, un-computable
        fetch) fails here with op-attributed diagnostics instead of deep
        inside XLA. ``PADDLE_TPU_ANALYSIS=off|verify|full`` selects the
        depth; analyzer crashes are swallowed (the gate must never break
        a healthy model)."""
        from ..analysis import analyzer as _analyzer

        level = _analyzer.mode()
        if level == "off":
            return
        t0 = time.monotonic()
        try:
            report = _analyzer.analyze(
                self.program, feed_names=self.feed_names,
                fetch_names=self.fetch_names,
                state_names=set(self._state.keys()),
                state_specs=self._state, platform=platform,
                level=level, is_test=True, device_kind=_device_kind())
        except Exception as e:  # noqa: BLE001 — analyzer bug, not user's
            obs.event("analysis_failed", source="predictor",
                      error="%s: %s" % (type(e).__name__, e))
            return
        obs.observe("analysis.verify_seconds", time.monotonic() - t0)
        _publish_analysis_gauges(report)
        _ledger_predict(self.program, report.meta)
        if report.diagnostics:
            obs.inc("analysis.findings", len(report.findings))
            obs.event("analysis_report", source="predictor", count=False,
                      level=level, summary=report.summary())
        report.raise_if_errors()

    @classmethod
    def from_model(cls, dirname, model_filename=None, params_filename=None,
                   **kw):
        """Load a save_inference_model directory (ref api: load + build).

        Params land in a **private scope** per predictor (unless an
        explicit ``scope=`` is passed): two loaded models with
        overlapping var names — every default-named ``fc_0.w_0``, every
        BN stat — must not clobber each other through the process-wide
        ``global_scope()``."""
        from .io import load_inference_model

        exe = Executor(core.CPUPlace())
        scope = kw.pop("scope", None)
        if scope is None:
            scope = Scope()
        program, feed_names, fetch_vars = load_inference_model(
            dirname, exe, model_filename, params_filename, scope=scope
        )
        return cls(program, feed_names, fetch_vars, scope=scope, **kw)

    def _prepare(self, feeds):
        """Normalize one request: dict (or feed_names-aligned list) ->
        ({name: array}, shape signature). Each feed is converted at most
        ONCE — committed device arrays pass through untouched instead of
        bouncing off the host — and coerced to the program's declared
        feed dtype."""
        if not isinstance(feeds, dict):
            feeds = dict(zip(self.feed_names, feeds))
        jax = self._jax
        prepared = {}
        for n in self.feed_names:
            v = feeds[n]
            want = self._want_dtypes.get(n)
            if isinstance(v, jax.ShapeDtypeStruct):
                pass  # a described feed: enough for warm(), which compiles
            elif isinstance(v, jax.Array):
                if want is not None and v.dtype != want:
                    v = v.astype(want)
            else:
                v = np.asarray(v)
                if want is not None and v.dtype != want:
                    v = v.astype(want)
            prepared[n] = v
        sig = tuple(
            (n, tuple(prepared[n].shape), _dtype_name(prepared[n].dtype))
            for n in self.feed_names
        )
        return prepared, sig

    def _sig(self, feeds):
        return self._prepare(feeds)[1]

    def _get_exec(self, feeds):
        prepared, sig = self._prepare(feeds)
        return self._ensure_exec(sig, prepared)[0]

    def _ensure_exec(self, sig, prepared):
        """The executable for `sig`, building it if needed. Returns
        ``(executable, source)`` with source one of ``"memory"`` /
        ``"disk"`` (compile-cache tier hit, no XLA) / ``"compile"``."""
        ex = self._compiled.get(sig)
        if ex is not None:
            return ex, "memory"
        with self._lock:
            sig_lock = self._sig_locks.setdefault(sig, threading.Lock())
        with sig_lock:
            ex = self._compiled.get(sig)
            if ex is not None:  # lost the race: the winner already built it
                return ex, "memory"
            jax = self._jax
            source = "compile"
            disk_key = None
            if compile_cache.enabled():
                try:
                    disk_key = compile_cache.entry_key(
                        self.program, self.feed_names, self.fetch_names,
                        sig, self._state_sig, self._platform,
                        kind="predict", name=self.name,
                        donated=self.donate_feeds)
                except compile_cache.Unfingerprintable:
                    disk_key = None
                else:
                    ex = compile_cache.load(disk_key)
                    if ex is not None:
                        source = "disk"
                        if self.donate_feeds:
                            # jax.export drops donation: put it back
                            # around the exported call, so a disk hit
                            # never runs this program on a copied cache
                            ex = self._jit(ex)
                        _ledger_register(self.program, self.ledger_tag,
                                         ex, "disk",
                                         donated=self.donate_feeds)
            if ex is None:
                obs.event("compile_start", source="predictor", count=False,
                          sig=repr(sig))
                t0 = time.monotonic()
                jitted = self._jit(self._fwd)
                args = self._call_args(prepared)
                ex = jitted.lower(*args).compile()
                dt = time.monotonic() - t0
                self.compile_seconds[sig] = dt
                obs.observe("predictor.compile_seconds", dt)
                obs.event("compile_done", source="predictor", count=False,
                          sig=repr(sig), seconds=round(dt, 6))
                _ledger_register(self.program, self.ledger_tag, ex,
                                 "compile", compile_seconds=dt,
                                 donated=self.donate_feeds)
                if disk_key is not None:
                    compile_cache.store(disk_key, jitted, args)
            with self._lock:
                self._compiled[sig] = ex
            return ex, source

    def _jit(self, fn):
        """``fn`` jitted under the name this predictor's module should
        carry (jax names a module after the function it is given), its
        donated feeds (argument 2, see :meth:`_call_args`) donated."""
        def named(*args):
            return fn(*args)

        named.__name__ = named.__qualname__ = (
            "fwd_%s" % self.name if self.name else "fwd")
        return self._jax.jit(
            named, donate_argnums=(2,) if self.donate_feeds else ())

    def _call_args(self, prepared):
        """The executable's arguments: ``(state, feeds)``, or ``(state,
        kept feeds, donated feeds)`` for a donating predictor."""
        if not self.donate_feeds:
            return self._state, prepared
        kept = {n: v for n, v in prepared.items()
                if n not in self.donate_feeds}
        return (self._state, kept,
                tuple(prepared[n] for n in self.donate_feeds))

    def warm(self, feeds):
        """Ensure the executable for this feed signature exists without
        dispatching it; returns where it came from (``"memory"`` /
        ``"disk"`` / ``"compile"``). The serving engine pre-warms its
        shape buckets through this at model-load time."""
        prepared, sig = self._prepare(feeds)
        return self._ensure_exec(sig, prepared)[1]

    def run(self, feeds, return_numpy=True):
        """feeds: dict name -> array (or list aligned with feed_names).
        The buffers of ``donate_feeds`` are consumed: a device array
        passed for one is deleted when this returns (or raises after
        the dispatch)."""
        prepared, sig = self._prepare(feeds)
        ex = self._ensure_exec(sig, prepared)[0]
        if self.donate_feeds and _conc._on:
            # the registry's "scope" is whoever owns the named buffers:
            # for donated feeds, this predictor
            _dataflow.note_donation(self, self.donate_feeds)
        outs = ex(*self._call_args(prepared))
        if return_numpy:
            outs = [np.asarray(o) for o in outs]
        return list(outs)

    __call__ = run

    def profile(self):
        return {
            "n_engines": len(self._compiled),
            "compile_seconds": dict(self.compile_seconds),
            "n_params": len(self._state),
        }


class AnalysisConfig:
    """Deployment config (ref: paddle/fluid/inference/api/
    paddle_analysis_config.h via core.AnalysisConfig). The reference's
    IR analysis passes / TensorRT / MKLDNN toggles are replaced by XLA's
    own pass pipeline; device selection maps to the jit platform. Knobs
    that can't apply on this stack are accepted and recorded so
    deployment scripts run unchanged."""

    def __init__(self, model_dir=None, params_file=None):
        self.model_dir = model_dir
        self.params_file = params_file
        self._use_gpu = False
        self._device_id = 0
        self._switches = {}

    # -- device ----------------------------------------------------------
    def enable_use_gpu(self, memory_pool_init_size_mb=100, device_id=0):
        # "gpu" in deployment scripts means "the accelerator": TPU here
        self._use_gpu = True
        self._device_id = device_id

    def disable_gpu(self):
        self._use_gpu = False

    def use_gpu(self):
        return self._use_gpu

    def gpu_device_id(self):
        return self._device_id

    # -- accepted no-op switches (XLA subsumes these passes) -------------
    def switch_ir_optim(self, x=True):
        self._switches["ir_optim"] = x

    def enable_tensorrt_engine(self, **kw):
        self._switches["tensorrt"] = kw

    def enable_mkldnn(self):
        self._switches["mkldnn"] = True

    def switch_use_feed_fetch_ops(self, x=False):
        self._switches["feed_fetch_ops"] = x

    def switch_specify_input_names(self, x=True):
        self._switches["specify_input_names"] = x

    def set_cpu_math_library_num_threads(self, n):
        self._switches["cpu_threads"] = n


def create_paddle_predictor(config_or_dirname, **kw):
    """ref inference api: create_paddle_predictor(AnalysisConfig | dir)."""
    if isinstance(config_or_dirname, str):
        return Predictor.from_model(config_or_dirname, **kw)
    if isinstance(config_or_dirname, AnalysisConfig):
        cfg = config_or_dirname
        if not cfg.model_dir:
            raise ValueError("AnalysisConfig has no model_dir set")
        from . import core

        place = core.TPUPlace() if cfg.use_gpu() else core.CPUPlace()
        return Predictor.from_model(cfg.model_dir, place=place, **kw)
    raise TypeError(
        "pass an AnalysisConfig or a save_inference_model dirname"
    )
