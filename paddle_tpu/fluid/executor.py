"""Executor + Scope.

TPU-native analogue of ref python/paddle/fluid/executor.py (Executor) and
paddle/fluid/framework/scope.cc. The Scope holds device-resident jax arrays;
Executor.run lowers the Program once per (program version, feed signature)
into a jitted step function with donated state, then replays it — so steady-
state training is a single XLA executable launch per iteration.
"""
import collections
import os
import time
import warnings

import numpy as np

import jax
import jax.numpy as jnp

from . import compile_cache
from . import core
from . import framework
from .framework import Program, Variable, default_main_program
from .lowering import OpLoweringError, build_step_fn
from .resilience import fault_check
from .. import observability as obs
from ..observability import runhealth as _runhealth
# stdlib-only runtime guard (PADDLE_TPU_SCOPE_SANITIZER); the hot-path
# cost with the sanitizer off is one module-bool check per Scope write
from ..analysis import concurrency as _conc
from ..analysis import sanitizer as _sanitizer

__all__ = ["Executor", "Scope", "global_scope", "scope_guard"]


def _device_kind():
    """The jax device kind the analysis gate prices against."""
    return jax.devices()[0].device_kind


def _publish_analysis_gauges(report):
    """Mirror the analyzer's quantitative meta into the telemetry hub
    (documented in observability.__init__: analysis.predicted_*)."""
    peak = report.meta.get("predicted_peak_hbm_bytes")
    if peak is not None:
        obs.set_gauge("analysis.predicted_peak_hbm", peak)
    mfu = report.meta.get("predicted_mfu")
    if mfu is not None:
        obs.set_gauge("analysis.predicted_mfu", mfu)


def _ledger_register(program, kind, compiled, source,
                     compile_seconds=None, donated=None):
    """Register one executable in the process-wide ledger (best effort
    — the observatory must never break a step)."""
    try:
        obs.get_ledger().register(
            kind=kind,
            fingerprint=compile_cache.fingerprint_or_none(program),
            compiled=compiled, source=source,
            compile_seconds=compile_seconds, donated=donated)
    except Exception:  # noqa: BLE001 — ledger is observability only
        pass


def _ledger_predict(program, meta):
    """Attach the analyzer's prediction to the program's fingerprint so
    the ledger can report predicted-vs-XLA-vs-measured drift."""
    try:
        fp = compile_cache.fingerprint_or_none(program)
        if fp is not None:
            obs.get_ledger().note_prediction(fp, meta)
    except Exception:  # noqa: BLE001
        pass


class _TensorView:
    """Compat shim for `scope.find_var(name).get_tensor()` usage."""

    def __init__(self, scope, name):
        self._scope = scope
        self._name = name

    def get_tensor(self):
        return self._scope[self._name]

    def set(self, value, place=None):
        self._scope.set(self._name, value)

    def __array__(self, dtype=None):
        arr = np.asarray(self._scope[self._name])
        return arr.astype(dtype) if dtype else arr


class Scope:
    """name -> device array mapping (device-resident between runs)."""

    def __init__(self, parent=None):
        self._vars = {}
        self._parent = parent
        self._kids = []

    def set(self, name, value):
        self._vars[name] = value
        if _sanitizer._on:
            _sanitizer.record_write(self, name)

    def __getitem__(self, name):
        return self._vars[name]

    def __contains__(self, name):
        return name in self._vars

    def get(self, name, default=None):
        return self._vars.get(name, default)

    def keys(self):
        return self._vars.keys()

    def items(self):
        return self._vars.items()

    def pop(self, name, default=None):
        return self._vars.pop(name, default)

    def find_var(self, name):
        """Look up a var here or in any ancestor scope (ref
        framework/scope.cc Scope::FindVar parent-chain semantics)."""
        scope = self
        while scope is not None:
            if name in scope._vars:
                return _TensorView(scope, name)
            scope = scope._parent
        return None

    def find_value(self, name, default=None):
        """Parent-chain value lookup (FindVar semantics, raw value)."""
        scope = self
        while scope is not None:
            if name in scope._vars:
                return scope._vars[name]
            scope = scope._parent
        return default

    def update(self, name, value):
        """Write to the scope in the chain that owns `name` (the reference
        executor updates the variable FindVar resolves, not a shadow copy
        in the child scope); falls back to a local set for new names."""
        scope = self
        while scope is not None:
            if name in scope._vars:
                scope._vars[name] = value
                if _sanitizer._on:
                    _sanitizer.record_write(scope, name)
                return
            scope = scope._parent
        self._vars[name] = value
        if _sanitizer._on:
            _sanitizer.record_write(self, name)

    def var(self, name):
        return _TensorView(self, name)

    def new_scope(self):
        kid = Scope(parent=self)
        self._kids.append(kid)
        return kid

    def drop_kids(self):
        self._kids = []


_global_scope = Scope()
_scope_stack = [_global_scope]


def global_scope():
    return _scope_stack[-1]


class scope_guard:
    def __init__(self, scope):
        self._scope = scope

    def __enter__(self):
        _scope_stack.append(self._scope)
        return self._scope

    def __exit__(self, *exc):
        _scope_stack.pop()


def _as_name(v):
    if isinstance(v, Variable):
        return v.name
    if isinstance(v, str):
        return v
    raise TypeError("fetch/feed entry must be Variable or str, got %r" % (v,))


class Executor:
    """Runs Programs. `place` selects the XLA backend (TPUPlace/CPUPlace)."""

    def __init__(self, place=None):
        self.place = place if place is not None else core.default_place()
        # compiled-executable cache, LRU-bounded: every entry pins an
        # XLA executable (and its host-side constants); long-running
        # multi-program processes would otherwise grow without bound
        self._cache = collections.OrderedDict()
        self._cache_cap = int(
            os.environ.get("PADDLE_TPU_EXECUTOR_CACHE_CAP", 32)
        )
        self._run_counter = 0
        self._closed = False
        self._verified = set()  # signatures the analyzer already gated

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed=None,
        fetch_list=None,
        feed_var_name="feed",
        fetch_var_name="fetch",
        scope=None,
        return_numpy=True,
        use_program_cache=True,
        use_prune=False,
    ):
        if self._closed:
            raise RuntimeError("Executor is closed")
        # fault-injection hook (resilience.FaultInjector): BEFORE the
        # reader pop so an injected run fault doesn't consume a batch —
        # a guarded retry re-runs the same step on the same data
        fault_check("run")
        program = program if program is not None else default_main_program()
        if not feed:
            # a started py_reader attached to the program supplies the
            # batch (ref: reader ops pulling from the C++ blocking queue);
            # raises core.EOFException at end of epoch. Checked BEFORE the
            # CompiledProgram/pipeline dispatch so every execution path
            # auto-feeds. CompiledProgram wraps the underlying Program.
            src = getattr(program, "_program", program)
            readers = getattr(src, "_py_readers", [])
            for reader in readers:
                batch = reader._next_feed()
                if batch is not None:
                    feed = dict(batch)
                    break
            else:
                self._check_unstarted_readers(src, readers)
        # CompiledProgram (data-parallel) delegates to its own runner
        if hasattr(program, "_executor_run"):
            return program._executor_run(
                self, feed, fetch_list, scope, return_numpy
            )
        # collective-transpiled programs (transpiler.collective) carry
        # their mesh runner; running the plain program runs it sharded
        dist = getattr(program, "_transpiled_dist", None)
        if dist is not None:
            return dist._executor_run(
                self, feed, fetch_list, scope, return_numpy
            )
        # PipelineOptimizer-annotated programs run the gpipe schedule
        info = getattr(program, "_parallel_info", None)
        if info and info.get("mode") == "pipeline" and not getattr(
            program, "_is_start_up_program", False
        ):
            from .pipeline_executor import run_pipeline_program

            return run_pipeline_program(
                self, program, feed or {}, fetch_list or [],
                scope if scope is not None else global_scope(),
                return_numpy,
            )
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [_as_name(f) for f in fetch_list]

        # run-health phase split: three monotonic reads per step when a
        # run-health bundle is active (TrainGuard pops the result right
        # after this call returns), zero timestamps otherwise
        rh_on = _runhealth.active() is not None
        t_feed0 = time.monotonic() if rh_on else 0.0
        with obs.span("executor.run"):
            with obs.span("executor.feed_convert"):
                feed_arrays = self._prepare_feeds(program, feed)
                state = self._gather_state(program, scope)
            t_feed1 = time.monotonic() if rh_on else 0.0

            sig = (
                program._uid,
                program._version,
                tuple(sorted((k, v.shape, str(v.dtype)) for k, v in feed_arrays.items())),
                tuple(fetch_names),
                tuple(sorted((k, v.shape, str(v.dtype)) for k, v in state.items())),
            )
            rng = self._next_rng(program)
            platform = "cpu" if isinstance(self.place, core.CPUPlace) else "tpu"
            entry = self._cache_lookup(sig) if use_program_cache else None
            if entry is None and sig not in self._verified:
                # first compile of this signature: gate it on the static
                # analyzer (PADDLE_TPU_ANALYSIS=off|verify|full) — a
                # broken program fails HERE with op-attributed
                # diagnostics instead of deep inside lowering/XLA
                self._verify_first_compile(
                    program, feed_arrays, state, fetch_names, platform)
                self._verified.add(sig)
            disk_key = None
            if entry is None and use_program_cache and compile_cache.enabled():
                # disk tier: a hit deserializes the AOT artifact in ms and
                # emits NO compile_start — warm processes skip the compile
                try:
                    disk_key = compile_cache.entry_key(
                        program, list(feed_arrays.keys()), fetch_names,
                        sig[2], sig[4], platform)
                except compile_cache.Unfingerprintable:
                    disk_key = None
                else:
                    entry = compile_cache.load(disk_key)
                    if entry is not None:
                        self._cache_store(sig, entry)
                        _ledger_register(program, "executor", entry,
                                         "disk")
            if entry is None:
                obs.inc("executor.cache_miss")
                obs.event("compile_start", source="executor", count=False,
                          program=program._uid, version=program._version)
                t_compile = time.monotonic()
                step = build_step_fn(
                    program, list(feed_arrays.keys()), fetch_names,
                    platform=platform,
                )
                jitted = jax.jit(step, donate_argnums=(0,))
                # AOT-compile: freezes one executable for this signature. Without
                # this, the donated state outputs come back in compiler-chosen
                # layouts, and the SECOND run would retrace+recompile the whole
                # module against those layouts (a full minutes-long compile for a
                # big model). The AOT executable instead relayouts inputs on
                # device, so run 2+ reuse the same binary.
                entry = jitted.lower(state, feed_arrays, rng).compile()
                if disk_key is not None:
                    # persist the AOT artifact so the NEXT process (crash
                    # resume, repeat bench) skips this compile entirely
                    compile_cache.store(
                        disk_key, jitted, (state, feed_arrays, rng))
                dt_compile = time.monotonic() - t_compile
                _runhealth.goodput_note("compile", dt_compile)
                obs.observe("executor.compile_seconds", dt_compile)
                obs.event("compile_done", source="executor", count=False,
                          program=program._uid, version=program._version,
                          seconds=round(dt_compile, 6))
                _ledger_register(program, "executor", entry, "compile",
                                 compile_seconds=dt_compile,
                                 donated=sorted(state.keys()))
                if use_program_cache:
                    self._cache_store(sig, entry)
            else:
                obs.inc("executor.cache_hit")

            if _conc._on:
                # dispatch donates the state buffers: flag captures of
                # them (serving engines sharing this scope) and any lock
                # held across the blocking device call
                from ..analysis import dataflow as _dataflow

                _dataflow.note_donation(scope, state)
                _conc.note_blocking("device.dispatch")
            t_comp0 = time.monotonic() if rh_on else 0.0
            with obs.span("executor.device_compute"):
                try:
                    fetches, new_state = entry(state, feed_arrays, rng)
                except Exception:
                    # cache-safe re-run: a failed dispatch may have consumed the
                    # donated state buffers or left the executable poisoned —
                    # evict so a guarded retry recompiles against fresh state
                    # instead of replaying a dead executable
                    if self._cache.pop(sig, None) is not None:
                        obs.inc("executor.cache_evict")
                    raise
                if obs.trace_enabled():
                    # trace mode: make the span measure true device time
                    # (dispatch is async; only block when asked — blocking
                    # every step would serialize the pipeline)
                    for v in fetches:
                        if hasattr(v, "block_until_ready"):
                            v.block_until_ready()
                    for v in new_state.values():
                        if hasattr(v, "block_until_ready"):
                            v.block_until_ready()

            t_comp1 = time.monotonic() if rh_on else 0.0
            with obs.span("executor.fetch"):
                for k, v in new_state.items():
                    scope.update(k, v)
                if return_numpy:
                    result = [np.asarray(v) for v in fetches]
                else:
                    result = list(fetches)
            if rh_on:
                _runhealth.note_exec_phases(
                    feed_convert_s=t_feed1 - t_feed0,
                    compute_s=t_comp1 - t_comp0,
                    fetch_s=time.monotonic() - t_comp1)
            return result

    # ------------------------------------------------------------------
    def run_pipelined(self, program=None, feeds=None, fetch_list=None,
                      scope=None, return_numpy=True, depth=None,
                      window=None):
        """Pipelined step loop: returns an iterable of per-step fetch
        lists where host-side feed conversion + device transfer for
        batch N+1 overlap device compute for batch N (double-buffered
        staging thread), and fetches materialize lazily behind a bounded
        in-flight window. ``feeds`` is an iterable of feed dicts, or
        None to pull from the program's started py_reader until EOF.
        Step results are bit-identical to calling :meth:`run` in a loop
        — same feed preparation, same PRNG sequence, same dispatch
        order. See :mod:`paddle_tpu.fluid.async_pipeline`."""
        from .async_pipeline import PipelinedRunner

        return PipelinedRunner(
            self, program, feeds, fetch_list, scope,
            return_numpy=return_numpy, depth=depth, window=window)

    # ------------------------------------------------------------------
    def _run_dataset_scan(self, program, feed, k, scope):
        """Run ``k`` program steps in ONE device dispatch: the feed
        holds k stacked minibatches (leading dim k*bs) and the jitted
        body is ``lax.scan`` over the single-step function. This is the
        TPU-native analogue of the reference's Hogwild worker threads —
        they amortize per-batch framework overhead across C++ threads
        (ref executor.py train_from_dataset); here one XLA launch
        amortizes the host dispatch across k sequential steps.
        Bit-identical to k sequential run() calls: scan is sequential
        and the per-step PRNG keys consume the same _next_rng counter
        sequence. Raises OpLoweringError if the program's state
        structure is not scan-stable (caller falls back to single
        steps)."""
        scope = scope if scope is not None else global_scope()
        feed_arrays = self._prepare_feeds(program, feed)
        state = self._gather_state(program, scope)
        stacked = {}
        for name, v in feed_arrays.items():
            if v.shape[0] % k:
                raise OpLoweringError(
                    "dataset scan: feed %r rows %d not divisible by "
                    "k=%d" % (name, v.shape[0], k))
            stacked[name] = v.reshape((k, v.shape[0] // k) + v.shape[1:])
        counter_before = self._run_counter
        rngs = jnp.stack([self._next_rng(program) for _ in range(k)])
        sig = (
            "dataset_scan", k, program._uid, program._version,
            tuple(sorted((n, v.shape, str(v.dtype))
                         for n, v in stacked.items())),
            tuple(sorted((n, v.shape, str(v.dtype))
                         for n, v in state.items())),
        )
        platform = "cpu" if isinstance(self.place, core.CPUPlace) \
            else "tpu"
        entry = self._cache_lookup(sig)
        disk_key = None
        if entry is None and compile_cache.enabled():
            try:
                disk_key = compile_cache.entry_key(
                    program, list(stacked.keys()), [], sig[4], sig[5],
                    platform, kind="dataset_scan:%d" % k)
            except compile_cache.Unfingerprintable:
                disk_key = None
            else:
                entry = compile_cache.load(disk_key)
                if entry is not None:
                    self._cache_store(sig, entry)
                    _ledger_register(program, "executor.scan", entry,
                                     "disk")
        if entry is None:
            obs.inc("executor.cache_miss")
            t_compile = time.monotonic()
            step = build_step_fn(program, list(feed_arrays.keys()), [],
                                 platform=platform)
            state_keys = frozenset(state.keys())

            def multi(st, feeds_k, rngs_k):
                def body(carry, xs):
                    fd, rng = xs
                    _, new_st = step(carry, fd, rng)
                    if frozenset(new_st.keys()) != state_keys:
                        # trace-time structure check: scan carries must
                        # be stable; warmup single-steps create lazy
                        # state before this path engages
                        raise OpLoweringError(
                            "dataset scan: state keys changed inside "
                            "the step (%r)" % sorted(
                                frozenset(new_st.keys()) ^ state_keys))
                    return new_st, ()

                out, _ = jax.lax.scan(body, st, (feeds_k, rngs_k))
                return out

            jitted = jax.jit(multi, donate_argnums=(0,))
            try:
                entry = jitted.lower(state, stacked, rngs).compile()
            except Exception as e:
                # ANY compile failure (structure check, XLA resource
                # exhaustion on the k-step module, ...) means "fall
                # back to single steps". Nothing ran and nothing was
                # donated, so rewind the PRNG counter — the caller's
                # single-step replay must consume the SAME k keys or
                # reproducibility silently breaks.
                self._run_counter = counter_before
                raise OpLoweringError(
                    "dataset scan compile failed (%s: %s)"
                    % (type(e).__name__, str(e)[:200]))
            if disk_key is not None:
                compile_cache.store(disk_key, jitted,
                                    (state, stacked, rngs))
            dt_compile = time.monotonic() - t_compile
            obs.observe("executor.compile_seconds", dt_compile)
            _ledger_register(program, "executor.scan", entry, "compile",
                             compile_seconds=dt_compile,
                             donated=sorted(state.keys()))
            self._cache_store(sig, entry)
        else:
            obs.inc("executor.cache_hit")
        if _conc._on:
            from ..analysis import dataflow as _dataflow

            _dataflow.note_donation(scope, state)
            _conc.note_blocking("device.dispatch")
        new_state = entry(state, stacked, rngs)
        for name, v in new_state.items():
            scope.update(name, v)

    def _prepare_feeds(self, program, feed):
        block = program.global_block()
        out = {}
        feed = dict(feed)
        # LoDTensor feeds expand into (padded array, @SEQ_LEN lengths);
        # plain-array feeds of lod_level>0 vars default to full lengths
        for name in list(feed.keys()):
            v = feed[name]
            seq_name = name + "@SEQ_LEN"
            if not block.has_var(seq_name) or seq_name in feed:
                continue
            if getattr(v, "seq_lens", None) is not None:
                feed[seq_name] = np.asarray(v.seq_lens, dtype="int32")
            else:
                arr = getattr(v, "_ndarray", v)
                # .shape avoids a host copy for device arrays; plain
                # list/tuple feeds still go through np.asarray
                shape = arr.shape if hasattr(arr, "shape") else \
                    np.asarray(arr).shape
                feed[seq_name] = np.full(
                    (shape[0],), shape[1], dtype="int32"
                )
        dev = self.place.jax_device()
        ready = {}   # already device-resident (or device-bound) values
        host = {}    # host arrays, transferred in ONE batched device_put
        for name, value in feed.items():
            value = getattr(value, "_ndarray", value)  # LoDTensor shim
            want = None
            if block.has_var(name):
                var = block.var(name)
                if var.dtype is not None:
                    want = core.np_dtype(var.dtype)
            if isinstance(value, jax.Array):
                # already-device-resident feeds skip the host round-trip
                # entirely: a committed array on the target device passes
                # through untouched — re-feeding the same batch costs
                # nothing
                if want is not None and value.dtype != want:
                    value = value.astype(want)
                if getattr(value, "committed", False) \
                        and dev in value.devices():
                    ready[name] = value
                else:
                    ready[name] = jax.device_put(value, dev)
                continue
            arr = np.asarray(value)
            if want is not None and arr.dtype != want:
                arr = arr.astype(want)
            host[name] = arr
        if host:
            # one device_put for every host-side feed: batched transfers
            # amortize the per-call dispatch overhead vs per-tensor puts
            ready.update(jax.device_put(host, dev))
        for name in feed:  # preserve feed order (part of the cache sig)
            out[name] = ready[name]
        return out

    def _gather_state(self, program, scope):
        state = {}
        for v in program.global_block().vars.values():
            if not v.persistable:
                continue
            val = scope.find_value(v.name)
            if val is not None:
                state[v.name] = val
        return state

    def _next_rng(self, program):
        self._run_counter += 1
        seed = program.random_seed
        if seed == 0:
            seed = abs(hash(("paddle_tpu", program._uid))) % (2**31)
        return jax.random.PRNGKey(seed + 1000003 * self._run_counter)

    @staticmethod
    def _check_unstarted_readers(program, readers):
        """No feed given and no attached reader produced a batch: if a
        decorated-but-unstarted reader feeds vars the program's ops
        actually consume, fail HERE with the fix, instead of deep in
        lowering with a missing-value error."""
        idle = [r for r in readers
                if r._paddle_reader is not None and not r._started]
        if not idle:
            return
        consumed = set()
        for op in program.global_block().ops:
            consumed.update(op.input_arg_names)
        for r in idle:
            needed = [v.name for v in r._feed_list if v.name in consumed]
            if needed:
                raise core.ReaderNotStartedError(
                    "Executor.run got no feed and py_reader %r (feeding "
                    "%s) is not started — call reader.start() before "
                    "run(); after core.EOFException call reader.reset() "
                    "then reader.start() for the next epoch"
                    % (r._name, ", ".join(needed))
                )

    def close(self):
        """Release cached executables and flush pending async orbax
        checkpoint writes (parallel.checkpoint.finalize) so a process
        exiting right after a wait=False save can't lose it. Idempotent."""
        if self._closed:
            return
        self._cache.clear()
        self._closed = True
        from ..parallel import checkpoint as _ckpt

        try:
            _ckpt.finalize()
        except Exception as e:  # noqa: BLE001 — closing must not raise
            warnings.warn("checkpoint finalize on Executor.close failed: "
                          "%s: %s" % (type(e).__name__, e))

    # -- static-analysis gate (paddle_tpu.analysis) --------------------
    def _verify_first_compile(self, program, feed_arrays, state,
                              fetch_names, platform):
        """Run the static analyzer before the first compile of a
        signature. ``verify`` (the default) is a pure-python structural
        walk; ``full`` adds shape/dtype propagation + TPU-lint; ``off``
        restores the pre-analyzer executor exactly. Verifier errors —
        the program would provably fail at lowering — raise
        :class:`~paddle_tpu.analysis.ProgramVerifyError` before any XLA
        work; everything else flows to the telemetry hub + flight
        recorder. Analyzer *crashes* are swallowed (a gate must never be
        the thing that breaks a healthy run)."""
        from ..analysis import analyzer as _analyzer

        level = _analyzer.mode()
        if level == "off":
            return
        t0 = time.monotonic()
        try:
            report = _analyzer.analyze(
                program, feed_names=list(feed_arrays.keys()),
                fetch_names=fetch_names, state_names=set(state.keys()),
                feed_specs=feed_arrays, state_specs=state,
                platform=platform, level=level,
                device_kind=_device_kind())
        except Exception as e:  # noqa: BLE001 — analyzer bug, not user's
            obs.event("analysis_failed", source="executor",
                      error="%s: %s" % (type(e).__name__, e))
            return
        obs.observe("analysis.verify_seconds", time.monotonic() - t0)
        _publish_analysis_gauges(report)
        _ledger_predict(program, report.meta)
        if report.diagnostics:
            obs.inc("analysis.findings", len(report.findings))
            obs.event("analysis_report", source="executor", count=False,
                      program=program._uid, version=program._version,
                      level=level, summary=report.summary())
        report.raise_if_errors()

    # -- compiled-executable LRU (shared by run + dataset-scan paths) --
    def _cache_lookup(self, sig):
        entry = self._cache.get(sig)
        if entry is not None:
            self._cache.move_to_end(sig)
        return entry

    def _cache_store(self, sig, entry):
        self._cache[sig] = entry
        while len(self._cache) > self._cache_cap:
            self._cache.popitem(last=False)
            obs.inc("executor.cache_evict")

    # -- dataset trainer path (ref executor.py:1033,1103) --------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """Consume every batch of ``dataset`` through the jitted program
        step (ref executor.py train_from_dataset). The reference fans the
        work across C++ Hogwild threads; here `thread` tunes host-side
        parsing parallelism and batches stage through the native C++
        slot ring, while ONE XLA stream runs the step with donated
        params (see fluid/dataset.py module docstring)."""
        return self._run_from_dataset(
            program, dataset, scope, thread, False, debug, fetch_list,
            fetch_info, print_period, fetch_handler)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           fetch_handler=None):
        """Like train_from_dataset but runs a test-pruned clone: the
        backward op, optimizer updates, and anything dataflow-dependent
        on them are dropped (post-minimize forward/metric ops survive),
        mirroring the reference's infer-mode skip_ops."""
        return self._run_from_dataset(
            program, dataset, scope, thread, True, debug, fetch_list,
            fetch_info, print_period, fetch_handler)

    def _run_from_dataset(self, program, dataset, scope, thread, is_infer,
                          debug, fetch_list, fetch_info, print_period,
                          fetch_handler):
        from .data_feeder import DataFeeder  # noqa: F401 (via loader)
        from .reader import _GeneratorLoader
        from .trainer_factory import FetchHandlerMonitor, TrainerFactory

        if dataset is None:
            raise ValueError(
                "train/infer_from_dataset requires a dataset (build one "
                "with fluid.DatasetFactory().create_dataset())"
            )
        program = program if program is not None else default_main_program()
        program = getattr(program, "_program", program)  # CompiledProgram
        run_prog = self._strip_training_ops(program) if is_infer else program
        # trainer desc for parity/introspection (Hogwild contract)
        trainer = TrainerFactory()._create_trainer(
            getattr(program, "_fleet_opt", None))
        trainer.device_worker._set_infer(is_infer)
        trainer._set_thread(thread or dataset.thread_num)

        dataset._prepare_to_run()
        dataset._dynamic_adjust_before_train(thread or dataset.thread_num)
        monitor = None
        if fetch_handler is not None:
            monitor = FetchHandlerMonitor(
                scope or global_scope(), fetch_handler)
            monitor.start()
        fetch_vars = list(fetch_list or [])
        infos = list(fetch_info or [
            getattr(v, "name", str(v)) for v in fetch_vars])
        # Reuse one loader (and its native C++ pipe: mlock'd arena +
        # worker pool) per (dataset, feed signature, place) across
        # train_from_dataset calls — the pipe setup measured ~0.4s, and
        # a small dataset's epoch is shorter than that. The cache lives
        # ON the dataset so its lifetime tracks the data, not the
        # executor.
        cache_key = (
            tuple(v.name for v in dataset.use_vars),
            type(self.place).__name__,
        )
        cached = getattr(dataset, "_loader_cache", None)
        if cached is not None and cached[0] == cache_key:
            loader = cached[1]
            # the key matches on NAMES; refresh the Variable objects so
            # a same-named feed list from a different program can't
            # feed through stale dtype/shape/lod metadata
            loader._feed_list = list(dataset.use_vars)
        else:
            loader = _GeneratorLoader(
                feed_list=dataset.use_vars, capacity=8)
            dataset._loader_cache = (cache_key, loader)
        # k steps per device dispatch (lax.scan over the step body) when
        # nothing forces a per-step host round-trip; fetches, debug
        # mode, and mesh/pipeline runners keep the single-step loop
        scan_k = max(1, int(os.environ.get(
            "PADDLE_TPU_DATASET_STEPS_PER_CALL", "8")))
        plain_prog = not (hasattr(run_prog, "_executor_run")
                          or getattr(run_prog, "_transpiled_dist", None)
                          or getattr(run_prog, "_parallel_info", None))
        use_scan = (scan_k > 1 and not fetch_vars and not debug
                    and plain_prog
                    and all(v.lod_level == 0 for v in dataset.use_vars))
        bs = dataset.batch_size
        loader.set_sample_list_generator(
            lambda: dataset._batch_iterator(
                thread, rows=scan_k * bs if use_scan else None),
            places=self.place)
        step = 0
        # warmth is per (program, scope): the single-step warmup creates
        # lazily-materialized persistable STATE, which lives in the
        # scope — a fresh scope needs its own warmup even for a warm
        # program (else scan engages unwarmed, trips the structure
        # check, and both the fallback and the optimization misfire)
        flag_scope = scope if scope is not None else global_scope()
        warm_uids = getattr(flag_scope, "_dataset_scan_warm", None)
        if warm_uids is None:
            warm_uids = set()
            flag_scope._dataset_scan_warm = warm_uids
        scan_warm = run_prog._uid in warm_uids
        scan_ok = True
        try:
            for feed in loader():
                if use_scan:
                    nrows = next(iter(feed.values())).shape[0]
                    k = nrows // bs if nrows % bs == 0 else 0
                    if k > 1 and scan_warm and scan_ok:
                        try:
                            self._run_dataset_scan(run_prog, feed, k,
                                                   scope)
                            step += k
                            continue
                        except OpLoweringError:
                            scan_ok = False  # unstable state: fall back
                    # warmup (or fallback / ragged tail): replay the
                    # super-batch as bs-sized single steps — the warmup
                    # creates any lazily-materialized state so later
                    # scan carries are structure-stable
                    for lo in range(0, nrows, bs):
                        sub = {n: v[lo:lo + bs] for n, v in feed.items()}
                        self.run(run_prog, feed=sub, scope=scope)
                        step += 1
                    scan_warm = True
                    warm_uids.add(run_prog._uid)
                    continue
                step += 1
                want_fetch = fetch_vars and (
                    debug or step % print_period == 0)
                out = self.run(
                    run_prog, feed=feed,
                    fetch_list=fetch_vars if want_fetch else None,
                    scope=scope,
                )
                if want_fetch:
                    msg = ", ".join(
                        "%s=%s" % (i, np.asarray(v).reshape(-1)[:8])
                        for i, v in zip(infos, out)
                    )
                    print("[dataset step %d] %s" % (step, msg))
        finally:
            if monitor is not None:
                monitor.stop()
            dataset._dynamic_adjust_after_train()
            dataset._finish_to_run()
        return None

    # per-param update op types (mirror of the reference infer-mode
    # skip-ops list: grad + optimizer ops)
    _OPT_UPDATE_TYPES = frozenset({
        "sgd", "momentum", "dgc_momentum", "lars_momentum", "adagrad",
        "decayed_adagrad", "adadelta", "adam", "adamax", "rmsprop",
        "ftrl", "lamb", "dpsgd",
    })

    @classmethod
    def _strip_training_ops(cls, program):
        """Clone with the training ops removed: the symbolic `backward`
        op, per-param update ops, and anything dataflow-dependent on
        their outputs (clip/regularizer/loss-scaling ops consuming @GRAD
        vars). Forward/metric ops appended AFTER minimize() survive —
        the reference infer mode skips op types, it doesn't truncate."""
        pruned = program.clone()
        block = pruned.global_block()
        dead = set()
        defined = set()  # vars produced by kept ops so far
        kept = []
        for op in block.ops:
            drop = (
                op.type == "backward"
                or op.type in cls._OPT_UPDATE_TYPES
                or any(n in dead for n in op.input_arg_names)
            )
            if drop:
                ins = set(op.input_arg_names)
                for n in op.output_arg_names:
                    # only fresh vars die: in-place writes, vars a kept
                    # op already produced, and persistable vars (their
                    # startup-initialized value stays valid — e.g. an
                    # AMP loss-scaling var whose update op is dropped)
                    var = block.vars.get(n)
                    if (n not in ins and n not in defined
                            and not (var is not None and var.persistable)):
                        dead.add(n)
            else:
                kept.append(op)
                defined.update(op.output_arg_names)
                dead.difference_update(op.output_arg_names)
        if len(kept) != len(block.ops):
            block.ops = kept
            pruned._bump_version()
        return pruned
