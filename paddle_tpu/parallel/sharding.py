"""GSPMD sharding of lowered Programs.

TPU-native replacement for the reference's distributed transpilers
(ref: python/paddle/fluid/transpiler/distribute_transpiler.py and the fleet
collective transpiler): instead of rewriting the program with collective
ops, the ONE lowered step function is jitted with sharding-annotated inputs
over a Mesh — data parallel (batch over 'dp'), tensor parallel (weight
shards over 'tp' by name-pattern rules), sequence parallel (sequence dim
over 'sp'). XLA's partitioner inserts the all-reduce / all-gather /
reduce-scatter collectives on ICI.
"""
import re

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map


def shard_map_manual(f, mesh, in_specs, out_specs, manual_axes=None):
    """``jax.shard_map`` with replication checking off.
    ``manual_axes=None`` means every mesh axis is manual; a set selects
    partially-manual mode (the remaining axes stay GSPMD-auto)."""
    kw = dict(mesh=mesh, in_specs=in_specs, out_specs=out_specs)
    if manual_axes is not None:
        kw["axis_names"] = frozenset(manual_axes)
    return shard_map(f, check_vma=False, **kw)

from ..fluid import core
from ..fluid.framework import Variable
from ..fluid.lowering import build_step_fn

__all__ = ["ShardingRule", "DistributedProgram", "StackedDpProgram",
           "replicated", "batch_sharded"]


class ShardingRule:
    """Map parameter names (regex) to a PartitionSpec over mesh axes."""

    def __init__(self, pattern, spec):
        self.pattern = re.compile(pattern)
        self.spec = spec if isinstance(spec, P) else P(*spec)

    def match(self, name):
        return self.pattern.search(name) is not None


def replicated(mesh):
    return NamedSharding(mesh, P())


def batch_sharded(mesh, axis="dp"):
    return NamedSharding(mesh, P(axis))


def _merge_axis_into(base_spec, extra_spec, shape, mesh):
    """Place extra_spec's (single) mesh axis onto the first free,
    evenly-divisible dim of base_spec. Returns the merged PartitionSpec or
    None when it can't be merged (base is None, axis taken, nothing
    divides)."""
    if base_spec is None:
        return None
    extra_axes = [a for a in extra_spec if a is not None]
    if len(extra_axes) != 1:
        return None
    axis = extra_axes[0]
    entries = list(base_spec) + [None] * (len(shape) - len(base_spec))
    if any(a == axis or (isinstance(a, tuple) and axis in a)
           for a in entries if a is not None):
        return None
    size = mesh.shape[axis]
    for dim in range(len(shape)):
        if entries[dim] is None and shape[dim] % size == 0:
            entries[dim] = axis
            merged = P(*entries)
            if _spec_fits(merged, shape, mesh):
                return merged
            entries[dim] = None
    return None


def _spec_fits(spec, shape, mesh):
    """A PartitionSpec only applies if every sharded dim divides evenly."""
    for dim, axis in enumerate(spec):
        if axis is None:
            continue
        if dim >= len(shape):
            return False
        size = mesh.shape[axis] if not isinstance(axis, tuple) else int(
            np.prod([mesh.shape[a] for a in axis])
        )
        if shape[dim] % size != 0:
            return False
    return True


class DistributedProgram:
    """Wraps a Program with a mesh + sharding rules; runnable through the
    ordinary Executor (same hook as CompiledProgram)."""

    def __init__(self, program, mesh, param_rules=None, feed_axis="dp",
                 feed_specs=None, opt_state_rules=None):
        self._program = program
        self._mesh = mesh
        self._param_rules = list(param_rules or [])
        # ZeRO-style rules applied ONLY to optimizer state (moments etc.):
        # params/grads stay wherever param_rules put them while the
        # optimizer state + its update shard over 'dp' — the memory win of
        # ZeRO-1 expressed as GSPMD shardings instead of manual
        # reduce-scatter/all-gather (XLA inserts those on ICI itself)
        self._opt_state_rules = list(opt_state_rules or [])
        self._opt_state_names = {
            v.name
            for v in program.global_block().vars.values()
            if getattr(v, "belong_to_optimizer", False)
        }
        # longest-first so "emb_2"'s accumulators never match "emb"
        self._param_names = sorted(
            (p.name for p in program.global_block().all_parameters()),
            key=len, reverse=True,
        )
        # honor sharding annotations left by DistributeTranspiler.transpile
        for name, spec in (getattr(program, "_sharding_spec", None) or []):
            # exact-name anchor: a bare suffix pattern would also capture
            # params like "src_emb" when the annotation targets "emb"
            self._param_rules.append(
                ShardingRule("^" + re.escape(name) + "$", spec))
        self._feed_axis = feed_axis
        self._feed_specs = feed_specs or {}  # feed name -> PartitionSpec
        self._cache = {}

    # -- sharding resolution --------------------------------------------
    def _param_rule_spec(self, name, shape):
        for rule in self._param_rules:
            if rule.match(name) and _spec_fits(rule.spec, shape, self._mesh):
                return rule.spec
        return None

    def param_sharding(self, name, shape):
        if name in self._opt_state_names and self._opt_state_rules:
            base = self._param_rule_spec(name, shape)
            for rule in self._opt_state_rules:
                if not rule.match(name):
                    continue
                # moments of tp-sharded params keep the tp layout AND gain
                # the ZeRO axis on a free dim (P('dp','tp') beats either
                # alone); fall back to the plain ZeRO spec, then to the
                # param layout
                merged = _merge_axis_into(
                    base, rule.spec, shape, self._mesh
                )
                if merged is not None:
                    return NamedSharding(self._mesh, merged)
                if _spec_fits(rule.spec, shape, self._mesh):
                    return NamedSharding(self._mesh, rule.spec)
            if base is not None:
                return NamedSharding(self._mesh, base)
        spec = self._param_rule_spec(name, shape)
        if spec is None and name in self._opt_state_names:
            # accumulators inherit their param's layout (they share its
            # shape; a replicated moment of a sharded param would force
            # a resharding round-trip every step — and on multi-process
            # meshes the host fetch outright fails). Accumulator names
            # are "<param>_<acc>_<n>" (optimizer._add_accumulator).
            for pname in self._param_names:
                if name.startswith(pname + "_"):
                    spec = self._param_rule_spec(pname, shape)
                    break
        return NamedSharding(self._mesh, spec if spec is not None else P())

    def feed_sharding(self, name, shape):
        if name in self._feed_specs:
            spec = self._feed_specs[name]
            if _spec_fits(spec, shape, self._mesh):
                return NamedSharding(self._mesh, spec)
        if (
            self._feed_axis
            and self._feed_axis in self._mesh.shape
            and shape
            and shape[0] % self._mesh.shape[self._feed_axis] == 0
        ):
            return NamedSharding(self._mesh, P(self._feed_axis))
        return NamedSharding(self._mesh, P())

    @staticmethod
    def _same_sharding(a, b, ndim):
        """Sharding equivalence modulo trailing-None spec entries (jit
        outputs normalize P('dp', None) to P('dp'); strict equality
        would silently round-trip state through the host every step —
        and crash outright on multi-process meshes, where np.asarray
        can't fetch a spanning array). ``is_equivalent_to`` also checks
        the device assignment, so differently-laid-out meshes with the
        same axis sizes stay distinct."""
        try:
            return a.is_equivalent_to(b, ndim)
        except Exception:  # noqa: BLE001 — non-NamedSharding and co.
            return a == b

    def shard_state(self, state):
        """Device-put scope state onto the mesh per rules (params sharded,
        everything else replicated)."""
        out = {}
        for k, v in state.items():
            arr = np.asarray(v) if not hasattr(v, "sharding") else v
            sh = self.param_sharding(k, np.shape(arr))
            if (hasattr(v, "sharding")
                    and self._same_sharding(v.sharding, sh,
                                            np.ndim(arr))):
                out[k] = v
            else:
                out[k] = jax.device_put(np.asarray(v), sh)
        return out

    # -- executor hook ---------------------------------------------------
    def _executor_run(self, executor, feed, fetch_list, scope, return_numpy):
        from ..fluid.executor import global_scope

        program = self._program
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_names = [
            f.name if isinstance(f, Variable) else f for f in (fetch_list or [])
        ]
        block = program.global_block()
        feed_arrays = {}
        for name, value in feed.items():
            value = getattr(value, "_ndarray", value)
            arr = np.asarray(value)
            if block.has_var(name) and block.var(name).dtype is not None:
                want = core.np_dtype(block.var(name).dtype)
                if arr.dtype != want:
                    arr = arr.astype(want)
            feed_arrays[name] = jax.device_put(
                arr, self.feed_sharding(name, arr.shape)
            )
        state = self.shard_state(executor._gather_state(program, scope))

        sig = (
            id(program), program._version,
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in feed_arrays.items())),
            tuple(fetch_names),
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in state.items())),
        )
        entry = self._cache.get(sig)
        if entry is None:
            # mesh_axes marks this lowering as SPMD-partitioned so ops with
            # partitioner-opaque kernels (pallas attention) pick their
            # einsum formulations instead
            step = build_step_fn(
                program, list(feed_arrays), fetch_names,
                mesh_axes={a: a for a in self._mesh.axis_names},
                mesh=self._mesh,
            )
            entry = jax.jit(step, donate_argnums=(0,))
            self._cache[sig] = entry
        rng = jax.device_put(
            executor._next_rng(program), replicated(self._mesh)
        )
        fetches, new_state = entry(state, feed_arrays, rng)
        for k, v in new_state.items():
            scope.update(k, v)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)


class StackedDpProgram(DistributedProgram):
    """Shared machinery for programs that run the ONE lowered step under
    ``shard_map`` over the 'dp' mesh axis with per-shard parameter /
    optimizer-state copies riding a stacked leading dp dimension in the
    scope (sharded ``P('dp')``).

    Two subsystems need exactly this stage: LocalSGD
    (:class:`..local_sgd.LocalSGDProgram` — k-step local updates +
    periodic averaging) and explicit gradient sync
    (:class:`..comms.grad_sync.GradSyncProgram` — every-step bucketed /
    quantized allreduce). They differ only in WHAT the per-shard step
    does around the base program step, so that is the subclass hook:

    - :meth:`_make_per_shard` (required) — wrap the base step into the
      per-shard function ``(state, feeds, rng, step_i) -> (fetches,
      new_state)`` that unstacks/restacks local state and issues
      whatever collectives the mode needs;
    - :meth:`_seed_extra_state` — inject mode-private scope state
      (LocalSGD sync anchors, error-feedback residuals) into the raw
      state dict before stacking;
    - :meth:`_build_base_step` — how the program lowers to the base
      step (grad-sync threads its ``grad_comm`` hook through here);
    - :meth:`_on_dispatch` — called right before each step dispatch
      (fault-site / deadline checks, telemetry).

    Everything else — state staging, collapse-for-serialization,
    elastic shrink, the executor hook — is shared here. Use
    :meth:`consolidate_scope` / :meth:`consolidated_scope` before
    saving persistables.
    """

    _mode_name = "StackedDp"

    def __init__(self, program, mesh, **kw):
        super().__init__(program, mesh, **kw)
        if "dp" not in mesh.shape or mesh.shape["dp"] <= 1:
            raise ValueError(
                "%s requires a dp mesh axis of size > 1 "
                "(got mesh %s); with one worker there is nothing to "
                "synchronize — use the plain collective mode"
                % (self._mode_name, mesh.shape,)
            )
        block = program.global_block()
        self._avg_names = {
            v.name for v in block.all_parameters()
            if getattr(v, "trainable", True)
        }
        opt_state = {
            v.name for v in block.vars.values()
            if getattr(v, "belong_to_optimizer", False)
        }
        # per-shard (divergent) state: params + accumulators + EVERY
        # persistable var some op writes (BN moving stats, AMP loss-scale
        # counters, lr counters, ...). Each shard computes these from its
        # own sub-batch, so pretending they are replicated would silently
        # keep one shard's value; stacking them is always correct (vars
        # that update identically just carry identical copies).
        written = {n for op in block.ops for n in op.output_arg_names}
        step_state = {
            v.name for v in block.vars.values()
            if getattr(v, "persistable", False) and v.name in written
        }
        self._local_names = self._avg_names | opt_state | step_state
        self._step_i = 0
        self._stacked_shapes = {}

    # -- subclass hooks ---------------------------------------------------
    def _seed_extra_state(self, raw_state, scope):
        """Inject mode-private state (residuals, anchors, ...) into the
        raw state dict before stacking. Names must be in
        ``self._local_names`` to ride the stacked dp layout."""

    def _build_base_step(self, feed_names, fetch_names):
        return build_step_fn(
            self._program, feed_names, fetch_names,
            mesh_axes={a: a for a in self._mesh.axis_names},
            mesh=self._mesh,
        )

    def _make_per_shard(self, base_step):
        raise NotImplementedError

    def _on_dispatch(self):
        """Called right before each jitted step dispatch."""

    # -- state staging ----------------------------------------------------
    def _stack_state(self, state):
        """Scope values -> stacked-local / replicated device arrays."""
        ndp = self._mesh.shape["dp"]
        out = {}
        for k, v in state.items():
            arr = v if hasattr(v, "sharding") else np.asarray(v)
            if k in self._local_names:
                if hasattr(v, "sharding") and self._is_stacked_sharding(
                        v.sharding):
                    # already stacked on device from the previous step:
                    # (dp, *orig) with the LEADING dim as the dp axis —
                    # keep it there (no host round-trip, donation works)
                    out[k] = v
                    continue
                np_arr = np.asarray(arr)
                if np_arr.ndim >= 1 and np_arr.shape[0] == ndp and \
                        self._already_stacked(k, np_arr):
                    stacked = np_arr          # host copy, already stacked
                else:
                    stacked = np.broadcast_to(
                        np_arr, (ndp,) + np_arr.shape)
                    self._mark_stacked(k, stacked)
                out[k] = jax.device_put(stacked, NamedSharding(
                    self._mesh,
                    P("dp", *([None] * (stacked.ndim - 1)))))
            else:
                sh = NamedSharding(self._mesh, P())
                out[k] = (v if hasattr(v, "sharding")
                          and v.sharding == sh
                          else jax.device_put(np.asarray(arr), sh))
        return out

    def _is_stacked_sharding(self, sh):
        """dp on the leading dim, nothing else — robust to jax's
        trailing-None normalization (P('dp',) vs P('dp', None))."""
        spec = getattr(sh, "spec", None)
        mesh = getattr(sh, "mesh", None)
        if spec is None or mesh is None:
            return False
        try:
            if dict(mesh.shape) != dict(self._mesh.shape):
                return False
        except Exception:  # noqa: BLE001
            return False
        entries = tuple(spec)
        return (len(entries) >= 1 and entries[0] == "dp"
                and all(e is None for e in entries[1:]))

    def _already_stacked(self, name, arr):
        return self._stacked_shapes.get(name) == arr.shape

    def _mark_stacked(self, name, arr):
        if not hasattr(self, "_stacked_shapes"):
            self._stacked_shapes = {}
        self._stacked_shapes[name] = arr.shape

    def _collapse(self, name, arr):
        """Collapse a stacked (ndp, ...) value to program-var shape:
        floats mean over the dp axis, ints take shard 0. Device values
        stay ON DEVICE (eager jnp ops; XLA reduces over the sharded
        leading axis) — serialization pulls only what it writes, so a
        checkpoint-during-training save is O(bytes written), not an
        O(params x ndp) host round-trip of the whole scope."""
        if isinstance(arr, np.ndarray):        # already host: stay host
            if np.issubdtype(arr.dtype, np.floating):
                return arr.mean(axis=0)
            return arr[0]
        if np.issubdtype(np.dtype(arr.dtype), np.floating):
            return jnp.mean(arr, axis=0)
        return arr[0]

    def _stacked_here(self, name, v):
        return (name in self._local_names
                and getattr(self, "_stacked_shapes", {}).get(name)
                is not None
                and self._stacked_shapes[name]
                == tuple(getattr(v, "shape", ()) or ()))

    def consolidated_scope(self, scope):
        """A COPY of ``scope`` with stacked per-shard state collapsed to
        program-var shapes (floats: cross-shard mean; ints: shard 0) —
        for serialization. The LIVE scope is untouched: an off-schedule
        save must not act as a parameter sync or average away the
        worker-local optimizer moments. Device values stay on device
        (no host materialization); non-collapsed device values are
        device-COPIED, never aliased — the live buffer may be donated
        to the next jitted step, and a snapshot held across that step
        must not dereference a deleted buffer."""
        from ..fluid.executor import Scope

        snap = Scope()
        for name, v in list(scope.items()):
            if self._stacked_here(name, v):
                snap.set(name, self._collapse(name, v))
            elif isinstance(v, jax.Array):
                snap.set(name, jnp.copy(v))
            else:
                snap.set(name, v)
        return snap

    def consolidate_scope(self, scope):
        """IN-PLACE collapse (end of training / before handing the
        scope to non-stacked consumers). For checkpoint-during-training
        use :meth:`consolidated_scope` — it leaves training state
        alone."""
        for name in self._local_names:
            v = scope.find_value(name)
            if v is None:
                continue
            if not self._stacked_here(name, v):
                continue
            scope.update(name, self._collapse(name, v))
            self._stacked_shapes.pop(name, None)

    # -- elastic shrink ---------------------------------------------------
    def shrink_dp(self, scope, surviving_shards, new_mesh=None):
        """Shrink-to-survivors (parallel/elastic.py): drop the dead
        workers' rows from every stacked per-shard value in `scope`,
        rebuild on a mesh over the surviving devices, and invalidate the
        jit cache so the next step re-traces on the smaller dp axis.
        Collectives over 'dp' then reduce over the NEW axis size — the
        averaging denominator is rescaled from the old world to the
        survivor count, instead of silently averaging ghosts. Returns
        the new mesh.

        Rare-event path: stacked state round-trips through the host
        (the old mesh's device set no longer exists, so device-to-device
        resharding has no target layout to reuse).
        """
        old_ndp = self._mesh.shape["dp"]
        keep = sorted(set(surviving_shards))
        bad = [i for i in keep if not 0 <= i < old_ndp]
        if bad:
            raise ValueError(
                "surviving shard positions %s out of range for dp=%d"
                % (bad, old_ndp))
        if len(keep) < 2:
            raise ValueError(
                "%s needs >= 2 surviving shards (got %d of %d); "
                "with one worker left, consolidate the scope and fall "
                "back to single-worker training"
                % (self._mode_name, len(keep), old_ndp))
        if new_mesh is None:
            from .mesh import shrink_mesh

            new_mesh = shrink_mesh(self._mesh, survivors=keep)
        if new_mesh.shape.get("dp") != len(keep):
            raise ValueError(
                "new mesh dp axis is %s but %d shards survive"
                % (new_mesh.shape.get("dp"), len(keep)))
        for name, shape in list(getattr(self, "_stacked_shapes",
                                        {}).items()):
            v = scope.find_value(name)
            if v is None or tuple(getattr(v, "shape", ())) != shape:
                continue
            sliced = np.ascontiguousarray(np.asarray(v)[keep])
            scope.update(name, sliced)
            self._stacked_shapes[name] = sliced.shape
        self._mesh = new_mesh
        self._cache.clear()
        return new_mesh

    # -- executor hook ----------------------------------------------------
    def _executor_run(self, executor, feed, fetch_list, scope,
                      return_numpy):
        from ..fluid.executor import global_scope

        if not hasattr(self, "_stacked_shapes"):
            self._stacked_shapes = {}
        program = self._program
        mesh = self._mesh
        ndp = mesh.shape["dp"]
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_names = [
            f.name if isinstance(f, Variable) else f
            for f in (fetch_list or [])
        ]
        block = program.global_block()

        feed_arrays, feed_specs = {}, {}
        for name, value in feed.items():
            value = getattr(value, "_ndarray", value)
            arr = np.asarray(value)
            if block.has_var(name) and block.var(name).dtype is not None:
                want = core.np_dtype(block.var(name).dtype)
                if arr.dtype != want:
                    arr = arr.astype(want)
            # same contract as DistributedProgram.feed_sharding:
            # explicit feed_specs win (P() opts a feed out of batch
            # splitting), then the feed_axis heuristic
            if name in self._feed_specs:
                spec = self._feed_specs[name]
                entries = tuple(spec)
                # P() (replicate) or P('dp') / P('dp', None, ...)
                # (batch-split) only: 'dp' anywhere but the leading dim
                # would slice features, not examples
                if not (all(a is None for a in entries)
                        or (entries[:1] == ("dp",)
                            and all(a is None for a in entries[1:]))):
                    raise NotImplementedError(
                        "%s feeds shard over 'dp' on the LEADING "
                        "(batch) dim only; feed %r asked for %s"
                        % (self._mode_name, name, spec))
            elif (self._feed_axis and arr.ndim
                    and arr.shape[0] % ndp == 0):
                spec = P("dp")
            else:
                spec = P()
            feed_specs[name] = spec
            feed_arrays[name] = jax.device_put(
                arr, NamedSharding(mesh, spec))
        raw_state = executor._gather_state(program, scope)
        self._seed_extra_state(raw_state, scope)
        state = self._stack_state(raw_state)
        state_specs = {
            k: (P("dp", *([None] * (np.ndim(v) - 1)))
                if k in self._local_names else P())
            for k, v in state.items()
        }

        sig = (
            id(program), program._version,
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in feed_arrays.items())),
            tuple(fetch_names),
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in state.items())),
        )
        entry = self._cache.get(sig)
        if entry is None:
            base_step = self._build_base_step(
                list(feed_arrays), fetch_names)
            per_shard = self._make_per_shard(base_step)
            smap_kw = dict(
                mesh=mesh,
                in_specs=(state_specs, feed_specs, P(), P()),
                out_specs=([P("dp")] * len(fetch_names), state_specs),
            )
            stepper = shard_map(per_shard, check_vma=False, **smap_kw)
            entry = jax.jit(stepper, donate_argnums=(0,))
            self._cache[sig] = entry

        self._step_i += 1
        self._on_dispatch()
        rng = jax.device_put(executor._next_rng(program),
                             NamedSharding(mesh, P()))
        step_i = jax.device_put(jnp.asarray(self._step_i, jnp.int32),
                                NamedSharding(mesh, P()))
        fetches, new_state = entry(state, feed_arrays, rng, step_i)
        for k, v in new_state.items():
            scope.update(k, v)
            if k in self._local_names:
                self._stacked_shapes[k] = tuple(v.shape)

        out = []
        for name, v in zip(fetch_names, fetches):
            # v is (ndp, *per_shard_shape)
            var = block.vars.get(name)
            vshape = getattr(var, "shape", None)
            batchy = bool(vshape) and len(vshape) and (
                vshape[0] in (None, -1)
                # static batch dims count too: a declared leading dim
                # equal to ndp * per-shard is a sharded batch, and
                # averaging unrelated examples would be silent garbage
                or (isinstance(vshape[0], int) and len(v.shape) >= 2
                    and vshape[0] == v.shape[0] * v.shape[1])
            )
            if batchy:
                # per-shard batch outputs concatenate back to the
                # global batch
                v = jnp.reshape(v, (-1,) + tuple(v.shape[2:]))
            elif jnp.issubdtype(v.dtype, jnp.floating):
                v = jnp.mean(v, axis=0)     # e.g. per-shard losses
            else:
                v = v[0]
            out.append(np.asarray(v) if return_numpy else v)
        return out
