"""CompiledProgram: data-parallel compilation
(ref: python/paddle/fluid/compiler.py + framework/parallel_executor.cc).

TPU-native redesign: the reference builds one SSA graph per GPU and
all-reduces gradients over NCCL. Here the SAME lowered step function is
jitted with jax shardings over a device Mesh: feeds are sharded on the batch
axis, state is replicated, and XLA inserts the ICI all-reduces for the vjp
gradients automatically. One executable, N chips.
"""
import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import core
from .framework import Variable
from .lowering import build_step_fn

__all__ = ["CompiledProgram", "BuildStrategy", "ExecutionStrategy"]


class BuildStrategy:
    """Accepted for API parity; the XLA partitioner replaces the reference's
    graph-pass knobs."""

    class ReduceStrategy:
        AllReduce = 0
        Reduce = 1

    class GradientScaleStrategy:
        CoeffNumDevice = 0
        One = 1
        Customized = 2

    def __init__(self):
        self.reduce_strategy = BuildStrategy.ReduceStrategy.AllReduce
        self.gradient_scale_strategy = (
            BuildStrategy.GradientScaleStrategy.CoeffNumDevice
        )
        self.memory_optimize = None
        self.enable_inplace = None
        self.fuse_all_reduce_ops = True
        self.fuse_all_optimizer_ops = False
        self.fuse_elewise_add_act_ops = False
        self.sync_batch_norm = False
        self.num_trainers = 1
        self.trainer_id = 0


class ExecutionStrategy:
    def __init__(self):
        self.num_threads = 0
        self.num_iteration_per_drop_scope = 100
        self.num_iteration_per_run = 1
        self.use_thread_barrier = True


class CompiledProgram:
    def __init__(self, program_or_graph, build_strategy=None):
        self._program = program_or_graph
        self._build_strategy = build_strategy or BuildStrategy()
        self._is_data_parallel = False
        self._loss_name = None
        self._places = None
        self._share_vars_from = None
        self._mesh = None
        self._cache = {}

    def with_data_parallel(
        self,
        loss_name=None,
        build_strategy=None,
        exec_strategy=None,
        share_vars_from=None,
        places=None,
    ):
        self._is_data_parallel = True
        self._loss_name = loss_name
        if build_strategy is not None:
            self._build_strategy = build_strategy
        self._share_vars_from = share_vars_from
        self._places = places
        return self

    def _get_mesh(self, place):
        if self._mesh is not None:
            return self._mesh
        if self._places is not None and len(self._places):
            devs = [p.jax_device() if hasattr(p, "jax_device") else p
                    for p in self._places]
        else:
            # every device of the executor place's backend; a TPUPlace
            # without a TPU raises here, it never lands on the CPU
            devs = jax.devices(place._backend)
        self._mesh = Mesh(np.array(devs), axis_names=("dp",))
        return self._mesh

    def _shard_feeds(self, feed, mesh):
        """Host feeds -> device arrays split on the batch axis over
        'dp'. A feed with no batch axis (a scalar, or a static leading
        dim the device count does not divide) is replicated; a batch
        the device count does not divide is an error, never a silent
        replication of the whole batch onto every chip."""
        ndev = mesh.devices.size
        block = self._program.global_block()
        out = {}
        for name, value in (feed or {}).items():
            value = getattr(value, "_ndarray", value)
            arr = np.asarray(value)
            var = block.var(name) if block.has_var(name) else None
            if var is not None and var.dtype is not None:
                want = core.np_dtype(var.dtype)
                if arr.dtype != want:
                    arr = arr.astype(want)
            if arr.shape and arr.shape[0] % ndev == 0:
                spec = P("dp")
            elif (arr.shape and var is not None and var.shape
                  and var.shape[0] in (None, -1)):
                raise ValueError(
                    "data-parallel feed %r has batch %d, which %d devices "
                    "do not divide — feed a multiple of the device count"
                    % (name, arr.shape[0], ndev))
            else:
                spec = P()
            out[name] = jax.device_put(arr, NamedSharding(mesh, spec))
        return out

    # called by Executor.run when program is a CompiledProgram
    def _executor_run(self, executor, feed, fetch_list, scope, return_numpy):
        from .executor import global_scope

        program = self._program
        scope = scope if scope is not None else global_scope()
        feed = feed or {}
        fetch_list = fetch_list or []
        fetch_names = [
            f.name if isinstance(f, Variable) else f for f in fetch_list
        ]
        if not self._is_data_parallel:
            return executor.run(
                program, feed, fetch_list, scope=scope,
                return_numpy=return_numpy,
            )

        mesh = self._get_mesh(executor.place)
        ndev = mesh.devices.size
        repl = NamedSharding(mesh, P())
        feed_arrays = self._shard_feeds(feed, mesh)
        state = {
            k: (v if hasattr(v, "sharding")
                and getattr(v.sharding, "mesh", None) is mesh
                else jax.device_put(np.asarray(v), repl))
            for k, v in executor._gather_state(program, scope).items()
        }

        sig = (
            program._uid, program._version,
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in feed_arrays.items())),
            tuple(fetch_names), ndev,
            tuple(sorted((k, v.shape, str(v.dtype))
                         for k, v in state.items())),
        )
        entry = self._cache.get(sig)
        if entry is None:
            # the mesh marks the lowering as partitioned over 'dp': ops
            # with a per-shard formulation (the fused vocabulary head's
            # row lists) take it instead of what the partitioner would
            # make of their single-device one
            step = build_step_fn(program, list(feed_arrays), fetch_names,
                                 platform=executor.place._backend,
                                 mesh_axes={"dp": "dp"}, mesh=mesh)
            # shardings are carried by the committed input arrays (feeds
            # batch-sharded over 'dp', state replicated); XLA partitions the
            # whole step and inserts the ICI collectives for the vjp grads
            entry = jax.jit(step, donate_argnums=(0,))
            self._cache[sig] = entry

        rng = jax.device_put(executor._next_rng(program), repl)
        fetches, new_state = entry(state, feed_arrays, rng)
        for k, v in new_state.items():
            scope.set(k, v)
        if return_numpy:
            return [np.asarray(v) for v in fetches]
        return list(fetches)


CompiledProgram.with_inference_optimize = lambda self, config: self
