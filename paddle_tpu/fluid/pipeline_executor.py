"""Pipeline-parallel execution of a fluid Program (PipelineOptimizer path).

TPU-native rework of the reference's pipeline trainer
(ref: python/paddle/fluid/optimizer.py:3193 PipelineOptimizer, which splits
the program at ``cut_list`` vars and runs section workers over blocking
queues on different devices). Here:

  * the forward region is split at the cut vars' producing ops into S
    heterogeneous stage functions;
  * all S stages run under one ``shard_map`` over the 'pp' mesh axis —
    each device executes its own stage via ``lax.switch`` on its axis
    index, activations circulate with ``lax.ppermute`` inside a
    ``lax.scan`` over (microbatches + stages - 1) ticks (GPipe schedule);
  * the BACKWARD pipeline is not hand-written: ``jax.vjp`` through the
    scan + ppermute forward yields the reverse schedule mechanically
    (ppermute transposes to the inverse permutation, scan to a reverse
    scan) — the payoff of building the pipeline as a pure jax function;
  * grads are bound to the program's ``p@GRAD`` vars and the post-backward
    ops (optimizer updates, LR schedules) run replicated as usual.

Semantics: with M microbatches of equal size, mean-reduced losses match
sequential full-batch execution exactly (mean of microbatch means). v1
limitations (documented, loud): stage bodies must be stateless in the
persistable sense (no batch-norm running-stat updates inside the pipeline)
and fetches must be producible by the last stage.

Composed parallelism (dp x pp in ONE program — the fleet
DistributedStrategy composition the reference pursues in
incubate/fleet/collective/__init__.py:134-253): pass
``PipelineOptimizer(..., mesh=, feed_specs=)`` a mesh that carries a
'pp' axis PLUS other axes. The pipeline shard_map is then manual over
'pp' ONLY (``axis_names={'pp'}``) — stage dispatch and the ppermute
ring see their pp shard — while every other axis stays an *auto* axis:
feeds keep their dp batch sharding and GSPMD partitions the stage
bodies and inserts the dp collectives exactly as it does outside the
pipeline (batch-group all-reduces are executed by every device of one
pp coordinate, consistent with that coordinate's lax.switch branch).

Param sharding over auto axes (tp) is REJECTED here, deliberately: the
heterogeneous stage bodies live in lax.switch branches that diverge by
pp index, and GSPMD freely inserts mesh-wide resharding
collective-permutes inside those branches when re-laying-out sharded
weights for a dot — devices of the other pp coordinate never reach
them, which deadlocks the collective (observed on the 8-device CPU
mesh: 4 threads at op_id=1, 4 at op_id=2). Uniform-body pipelines
don't have this hazard — for true dp x tp x pp composition use the
stacked-stage pipeline (paddle_tpu.parallel.pipeline.gpipe_composed),
whose single stage body is executed by EVERY device so tp psums are
structurally uniform.
"""

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..ops.registry import LowerContext
from .lowering import (
    OpLoweringError, apply_op, run_ops, segment_cuts, _make_var_lookup,
)

__all__ = ["run_pipeline_program"]


def _cut_names(cut_list):
    names = []
    for c in cut_list or []:
        if isinstance(c, (list, tuple)):
            names.extend(_cut_names(c))
        else:
            names.append(c.name if hasattr(c, "name") else str(c))
    return names


def _split_stages(region, cut_list):
    """Partition the forward op span at each cut var's producing op
    (the cut op ends its stage, like the reference's section split)."""
    cuts = segment_cuts(region, _cut_names(cut_list))
    spans = []
    prev = 0
    for c in cuts:
        spans.append((prev, c + 1))
        prev = c + 1
    spans.append((prev, len(region)))
    return spans


def _boundary_vars(region, spans, program):
    """Vars produced in stage <= b and consumed in a later stage — the
    union over boundaries is the ring buffer's (uniform) structure. Reads
    include while/cond sub-block closure reads (op_read_names), which the
    op's declared inputs would miss."""
    from .lowering import op_read_names

    stage_of = {}
    for s, (lo, hi) in enumerate(spans):
        for j in range(lo, hi):
            for ns in region[j].outputs.values():
                for n in ns:
                    stage_of[n] = s
    crossing = set()
    for s, (lo, hi) in enumerate(spans):
        for j in range(lo, hi):
            for n in op_read_names(region[j], program):
                if n in stage_of and stage_of[n] < s:
                    crossing.add(n)
    return sorted(crossing), stage_of


def run_pipeline_program(executor, program, feed, fetch_list, scope,
                         return_numpy):
    info = program._parallel_info
    block = program.global_block()
    op_list = list(block.ops)

    bw_idx = next(
        (i for i, op in enumerate(op_list) if op.type == "backward"), None
    )
    if bw_idx is None:
        raise OpLoweringError(
            "pipeline mode needs a backward op: call "
            "PipelineOptimizer.minimize(loss) before Executor.run"
        )
    region = op_list[:bw_idx]
    bw_op = op_list[bw_idx]
    post_ops = op_list[bw_idx + 1:]

    spans = _split_stages(region, info.get("cut_list"))
    n_stages = len(spans)
    if n_stages < 2:
        raise OpLoweringError(
            "PipelineOptimizer cut_list produced %d stage(s); pass the "
            "boundary activation vars as cut_list=[...]" % n_stages
        )
    devices = jax.devices()
    if len(devices) < n_stages:
        raise OpLoweringError(
            "pipeline needs one device per stage: %d stages but only %d "
            "device(s) visible" % (n_stages, len(devices))
        )
    ring_names, stage_of = _boundary_vars(region, spans, program)

    from .executor import _as_name

    fetch_names = [_as_name(f) for f in fetch_list or []]
    loss_name = bw_op.input("Loss")[0]
    last_lo, last_hi = spans[-1]
    last_stage_produced = {
        n for j in range(last_lo, last_hi)
        for ns in region[j].outputs.values() for n in ns
    }
    post_produced = {
        n for op in post_ops for ns in op.outputs.values() for n in ns
    }
    persist_names = {
        v.name for v in block.vars.values() if v.persistable
    }
    for f in fetch_names:
        if (f != loss_name and f not in last_stage_produced
                and f not in post_produced and f not in persist_names):
            raise OpLoweringError(
                "pipeline fetch '%s' is produced mid-pipeline; only "
                "last-stage vars (loss, metrics), post-backward vars "
                "(lr, counters) and persistable state are fetchable in "
                "pipeline mode" % f
            )
    record_names = sorted(
        (set(fetch_names) & last_stage_produced) | {loss_name}
    )

    feed_arrays = executor._prepare_feeds(program, feed)
    state = executor._gather_state(program, scope)
    target_names = bw_op.attrs["targets"]
    for n in target_names:
        if n not in state:
            raise OpLoweringError(
                "pipeline backward target '%s' missing from scope — run the "
                "startup program first" % n
            )

    n_micro = info.get("n_microbatches") or n_stages
    # the batch dimension is the largest leading dim among feeds; only
    # feeds carrying it are microbatched — smaller leading dims are
    # non-batch constants (im_info vectors etc.) and get replicated
    dim0s = [v.shape[0] for v in feed_arrays.values() if v.ndim > 0]
    batch_dim = max(dim0s) if dim0s else 0
    if batch_dim and batch_dim % n_micro:
        raise OpLoweringError(
            "feed batch %d not divisible by %d microbatches"
            % (batch_dim, n_micro)
        )

    if info.get("param_rules"):
        # Rejected on ANY mesh: on a composed mesh sharded weights make
        # GSPMD insert mesh-wide resharding collectives inside the
        # divergent lax.switch branches (a structural deadlock, observed
        # as 4-vs-4 rendezvous splits on the 8-device CPU mesh); on the
        # default pp-only mesh there is no auto axis to shard over. Both
        # roads lead to the same advice.
        raise OpLoweringError(
            "PipelineOptimizer(param_rules=...) is not supported: the "
            "heterogeneous stage bodies diverge per pp index "
            "(lax.switch), and sharded weights make GSPMD insert "
            "mesh-wide resharding collectives inside the divergent "
            "branches — a structural deadlock. Shard the batch over "
            "'dp' via feed_specs (safe: dp collective groups stay "
            "within one pp coordinate), or use the stacked-stage "
            "pipeline for dp x tp x pp "
            "(paddle_tpu.parallel.pipeline.gpipe_composed).")
    mesh = info.get("mesh")
    if mesh is None:
        mesh = Mesh(np.array(devices[:n_stages]), ("pp",))
    else:
        if "pp" not in mesh.axis_names:
            raise OpLoweringError(
                "PipelineOptimizer mesh must carry a 'pp' axis; got axes %s"
                % (mesh.axis_names,))
        if mesh.shape["pp"] != n_stages:
            raise OpLoweringError(
                "mesh 'pp' axis has size %d but cut_list produced %d "
                "stages" % (mesh.shape["pp"], n_stages))

    repl = NamedSharding(mesh, P())
    feed_specs = info.get("feed_specs") or {}
    unknown = set(feed_specs) - set(feed_arrays)
    if unknown:
        raise OpLoweringError(
            "PipelineOptimizer feed_specs name(s) %s match no feed "
            "(feeds: %s) — a typo here would silently replicate the "
            "batch instead of sharding it"
            % (sorted(unknown), sorted(feed_arrays)))
    feed_arrays = {
        k: jax.device_put(v, NamedSharding(mesh, feed_specs[k]))
        if k in feed_specs else jax.device_put(v, repl)
        for k, v in feed_arrays.items()
    }

    # ZeRO-1 composed with the pipeline (the fleet sharding_degree +
    # pipeline composition, ref incubate/fleet/collective/__init__.py):
    # OPTIMIZER state (belong_to_optimizer vars, like
    # DistributedProgram._opt_state_names) may shard over auto axes
    # because it is only read by the POST-pipeline ops (Adam/Momentum
    # updates), which run outside the divergent lax.switch branches —
    # unlike param_rules (rejected above). A matched opt var the
    # forward region READS is refused for exactly that reason;
    # non-optimizer matches are ignored, like DistributedProgram.
    opt_rules = info.get("opt_state_rules") or []
    if opt_rules:
        state_shardings = _resolve_opt_shardings(
            executor, program, region, opt_rules, mesh, repl, state)
        state = {k: jax.device_put(v, state_shardings.get(k, repl))
                 for k, v in state.items()}
    else:
        state = {k: jax.device_put(v, repl) for k, v in state.items()}
    rng = jax.device_put(executor._next_rng(program), repl)

    sig = (
        "pipeline", program._uid, program._version, n_stages, n_micro,
        tuple(sorted((k, v.shape, str(v.dtype))
                     for k, v in feed_arrays.items())),
        tuple(fetch_names),
        tuple(sorted((k, v.shape, str(v.dtype)) for k, v in state.items())),
    )
    entry = executor._cache.get(sig)
    if entry is None:
        jitted = _build_pipeline_fn(
            program, region, spans, ring_names, record_names, target_names,
            bw_op, post_ops, loss_name, mesh, n_micro, batch_dim,
        )
        # AOT-compile like the main executor path: without this the
        # donated state comes back in compiler-chosen layouts and run 2
        # would retrace+recompile the whole shard_map/scan module
        entry = jitted.lower(state, feed_arrays, rng).compile()
        executor._cache[sig] = entry

    fetches, new_state = entry(state, feed_arrays, rng)
    for k, v in new_state.items():
        scope.update(k, v)
    out = [fetches[n] for n in fetch_names]
    if return_numpy:
        return [np.asarray(v) for v in out]
    return out


def _resolve_opt_shardings(executor, program, region, opt_rules, mesh,
                           repl, state):
    """{state name -> NamedSharding} for opt_state_rules. Constant per
    (program, rules, mesh), so it is cached on the executor — the
    per-step cost is one dict lookup per var, not a regex sweep plus a
    recursive region-read scan."""
    key = ("pipe_opt_shardings", program._uid, program._version, id(mesh))
    cached = executor._cache.get(key)
    if cached is not None:
        return cached

    from .lowering import op_read_names
    from ..parallel.sharding import _spec_fits

    opt_names = {
        v.name for v in program.global_block().vars.values()
        if getattr(v, "belong_to_optimizer", False)
    }
    region_reads = set()
    for op in region:
        region_reads.update(op_read_names(op, program))

    out = {}
    for name, value in state.items():
        if name not in opt_names:
            continue
        shape = np.shape(value)
        for r in opt_rules:
            if not r.match(name):
                continue
            entries = tuple(r.spec)
            while entries and entries[-1] is None:
                entries = entries[:-1]
            if len(entries) > len(shape):
                continue
            spec = P(*entries)
            if not _spec_fits(spec, shape, mesh):
                continue
            if name in region_reads:
                raise OpLoweringError(
                    "opt_state_rules matched %r, which the pipeline "
                    "forward region READS — sharding it would put "
                    "GSPMD reshard collectives inside the divergent "
                    "stage branches (see param_rules error). Only "
                    "post-pipeline optimizer state may shard." % name)
            out[name] = NamedSharding(mesh, spec)
            break
    executor._cache[key] = out
    return out


def _build_pipeline_fn(program, region, spans, ring_names, record_names,
                       target_names, bw_op, post_ops, loss_name, mesh,
                       n_micro, batch_dim):
    block = program.global_block()
    var_lookup = _make_var_lookup(block)
    n_stages = len(spans)
    persist = {
        v.name for v in block.vars.values() if v.persistable
    }

    def step(state, feeds, rng):
        ctx = LowerContext(rng=rng, is_test=False, program=program,
                           mesh_axes={}, platform=None)
        ctx.run_ops = run_ops

        # microbatch the batch-dim feeds: (B, ...) -> (M, B//M, ...);
        # scalars and non-batch feeds are replicated per tick
        feeds_mb = {}
        for k, v in feeds.items():
            if v.ndim > 0 and v.shape[0] == batch_dim and batch_dim:
                feeds_mb[k] = v.reshape(
                    (n_micro, v.shape[0] // n_micro) + v.shape[1:]
                )
            else:
                feeds_mb[k] = jnp.broadcast_to(
                    v, (n_micro,) + v.shape
                )

        # ring buffer template: zeros in every boundary var's
        # microbatch-sized shape (trace stage-by-stage to get shapes)
        shapes = _infer_boundary_shapes(
            region, spans, ring_names, record_names, state, feeds_mb,
            program, var_lookup,
        )

        nontarget_state = {
            k: v for k, v in state.items() if k not in set(target_names)
        }

        def pipelined_loss(params):
            def local(params_l, nt_state_l, feeds_mb_l):
                idx = lax.axis_index("pp")
                perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

                def stage_body(s, env_base, buf):
                    lo, hi = spans[s]
                    e = dict(env_base)
                    e.update(buf)
                    for j in range(lo, hi):
                        e = apply_op(region[j], e, ctx, var_lookup,
                                     op_tag=1000 + j)
                    new_buf = {
                        n: e.get(n, buf[n]) for n in ring_names
                    }
                    rec = {
                        n: e[n] if n in e else jnp.zeros(shapes["rec"][n][0],
                                                         shapes["rec"][n][1])
                        for n in record_names
                    }
                    return new_buf, rec

                def tick(carry, t):
                    buf, recs = carry
                    mb_idx = jnp.clip(t - idx, 0, n_micro - 1)
                    env_base = dict(params_l)
                    env_base.update(nt_state_l)
                    for k, v in feeds_mb_l.items():
                        env_base[k] = v[mb_idx]
                    branches = [
                        (lambda b, _s=s: stage_body(_s, env_base, b))
                        for s in range(n_stages)
                    ]
                    # distinct PRNG per microbatch: without the traced
                    # token, dropout in a stage would reuse one mask for
                    # every microbatch (fold_in of a constant op tag is
                    # itself a compile-time constant inside this scan)
                    ctx._iter_token = mb_idx
                    try:
                        new_buf, rec = lax.switch(idx, branches, buf)
                    finally:
                        ctx._iter_token = None
                    done = t - (n_stages - 1)
                    is_last = idx == n_stages - 1
                    valid = is_last & (done >= 0) & (done < n_micro)
                    di = jnp.clip(done, 0, n_micro - 1)
                    recs = jax.tree_util.tree_map(
                        lambda acc, r: lax.cond(
                            valid,
                            lambda a: a.at[di].set(r),
                            lambda a: a,
                            acc,
                        ),
                        recs, rec,
                    )
                    new_buf = jax.tree_util.tree_map(
                        lambda x: lax.ppermute(x, "pp", perm), new_buf
                    )
                    return (new_buf, recs), None

                buf0 = {
                    n: jnp.zeros(shapes["ring"][n][0], shapes["ring"][n][1])
                    for n in ring_names
                }
                recs0 = {
                    n: jnp.zeros((n_micro,) + shapes["rec"][n][0],
                                 shapes["rec"][n][1])
                    for n in record_names
                }
                (_, recs), _ = lax.scan(
                    tick, (buf0, recs0),
                    jnp.arange(n_micro + n_stages - 1),
                )
                # only the last stage recorded; psum broadcasts to all
                return jax.tree_util.tree_map(
                    lambda x: lax.psum(x, "pp"), recs
                )

            # manual ONLY over 'pp' (stage switch + ppermute ring); any
            # other mesh axis (dp/tp/...) stays auto — GSPMD keeps the
            # feeds' dp sharding and the params' tp sharding inside the
            # stage bodies and inserts those collectives itself
            from ..parallel.sharding import shard_map_manual
            recs = shard_map_manual(
                local, mesh,
                in_specs=(P(), P(), P()),
                out_specs=P(),
                manual_axes={"pp"},
            )(params, nontarget_state, feeds_mb)
            loss_mb = recs[loss_name]
            loss = jnp.mean(loss_mb.astype(jnp.float32))
            return loss, recs

        params = {n: state[n] for n in target_names}
        (loss_val, vjp_fn, recs) = jax.vjp(
            pipelined_loss, params, has_aux=True
        )
        (grads,) = vjp_fn(jnp.ones_like(loss_val))

        # bind grads + recorded fetches, then run optimizer/post ops
        env = dict(state)
        env.update(feeds)
        env[loss_name] = loss_val
        for n in record_names:
            if n != loss_name:
                # microbatch-mean for float metrics (exact for means);
                # SUM for integer fetches — counts (accuracy Correct,
                # chunk totals) are additive over microbatches, and the
                # last microbatch alone would be silently ~M× too small
                r = recs[n]
                env[n] = jnp.mean(r.astype(jnp.float32), axis=0) \
                    if jnp.issubdtype(r.dtype, jnp.floating) \
                    else jnp.sum(r, axis=0)
        grad_names = bw_op.output("Grads")
        for tname, gname in zip(target_names, grad_names):
            env[gname] = grads[tname]
        for k, op in enumerate(post_ops):
            env = apply_op(op, env, ctx, var_lookup, op_tag=50000 + k)

        fetch_all = set(record_names) | (persist & set(env))
        for op in post_ops:
            for ns in op.outputs.values():
                fetch_all.update(ns)
        fetches = {n: env[n] for n in fetch_all if n in env}
        fetches[loss_name] = loss_val
        new_state = {n: env[n] for n in persist if n in env}
        return fetches, new_state

    return jax.jit(step, donate_argnums=(0,))


def _infer_boundary_shapes(region, spans, ring_names, record_names, state,
                           feeds_mb, program, var_lookup):
    """Abstractly evaluate one microbatch through the stages to learn the
    shapes/dtypes of boundary + recorded vars. Uses a private ctx with a
    constant rng so no outer-trace tracers leak into eval_shape."""
    probe_ctx = LowerContext(rng=jax.random.PRNGKey(0), is_test=False,
                             program=program, mesh_axes={}, platform=None)
    probe_ctx.run_ops = run_ops

    def probe(state_s, feeds_one):
        e = dict(state_s)
        e.update(feeds_one)
        for lo, hi in spans:
            for j in range(lo, hi):
                e = apply_op(region[j], e, probe_ctx, var_lookup,
                             op_tag=1000 + j)
        return (
            {n: e[n] for n in ring_names},
            {n: e[n] for n in record_names},
        )

    state_s = {
        k: jax.ShapeDtypeStruct(jnp.shape(v), jnp.result_type(v))
        for k, v in state.items()
    }
    feeds_one = {
        k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
        for k, v in feeds_mb.items()
    }
    ring, rec = jax.eval_shape(probe, state_s, feeds_one)
    return {
        "ring": {k: (tuple(v.shape), v.dtype) for k, v in ring.items()},
        "rec": {k: (tuple(v.shape), v.dtype) for k, v in rec.items()},
    }
