"""Program → jax function lowering.

This is the TPU-native replacement for the reference's C++ executor loop
(ref: paddle/fluid/framework/executor.cc Executor::RunPreparedContext), which
walks the ProgramDesc and dispatches a kernel per op. Here the whole block is
traced into ONE pure function

    step(state_dict, feed_dict, rng) -> (fetches, new_state_dict)

and handed to jax.jit: XLA sees the full op graph (forward, vjp-derived
backward, optimizer updates) and fuses/schedules it as a single HloModule —
no per-op launches, no HBM round-trips between ops, params donated.

Autodiff: the symbolic `backward` op appended by backward.append_backward is
lowered by closing over the preceding ops and calling jax.vjp — replacing the
reference's per-op grad-kernel transpile (ref: python/paddle/fluid/backward.py
_append_backward_ops_).
"""
import jax
import jax.numpy as jnp
from jax import lax

from .. import ops as ops_lib
from ..ops.registry import LowerContext, get_lowering
from . import core


class OpLoweringError(RuntimeError):
    pass


def _format_callstack(op):
    try:
        frames = [
            "    %s:%d in %s" % (f.filename, f.lineno, f.name)
            for f in op.callstack[-3:]
        ]
        return "\n".join(frames)
    except Exception:
        return "    <no callstack>"


def resolve_inputs(op, env):
    ins = {}
    for slot, names in op.inputs.items():
        vals = []
        for n in names:
            if n not in env:
                raise OpLoweringError(
                    "op '%s' input %s='%s' has no value. Was the var fed, "
                    "initialized by the startup program, or produced by an "
                    "earlier op?\n  op: %s\n  defined at:\n%s"
                    % (op.type, slot, n, op, _format_callstack(op))
                )
            vals.append(env[n])
        ins[slot] = vals
    return ins


def bind_outputs(op, outs, env, var_lookup):
    for slot, names in op.outputs.items():
        vals = outs.get(slot)
        if vals is None:
            continue
        for i, n in enumerate(names):
            if i >= len(vals):
                break
            v = vals[i]
            var = var_lookup(n)
            if var is not None and var.stop_gradient and _is_float(v):
                v = lax.stop_gradient(v)
            env[n] = v


def _is_float(v):
    try:
        return jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
    except Exception:
        return False


def apply_op(op, env, ctx, var_lookup, op_tag=0):
    fn = get_lowering(op.type)
    ins = resolve_inputs(op, env)
    # generic skip gate (ref: adam op's SkipUpdate input / AMP found_inf):
    # when a "SkipGate" input is attached and lowers to 0, every in-place
    # output (an output bound to the same var as an input — param and
    # optimizer accumulators) keeps its OLD value, so the whole update op
    # is a true no-op. One lax.select per state var; XLA fuses it.
    gate_vals = ins.pop("SkipGate", None)
    ctx.set_op_tag(op_tag)
    ctx.current_env = env  # control-flow ops close over the outer env
    ctx.run_ops = run_ops
    # the HLO metadata of every device operation then says which op of
    # which block it came from ("mul/encoder_layer_7_ffn_fc_0.tmp_0")
    scope = "%s%s/%s" % (
        op.attrs.get("op_namescope", "/")[1:], op.type, next(
            (names[0] for names in op.outputs.values() if names), ""))
    try:
        with jax.named_scope(scope):
            outs = fn(ctx, ins, op.attrs)
    except (OpLoweringError, NotImplementedError):
        raise
    except Exception as e:
        raise OpLoweringError(
            "lowering op '%s' failed: %s: %s\n  op: %s\n  defined at:\n%s"
            % (op.type, type(e).__name__, e, op, _format_callstack(op))
        ) from e
    if gate_vals:
        gate = jnp.reshape(gate_vals[0], ()) != 0
        old_by_name = {
            n: v
            for slot, names in op.inputs.items() if slot != "SkipGate"
            for n, v in zip(names, ins.get(slot, []))
        }
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            vals = list(vals)
            for i, n in enumerate(names):
                if i < len(vals) and n in old_by_name:
                    vals[i] = jnp.where(gate, vals[i], old_by_name[n])
            outs[slot] = vals
    bind_outputs(op, outs, env, var_lookup)
    return env


def run_ops(block, op_list, env, ctx):
    """Sequentially lower a list of ops; each symbolic `backward` op is
    lowered by jax.vjp over a replay of the ENTIRE preceding program (so a
    second minimize/gradients call on the same program differentiates its
    own forward ops too). PRNG draws are keyed per op position, so the
    replay reproduces identical random draws (dropout masks etc.) and XLA
    CSE collapses the duplicated subgraph."""
    var_lookup = _make_var_lookup(block)
    # tag ops uniquely across blocks so sub-block PRNG keys don't collide
    # with outer-block keys (keys also fold in ctx._iter_token inside loops)
    tag_base = block.idx * 100003
    env0 = dict(env)  # initial state+feeds — replay starts here
    cached_grads = {}  # grads from earlier backward ops, replayed as consts
    for idx, op in enumerate(op_list):
        if op.type != "backward":
            env = apply_op(op, env, ctx, var_lookup, op_tag=tag_base + idx)
            continue
        bw_op = op
        target_names = bw_op.attrs["targets"]
        loss_name = bw_op.input("Loss")[0]
        region = op_list[:idx]

        # Targets bindable at program start (params/feeds/state) become
        # plain vjp primals. INTERMEDIATE targets (e.g. a GAN's fake
        # image) get a zero "probe" added right after their producing op:
        # d loss/d probe == d loss/d intermediate at that program point
        # (ref backward.py gradients() supports arbitrary targets).
        producer = producer_map(region)
        inter_targets = [n for n in target_names if n not in env0]
        for n in inter_targets:
            if n not in producer:
                raise OpLoweringError(
                    "backward target '%s' is neither a parameter/feed/"
                    "state var nor produced before the backward op" % n
                )
        probe_at = {}
        for n in inter_targets:
            probe_at.setdefault(producer[n], []).append(n)

        # no_grad_set vars become constants: a stop_gradient probe at the
        # producing op blocks any gradient flowing through them (vars bound
        # at program start are already vjp constants unless targeted).
        stop_at = {}
        for n in bw_op.attrs.get("no_grad", ()) or ():
            if n in producer and n not in env0:
                stop_at.setdefault(producer[n], []).append(n)

        probe_shapes = {}
        if inter_targets:
            def _shapes_probe():
                e = dict(env0)
                for j, rop in enumerate(region):
                    if rop.type == "backward":
                        for gn in rop.output("Grads"):
                            e[gn] = cached_grads[gn]
                        continue
                    e = apply_op(rop, e, ctx, var_lookup,
                                 op_tag=tag_base + j)
                return tuple(e[n] for n in inter_targets)

            shaped = jax.eval_shape(_shapes_probe)
            probe_shapes = {
                n: jnp.zeros(s.shape, s.dtype)
                for n, s in zip(inter_targets, shaped)
            }

        primals = []
        for n in target_names:
            primals.append(env0[n] if n in env0 else probe_shapes[n])

        # Recompute (ref optimizer.py:3491 RecomputeOptimizer): split the
        # forward region into segments ending at each checkpoint var's
        # producing op and wrap each in jax.checkpoint. The env handed
        # across a boundary is thinned to the variables genuinely needed
        # downstream — without thinning every intermediate would be a
        # segment output and nothing would be rematerialised.
        ckpt_names = [c for c in (bw_op.attrs.get("checkpoints") or []) if c]
        cuts = []
        needed_after = {}
        if ckpt_names:
            cuts = segment_cuts(region, ckpt_names)
            keep = set(getattr(ctx, "keep_names", ()) or ())
            keep.add(loss_name)
            program = getattr(ctx, "program", None)
            need = set(keep)
            for j in range(len(op_list) - 1, -1, -1):
                needed_after[j] = set(need)
                need.update(op_read_names(op_list[j], program))

        def fwd(primal_vals, _region=region, _tn=target_names,
                _ln=loss_name, _cuts=tuple(cuts)):
            by_name = dict(zip(_tn, primal_vals))
            e = dict(env0)
            for n, v in by_name.items():
                if n in env0:
                    e[n] = v

            def run_span(e_in, lo, hi):
                for j in range(lo, hi):
                    rop = _region[j]
                    if rop.type == "backward":
                        for gn in rop.output("Grads"):
                            e_in[gn] = lax.stop_gradient(cached_grads[gn])
                    else:
                        e_in = apply_op(rop, e_in, ctx, var_lookup,
                                        op_tag=tag_base + j)
                    for n in probe_at.get(j, ()):
                        # zero probe: identity on the value, carrier of
                        # d loss/d intermediate for the vjp. Also applies
                        # to Grads outputs of earlier backward ops so
                        # grad-of-grad targets work.
                        e_in[n] = e_in[n] + by_name[n]
                    for n in stop_at.get(j, ()):
                        e_in[n] = lax.stop_gradient(e_in[n])
                return e_in

            prev = 0
            for cut in _cuts:
                live = needed_after[cut]

                def seg(e_in, _lo=prev, _hi=cut + 1, _live=live):
                    ee = run_span(dict(e_in), _lo, _hi)
                    return {k: v for k, v in ee.items() if k in _live}

                e = jax.checkpoint(seg)(e)
                prev = cut + 1
            e = run_span(e, prev, len(_region))
            return e[_ln], e

        (loss_val, vjp_fn, env) = jax.vjp(fwd, primals, has_aux=True)
        init_grad = bw_op.input("InitGrad")
        if init_grad:
            # gradients(target_gradients=...): user-supplied vjp seed; the
            # seed var is produced by the region, so the aux env holds it.
            seed = jnp.broadcast_to(
                jnp.asarray(env[init_grad[0]], loss_val.dtype),
                loss_val.shape,
            )
        else:
            seed = jnp.ones_like(loss_val)
        (grads,) = vjp_fn(seed)
        grad_names = bw_op.output("Grads")
        # gradient-communication hook (parallel/comms): a dp grad-sync
        # program installs a callable that allreduces (optionally
        # quantized/bucketed) the raw grads HERE — between the backward
        # op and the optimizer ops that consume them — so XLA sees the
        # collectives interleaved with the remaining backward/update
        # compute and can overlap them.
        gc = getattr(ctx, "grad_comm", None)
        if gc is not None and block.idx == 0:
            synced = gc(dict(zip(grad_names, grads)))
            grads = [synced.get(n, g) for n, g in zip(grad_names, grads)]
        for n, g in zip(grad_names, grads):
            env[n] = g
            cached_grads[n] = g
    return env


_BLOCK_ATTRS = ("sub_block", "true_block", "false_block")


def op_read_names(op, program):
    """All var names an op may READ, including outer vars resolved inside
    its while/cond sub-blocks through the env closure (those never appear
    in the op's declared inputs). Needed by liveness analyses: thinning
    the env at a recompute/pipeline boundary using declared inputs alone
    would starve sub-block reads."""
    names = set()
    for ns in op.inputs.values():
        names.update(ns)
    if program is None:
        return names
    for attr in _BLOCK_ATTRS:
        idx = op.attrs.get(attr)
        if idx is None:
            continue
        try:
            blk = program.block(idx)
        except Exception:
            continue
        produced = set()
        for sop in blk.ops:
            names |= op_read_names(sop, program) - produced
            for ns in sop.outputs.values():
                produced.update(ns)
    return names


def producer_map(region):
    """name -> index of the op producing it (last writer wins). Shared by
    the recompute cut pass and the gradient probe placement."""
    produce = {}
    for j, rop in enumerate(region):
        for names in rop.outputs.values():
            for n in names:
                produce[n] = j
    return produce


def segment_cuts(region, cut_var_names):
    """Indices of ops ending a segment: each cut var's producing op closes
    its segment. A cut at the final op is dropped (no-op boundary). Shared
    by the recompute pass and the pipeline executor so stage/segment
    semantics can't diverge."""
    produce = producer_map(region)
    cuts = sorted({produce[c] for c in cut_var_names if c in produce})
    if cuts and cuts[-1] == len(region) - 1:
        cuts = cuts[:-1]
    return cuts


def _make_var_lookup(block):
    def lookup(name):
        blk = block
        while blk is not None:
            v = blk.vars.get(name)
            if v is not None:
                return v
            blk = blk.parent_block
        return None

    return lookup


def persistable_names(program):
    names = []
    for v in program.global_block().vars.values():
        if v.persistable:
            names.append(v.name)
    return names


def build_step_fn(program, feed_names, fetch_names, is_test=False,
                  extra_env=None, mesh_axes=None, platform=None, mesh=None,
                  grad_comm=None):
    """Return a pure function step(state, feeds, rng) -> (fetches, new_state).

    ``state`` / ``feeds`` are dicts name->array. ``new_state`` contains every
    persistable var that has a value after the run (parameters, optimizer
    accumulators, batch-norm stats, step counters, ...).

    ``grad_comm``: optional callable ``{grad_name: array} -> {grad_name:
    array}`` applied to the global block's backward-op gradients before
    the optimizer ops consume them (the gradient-communication hook;
    see :mod:`paddle_tpu.parallel.comms`).
    """
    block = program.global_block()
    op_list = list(block.ops)
    persist = set(persistable_names(program))

    def step(state, feeds, rng):
        ctx = LowerContext(rng=rng, is_test=is_test, program=program,
                           mesh_axes=mesh_axes, platform=platform,
                           mesh=mesh)
        ctx.grad_comm = grad_comm
        ctx.run_ops = run_ops  # control-flow ops recurse through this
        # names the recompute pass must keep live across jax.checkpoint
        # segment boundaries even if no later op consumes them
        ctx.keep_names = set(fetch_names) | persist
        env = {}
        if extra_env:
            env.update(extra_env)
        env.update(state)
        env.update(feeds)
        env = run_ops(block, op_list, env, ctx)
        missing = [n for n in fetch_names if n not in env]
        if missing:
            raise OpLoweringError(
                "fetch vars %s were never computed by the program" % missing
            )
        fetches = [env[n] for n in fetch_names]
        new_state = {n: env[n] for n in persist if n in env}
        return fetches, new_state

    return step
