"""Eager execution + autograd tape
(ref: paddle/fluid/imperative/tracer.cc, python/paddle/fluid/dygraph/base.py).

TPU-native: each eager op call runs its jax lowering immediately (jit-cached
by XLA at the lax level); the tape records (lowering, inputs, outputs) and
backward() replays it in reverse through jax.vjp — no per-op grad kernels.
"""
import numpy as np

import jax
import jax.numpy as jnp

from .. import core
from ... import ops as ops_lib
from ...ops.registry import LowerContext, get_lowering

# lazy: creating a PRNGKey initializes the jax backend, which must not
# happen at import time (another process may hold the chip)
_eager_rng = [None]
_rng_counter = [0]
_train_mode = [True]


def _next_eager_rng():
    if _eager_rng[0] is None:
        _eager_rng[0] = jax.random.PRNGKey(0)
    _rng_counter[0] += 1
    return jax.random.fold_in(_eager_rng[0], _rng_counter[0])


def seed(s):
    _eager_rng[0] = jax.random.PRNGKey(s)
    _rng_counter[0] = 0


def set_train_mode(mode):
    _train_mode[0] = bool(mode)


def in_train_mode():
    return _train_mode[0]


class VarBase:
    """Eager tensor (ref: framework.py ParamBase / imperative VarBase)."""

    _counter = [0]

    def __init__(self, value=None, name=None, stop_gradient=False,
                 persistable=False, trainable=True, dtype=None, shape=None):
        self.value = None if value is None else jnp.asarray(value)
        if name is None:
            VarBase._counter[0] += 1
            name = "eager_var_%d" % VarBase._counter[0]
        self.name = name
        self.stop_gradient = stop_gradient
        self.persistable = persistable
        self.trainable = trainable
        self.grad = None
        self._dtype_hint = dtype
        self.optimize_attr = {"learning_rate": 1.0}
        self.regularizer = None
        self.gradient_clip_attr = None

    # -- tensor interface ------------------------------------------------
    @property
    def shape(self):
        return tuple(self.value.shape) if self.value is not None else None

    @shape.setter
    def shape(self, _):
        # graph-mode layers annotate inferred shapes; eager shape always
        # comes from the concrete value, so the annotation is a no-op
        pass

    @property
    def dtype(self):
        if self.value is not None:
            return core.convert_dtype(self.value.dtype)
        return self._dtype_hint

    @property
    def lod_level(self):
        return 0

    def numpy(self):
        return np.asarray(self.value)

    def gradient(self):
        if self.grad is None:
            return None
        return np.asarray(self.grad)

    def clear_gradient(self):
        self.grad = None

    def detach(self):
        return VarBase(self.value, stop_gradient=True)

    def astype(self, dtype):
        return call_op(
            "cast", {"X": [self]}, {"out_dtype": core.convert_dtype(dtype)}
        )

    def set_value(self, value):
        self.value = jnp.asarray(value)

    def backward(self, backward_strategy=None, retain_graph=False):
        run_backward(self)

    def __repr__(self):
        return "VarBase(name=%s, shape=%s, dtype=%s%s)" % (
            self.name, self.shape, self.dtype,
            ", grad" if self.grad is not None else "",
        )

    def __len__(self):
        return int(self.value.shape[0])

    def __float__(self):
        return float(np.asarray(self.value).reshape(-1)[0])

    def __getitem__(self, item):
        return VarBase(self.value[item], stop_gradient=self.stop_gradient)


class Tracer:
    def __init__(self):
        self.tape = []
        self.enabled = True

    def reset(self):
        self.tape = []


_tracer = Tracer()


def get_tape():
    return _tracer.tape


def _is_float(v):
    try:
        return jnp.issubdtype(jnp.asarray(v).dtype, jnp.floating)
    except Exception:
        return False


def eager_run_op(type=None, inputs=None, outputs=None, attrs=None):
    """Execute one symbolic op eagerly; record on the tape. Matches the
    Block.append_op signature so LayerHelper routes here in dygraph mode."""
    inputs = inputs or {}
    outputs = outputs or {}
    attrs = dict(attrs or {})
    fn = get_lowering(type)
    ins_vb = {
        slot: [v for v in (vs if isinstance(vs, (list, tuple)) else [vs])]
        for slot, vs in inputs.items()
    }
    ins_vals = {
        slot: [v.value for v in vs] for slot, vs in ins_vb.items()
    }
    ctx = LowerContext(
        rng=_next_eager_rng(), is_test=not _train_mode[0]
    )
    out_vals = fn(ctx, ins_vals, attrs)
    outs_vb = {}
    for slot, vars_ in outputs.items():
        vars_ = vars_ if isinstance(vars_, (list, tuple)) else [vars_]
        vals = out_vals.get(slot, [])
        for i, var in enumerate(vars_):
            if i < len(vals):
                if not isinstance(var, VarBase):
                    raise TypeError(
                        "dygraph op '%s' output %s must be VarBase" % (type, slot)
                    )
                var.value = vals[i]
        outs_vb[slot] = list(vars_)

    needs_grad = any(
        isinstance(v, VarBase) and not v.stop_gradient and _is_float(v.value)
        for vs in ins_vb.values()
        for v in vs
    )
    if _tracer.enabled and needs_grad:
        _tracer.tape.append((type, fn, attrs, ins_vb, outs_vb,
                             ctx._rng, not _train_mode[0]))
        for vs in outs_vb.values():
            for v in vs:
                v.stop_gradient = False
    else:
        for vs in outs_vb.values():
            for v in vs:
                if v.value is not None and not needs_grad:
                    v.stop_gradient = True
    # single output convenience
    first_slot = next(iter(outputs), None)
    if first_slot is not None and len(outputs) == 1 and len(outs_vb[first_slot]) == 1:
        return outs_vb[first_slot][0]
    return outs_vb


def call_op(type, inputs, attrs=None, out_slots=("Out",), n_outs=None):
    """Functional eager op call: creates output VarBases itself."""
    outs = {}
    n_outs = n_outs or {}
    for slot in out_slots:
        k = n_outs.get(slot, 1)
        outs[slot] = [VarBase() for _ in range(k)]
    res = eager_run_op(type=type, inputs=inputs, outputs=outs, attrs=attrs)
    if isinstance(res, VarBase):
        return res
    if len(out_slots) == 1:
        vs = outs[out_slots[0]]
        return vs[0] if len(vs) == 1 else vs
    return outs


def run_backward(loss):
    """Reverse-mode sweep over the tape from `loss` (cotangent = ones)."""
    if loss.value is None:
        raise ValueError("backward() on empty VarBase")
    cotangents = {id(loss): jnp.ones_like(loss.value)}
    tape = _tracer.tape
    for (op_type, fn, attrs, ins_vb, outs_vb, rng, was_test) in reversed(tape):
        out_list = [v for vs in outs_vb.values() for v in vs]
        if not any(id(v) in cotangents for v in out_list):
            continue
        # differentiable input positions
        flat_ins = [(slot, i, v)
                    for slot, vs in ins_vb.items()
                    for i, v in enumerate(vs)]
        diff_pos = [
            (slot, i, v) for slot, i, v in flat_ins
            if not v.stop_gradient and _is_float(v.value)
        ]
        if not diff_pos:
            continue

        def fwd(primals):
            vals = {
                slot: [v.value for v in vs] for slot, vs in ins_vb.items()
            }
            for (slot, i, _), p in zip(diff_pos, primals):
                vals[slot][i] = p
            ctx = LowerContext(rng=rng, is_test=was_test)
            out = fn(ctx, vals, attrs)
            flat = []
            for slot, vs in outs_vb.items():
                ovals = out.get(slot, [])
                for j in range(len(vs)):
                    flat.append(ovals[j] if j < len(ovals) else None)
            return tuple(x for x in flat if x is not None)

        primals = [v.value for _, _, v in diff_pos]
        out_primals, vjp_fn = jax.vjp(fwd, primals)
        cts = []
        k = 0
        for slot, vs in outs_vb.items():
            for v in vs:
                if k < len(out_primals):
                    ct = cotangents.get(id(v))
                    if ct is None:
                        ct = jnp.zeros_like(out_primals[k])
                    elif not _is_float(out_primals[k]):
                        ct = jnp.zeros_like(out_primals[k])
                    cts.append(jnp.asarray(ct, out_primals[k].dtype)
                               if _is_float(out_primals[k])
                               else jnp.zeros_like(out_primals[k]))
                    k += 1
        (in_cts,) = vjp_fn(tuple(cts))
        for (slot, i, v), g in zip(diff_pos, in_cts):
            if g is None:
                continue
            prev = cotangents.get(id(v))
            cotangents[id(v)] = g if prev is None else prev + g
    # assign .grad on every input var that received a cotangent (params
    # accumulate across backward() calls, like the reference)
    seen = set()
    for (op_type, fn, attrs, ins_vb, outs_vb, rng, was_test) in tape:
        for vs in ins_vb.values():
            for v in vs:
                if id(v) in seen or id(v) not in cotangents:
                    continue
                seen.add(id(v))
                g = cotangents[id(v)]
                v.grad = g if v.grad is None else v.grad + g
    _tracer.tape = []
