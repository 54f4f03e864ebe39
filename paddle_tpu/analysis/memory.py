"""Liveness-based peak-HBM estimation (static, pure python).

Fluid's ``memory_optimize``/``DistributeTranspiler`` memory passes
rewrote the program to reuse buffers; under XLA the compiler does that
reuse, so what the framework owes the user instead is a *prediction*:
will this program fit, and which op is resident at the peak? This pass
answers that with def-use liveness over the global block (sub-block
closure reads included via :func:`.walker._op_reads`):

- persistable state (params, optimizer moments) is live for the whole
  step — divided by ``param_shards`` when the mesh shards parameters
  (ZeRO/tp);
- every other name is live from its defining op through its last
  reader (fetch targets stay live to the end) — divided by
  ``act_shards`` when the mesh shards the batch (dp/sp);
- the symbolic ``backward`` op reads every activation its forward
  region produced (vjp residuals), so activations stay resident
  through it — exactly the "peak at the backward pass" shape real
  training has.

Sizes come from the inferred shape env when available (exact), else
from feed/state specs, else from declared var metadata with ``-1``
dims resolved to ``default_dim``. The result is an *estimate* —
XLA fusion avoids materializing some intermediates — but it is a
usable upper bound for admission control and capacity planning.
"""
import numpy as np

from . import walker

__all__ = ["MemoryReport", "estimate", "sizes_from", "shard_divisors",
           "var_nbytes"]

DEFAULT_DIM = 8  # matches shapes.DEFAULT_DIM (keep import-light)

# mesh axis names that shard the BATCH (divide activations); every
# other axis is assumed to shard parameters (tp/mp/ZeRO) — including
# ep, which rows-shards embedding tables (paddle_tpu.retrieval), so an
# ep-width mesh divides the table's HBM residency, not the batch
_BATCH_AXES = ("dp", "data", "batch", "sp", "seq")


def shard_divisors(mesh):
    """``{axis: size}`` -> ``(param_shards, act_shards)``: batch-like
    axes divide activation footprints, everything else (tp/mp/ZeRO/ep)
    divides parameter footprints."""
    param_shards = act_shards = 1
    for axis, size in (mesh or {}).items():
        if str(axis).lower() in _BATCH_AXES:
            act_shards *= int(size)
        else:
            param_shards *= int(size)
    return max(param_shards, 1), max(act_shards, 1)


def var_nbytes(shape, dtype, default_dim=None):
    """Bytes of a declared (shape, dtype) with -1 dims resolved to
    ``default_dim``; None when the shape is unknown."""
    if shape is None:
        return None
    default_dim = DEFAULT_DIM if default_dim is None else default_dim
    n = 1
    for d in shape:
        n *= default_dim if (d is None or d < 0) else int(d)
    try:
        item = np.dtype(dtype or "float32").itemsize
    except TypeError:
        from ..fluid import core

        item = np.dtype(core.np_dtype(dtype)).itemsize
    return n * item


def _spec_nbytes(spec):
    n = 1
    for d in getattr(spec, "shape", ()) or ():
        n *= int(d)
    return n * np.dtype(spec.dtype).itemsize


def sizes_from(program, env=None, feed_specs=None, state_specs=None,
               default_dim=None):
    """name -> bytes for every sizable name: inferred env first
    (exact), then feed/state specs (real arrays at the executor gate),
    then declared var metadata across all blocks."""
    sizes = {}
    for name, v in _iter_declared_vars(program):
        b = var_nbytes(v.shape, v.dtype, default_dim)
        if b is not None:
            sizes[name] = b
    for src in (state_specs, feed_specs, env):
        for name, spec in (src or {}).items():
            try:
                sizes[name] = _spec_nbytes(spec)
            except TypeError:
                pass
    return sizes


def _iter_declared_vars(program):
    for block in program.blocks:
        for name, v in block.vars.items():
            yield name, v


class MemoryReport:
    """Peak live-set estimate with op attribution."""

    __slots__ = ("peak_bytes", "peak_op_index", "peak_op_type",
                 "param_bytes", "act_bytes_at_peak", "n_ops",
                 "param_shards", "act_shards", "top", "unsized")

    def __init__(self, peak_bytes, peak_op_index, peak_op_type,
                 param_bytes, act_bytes_at_peak, n_ops, param_shards,
                 act_shards, top, unsized):
        self.peak_bytes = peak_bytes
        self.peak_op_index = peak_op_index
        self.peak_op_type = peak_op_type
        self.param_bytes = param_bytes
        self.act_bytes_at_peak = act_bytes_at_peak
        self.n_ops = n_ops
        self.param_shards = param_shards
        self.act_shards = act_shards
        self.top = top          # [(name, bytes)] largest residents at peak
        self.unsized = unsized  # names with no shape info (uncounted)

    def to_dict(self):
        d = {
            "peak_bytes": int(self.peak_bytes),
            "param_bytes": int(self.param_bytes),
            "act_bytes_at_peak": int(self.act_bytes_at_peak),
            "n_ops": self.n_ops,
            "top_residents": [
                {"name": n, "bytes": int(b)} for n, b in self.top],
        }
        if self.peak_op_index is not None:
            d["peak_op_index"] = self.peak_op_index
            d["peak_op_type"] = self.peak_op_type
        if self.param_shards != 1 or self.act_shards != 1:
            d["param_shards"] = self.param_shards
            d["act_shards"] = self.act_shards
        if self.unsized:
            d["unsized_vars"] = len(self.unsized)
        return d


def _head_workspace(program, op, sizes):
    """Scratch of the fused vocabulary head (its forward and, under the
    backward op, its gradient loop): two float32 arrays of one chunk of
    rows by the vocabulary (logits, softmax gradient) and the float32
    accumulator of W's gradient. The labelled rows are unknown before the
    labels are fed; the chunk is what the rows' bound allows."""
    from ..ops.loss_ops import head_chunk_rows

    gb = program.global_block()
    x, w = op.input("X")[0], op.input("W")[0]
    if x not in sizes or not gb.has_var(w) or not gb.has_var(x):
        return 0
    shape = gb.var(w).shape
    if not shape or len(shape) != 2 or min(shape) <= 0:
        return 0
    vocab, hidden = int(shape[0]), int(shape[1])
    rows = sizes[x] // var_nbytes((hidden,), gb.var(x).dtype)
    return 4 * (2 * head_chunk_rows(rows, vocab) * vocab + vocab * hidden)


# ops that hold scratch of their own beyond their named outputs
_WORKSPACE = {"linear_softmax_with_cross_entropy": _head_workspace}


def _ceil_div(a, b):
    return -(-int(a) // int(b))


def estimate(program, env=None, feed_specs=None, state_specs=None,
             fetch_names=(), state_names=None, default_dim=None,
             param_shards=1, act_shards=1, sizes=None,
             resident_names=(), alias_names=()):
    """Run the liveness walk; returns a :class:`MemoryReport`.

    ``state_names=None`` treats every persistable as state (executor
    semantics). ``param_shards``/``act_shards`` divide parameter and
    activation footprints (see :func:`shard_divisors`).
    ``resident_names`` pins names live across the WHOLE program
    regardless of their def/use span — e.g. the persistent slot cache a
    decode engine feeds to every step: def-use liveness would let a fed
    buffer die at its last reader, but the serving process holds it for
    the region's entire lifetime. ``alias_names`` are outputs written
    into a resident buffer's storage (a donated cache feed updated in
    place and fetched): they occupy nothing of their own."""
    gb = program.global_block()
    if sizes is None:
        sizes = sizes_from(program, env=env, feed_specs=feed_specs,
                           state_specs=state_specs,
                           default_dim=default_dim)
    if state_names is None:
        state_names = {n for n, v in gb.vars.items() if v.persistable}
    else:
        state_names = set(state_names)
    fetch_names = set(fetch_names or ())
    feed_names = set(feed_specs or ())
    resident_names = set(resident_names or ())
    alias_names = set(alias_names or ())

    param_bytes = sum(
        _ceil_div(sizes[n], param_shards)
        for n in state_names if n in sizes)
    unsized = sorted(
        n for n in state_names if n not in sizes)

    n_ops = len(gb.ops)
    if n_ops == 0:
        return MemoryReport(param_bytes, None, None, param_bytes, 0, 0,
                            param_shards, act_shards, [], unsized)

    # def/last-use per transient name; the backward op reads its whole
    # forward region's outputs (vjp residuals)
    first_def = {}
    last_use = {}
    produced_before = set()  # non-persistable outputs of preceding ops
    reads_at = []
    for i, op in enumerate(gb.ops):
        reads = set(walker._op_reads(program, op))
        if op.type == "backward":
            reads |= set(produced_before)
        reads_at.append(reads)
        for n in reads:
            last_use[n] = i
        for ns in op.outputs.values():
            for n in ns:
                first_def.setdefault(n, i)
                if n not in state_names:
                    produced_before.add(n)

    transient = {}
    seen_unsized = set(unsized)
    for n in set(first_def) | set(last_use) | feed_names | resident_names:
        if n in state_names or n in alias_names:
            continue
        if n not in sizes:
            if n not in seen_unsized:
                seen_unsized.add(n)
                unsized.append(n)
            continue
        start = first_def.get(n, 0) if n not in feed_names else 0
        end = last_use.get(n, start)
        if n in fetch_names:
            end = n_ops - 1
        if n in resident_names:
            start, end = 0, n_ops - 1
        end = max(end, start)
        transient[n] = (start, end, _ceil_div(sizes[n], act_shards))

    # sweep: +size at def, -size after last use
    delta = [0] * (n_ops + 1)
    held = 0   # scratch of the forward ops, held again by a backward op
    for i, op in enumerate(gb.ops):
        if op.type in _WORKSPACE:
            b = _ceil_div(_WORKSPACE[op.type](program, op, sizes),
                          act_shards)
            held = max(held, b)
        elif op.type == "backward":
            b = held
        else:
            continue
        delta[i] += b
        delta[i + 1] -= b
    for _n, (start, end, b) in transient.items():
        delta[start] += b
        delta[end + 1] -= b
    live = 0
    peak_live = -1
    peak_i = 0
    for i in range(n_ops):
        live += delta[i]
        if live > peak_live:
            peak_live = live
            peak_i = i
    peak_live = max(peak_live, 0)

    top = sorted(
        ((n, b) for n, (s, e, b) in transient.items()
         if s <= peak_i <= e),
        key=lambda kv: (-kv[1], kv[0]))[:8]
    return MemoryReport(
        peak_bytes=param_bytes + peak_live,
        peak_op_index=peak_i,
        peak_op_type=gb.ops[peak_i].type,
        param_bytes=param_bytes,
        act_bytes_at_peak=peak_live,
        n_ops=n_ops,
        param_shards=param_shards,
        act_shards=act_shards,
        top=top,
        unsized=sorted(unsized),
    )
