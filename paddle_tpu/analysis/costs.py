"""Static cost model: per-op FLOPs, bytes moved, and a roofline
prediction of step time / MFU — computed BEFORE any XLA compile.

Like :mod:`.shapes`, this pass reuses the op lowering registry as the
single rule set: each op's lowering is traced with ``jax.make_jaxpr``
over the abstract shape env, and FLOPs are counted primitive by
primitive from the jaxpr (``dot_general``: 2·M·N·K,
``conv_general_dilated``: 2·out·k·Cin/g, elementwise: one per output
element, pure data movement: zero). Bytes per op are the op's input +
output footprints — the HBM traffic an unfused op would move, i.e. the
roofline's memory leg. The symbolic ``backward`` op is costed
analytically as 2x its forward region (the classic fwd:bwd ratio; the
vjp replay's duplicated forward is CSE'd by XLA, see lowering.run_ops).

The device table below is the ONE peak-FLOPs/HBM table the analyzer,
the planner and ``check_hbm_budget`` read. Env overrides (all optional)
calibrate or pin a profile where no table entry matches (CPU runs,
tests):

- ``PADDLE_TPU_PEAK_FLOPS`` — peak FLOPs/s
- ``PADDLE_TPU_HBM_BYTES``  — memory capacity in bytes
- ``PADDLE_TPU_HBM_BW``     — memory bandwidth in bytes/s
- ``PADDLE_TPU_ICI_BW``     — per-chip interconnect bandwidth in
  bytes/s (the gradient-allreduce leg; see
  :func:`ring_allreduce_seconds`)
- ``PADDLE_TPU_DCN_BW``     — per-chip CROSS-SLICE bandwidth in
  bytes/s (the data-center network leg a multi-slice allreduce rides)
- ``PADDLE_TPU_SLICE_CHIPS`` — chips one ICI slice can reach; groups
  wider than this pay the DCN wire (see :func:`allreduce_bandwidth`)
"""
import os

__all__ = [
    "DeviceProfile", "DEVICE_TABLE", "device_profile", "peak_flops",
    "require_device_profile", "OpCost", "op_costs", "jaxpr_flops",
    "CostReport", "analyze_cost", "predict_program",
    "ring_allreduce_seconds", "allreduce_bandwidth",
    "pipeline_bubble_fraction", "dp_grad_bytes", "ICI_BW_ENV",
    "DCN_BW_ENV", "SLICE_CHIPS_ENV", "CALIBRATION_ENV",
    "load_calibration",
]

PEAK_FLOPS_ENV = "PADDLE_TPU_PEAK_FLOPS"
HBM_BYTES_ENV = "PADDLE_TPU_HBM_BYTES"
HBM_BW_ENV = "PADDLE_TPU_HBM_BW"
ICI_BW_ENV = "PADDLE_TPU_ICI_BW"
DCN_BW_ENV = "PADDLE_TPU_DCN_BW"
SLICE_CHIPS_ENV = "PADDLE_TPU_SLICE_CHIPS"
# path to a calibration JSON written by DeviceProfile.calibrated_from;
# device_profile() layers it OVER the table match and UNDER the env
# overrides (operator pins always win)
CALIBRATION_ENV = "PADDLE_TPU_CALIBRATION_FILE"


class DeviceProfile:
    """Roofline constants of one accelerator: bf16 peak FLOPs/s, HBM
    capacity (bytes), HBM bandwidth (bytes/s), per-chip ICI
    (inter-chip interconnect) bandwidth (bytes/s — all links combined,
    the figure a ring allreduce rides), per-chip DCN bandwidth
    (bytes/s — what a collective pays once it crosses a slice
    boundary), and the chip count one ICI slice tops out at. Any field
    may be None (unknown) — consumers skip the corresponding
    check/prediction."""

    __slots__ = ("name", "peak_flops", "hbm_bytes", "hbm_bw", "ici_bw",
                 "dcn_bw", "slice_chips")

    def __init__(self, name, peak_flops=None, hbm_bytes=None, hbm_bw=None,
                 ici_bw=None, dcn_bw=None, slice_chips=None):
        self.name = name
        self.peak_flops = peak_flops
        self.hbm_bytes = hbm_bytes
        self.hbm_bw = hbm_bw
        self.ici_bw = ici_bw
        self.dcn_bw = dcn_bw
        self.slice_chips = slice_chips

    def copy(self):
        return DeviceProfile(self.name, self.peak_flops, self.hbm_bytes,
                             self.hbm_bw, self.ici_bw, self.dcn_bw,
                             self.slice_chips)

    def to_dict(self):
        return {"name": self.name, "peak_flops": self.peak_flops,
                "hbm_bytes": self.hbm_bytes, "hbm_bw": self.hbm_bw,
                "ici_bw": self.ici_bw, "dcn_bw": self.dcn_bw,
                "slice_chips": self.slice_chips}

    def __repr__(self):
        return ("DeviceProfile(%r, peak_flops=%r, hbm_bytes=%r, "
                "hbm_bw=%r, ici_bw=%r, dcn_bw=%r, slice_chips=%r)"
                % (self.name, self.peak_flops, self.hbm_bytes,
                   self.hbm_bw, self.ici_bw, self.dcn_bw,
                   self.slice_chips))

    @classmethod
    def calibrated_from(cls, ledger, measured_steps=None, path=None):
        """Fit *effective* peak-FLOPs / HBM-BW from measured step
        times in an executable ledger (the live
        ``observability.ExecutableLedger``, its ``snapshot()`` dict,
        or a bare entry list). ``measured_steps`` ({fingerprint:
        seconds}) augments/overrides the per-entry
        ``measured_step_seconds``.

        Two fit rungs, best first:

        - **ratio**: entries carrying both a prediction made under a
          known profile (``predicted["device"]``) and a measurement
          scale that profile's peak_flops/hbm_bw by the median
          ``predicted_step / measured_step``. The roofline's per-op
          ``max(compute leg, memory leg)`` sum scales inversely with
          a common factor on both constants, so the re-prediction
          under the calibrated profile lands on the measurement
          exactly (modulo run-to-run noise).
        - **rate** (fallback, no usable prediction): effective
          FLOPs/s and bytes/s as the median ``flops / measured`` and
          ``bytes / measured`` over entries (XLA's ``cost_analysis``
          figures when present, else the analyzer totals). An upper
          bound per leg — the per-op max-sum may over-predict up to
          2x — but it turns "no profile" into a usable one.

        With ``path`` the fit is also written as a calibration JSON
        that :func:`device_profile` layers under the env overrides
        (point ``PADDLE_TPU_CALIBRATION_FILE`` at it). Returns the
        calibrated profile, or None when no entry had a usable
        measurement."""
        entries, extra_measured = _ledger_entries(ledger)
        measured = dict(extra_measured)
        measured.update(measured_steps or {})
        ratio, peaks, bws, hbm_caps = [], [], [], []
        rate_flops, rate_bytes = [], []
        n_used = 0
        for e in entries:
            if not isinstance(e, dict):
                continue
            fp = e.get("fingerprint")
            t = measured.get(fp) or e.get("measured_step_seconds")
            if not t or t <= 0:
                continue
            n_used += 1
            pred = e.get("predicted") or {}
            dev = pred.get("device") or {}
            ps = pred.get("predicted_step_seconds")
            if ps and ps > 0 and (dev.get("peak_flops")
                                  or dev.get("hbm_bw")):
                r = float(ps) / float(t)
                ratio.append(r)
                if dev.get("peak_flops"):
                    peaks.append(float(dev["peak_flops"]) * r)
                if dev.get("hbm_bw"):
                    bws.append(float(dev["hbm_bw"]) * r)
                if dev.get("hbm_bytes"):
                    hbm_caps.append(float(dev["hbm_bytes"]))
            xla = e.get("xla") or {}
            f = xla.get("flops") or pred.get("total_flops")
            b = xla.get("bytes_accessed") or pred.get("total_bytes")
            if f and f > 0:
                rate_flops.append(float(f) / float(t))
            if b and b > 0:
                rate_bytes.append(float(b) / float(t))
        if peaks or bws:
            method = "ratio"
            peak = _median(peaks)
            bw = _median(bws)
        elif rate_flops or rate_bytes:
            method = "rate"
            peak = _median(rate_flops)
            bw = _median(rate_bytes)
        else:
            return None
        prof = cls("calibrated", peak_flops=peak, hbm_bw=bw,
                   hbm_bytes=_median(hbm_caps))
        if path:
            doc = prof.to_dict()
            doc["fit"] = {
                "method": method,
                "entries_used": n_used,
                "ratio_median": round(_median(ratio), 6)
                if ratio else None,
            }
            import json

            tmp = "%s.tmp-%d" % (path, os.getpid())
            with open(tmp, "w", encoding="utf-8") as fh:
                json.dump(doc, fh, indent=1)
            os.replace(tmp, path)
        return prof


# Public per-chip figures, matched by device_kind substring — the
# LONGEST matching key wins ("v5p" beats a shorter key regardless of
# row order, so adding rows can never shadow existing ones). A v5e
# chip reports device_kind "TPU v5 lite" (chip run, PR 21); "v5e" is
# the marketing name tools print. There is no bare "v5" row: a part
# this table does not know gets no peaks, never another part's.
# bf16 peak FLOPs/s, HBM bytes, HBM bytes/s, ICI bytes/s (all links
# per chip), DCN bytes/s per chip, max chips per ICI slice.
_V5E = ("v5e", 197e12, 16e9, 819e9, 200e9, 12.5e9, 256)
DEVICE_TABLE = [
    ("v6", DeviceProfile("v6e", 918e12, 32e9, 1640e9, 448e9,
                         25e9, 256)),
    ("v5p", DeviceProfile("v5p", 459e12, 95e9, 2765e9, 600e9,
                          25e9, 8960)),
    ("v5 lite", DeviceProfile(*_V5E)),
    ("v5e", DeviceProfile(*_V5E)),
    ("v4", DeviceProfile("v4", 275e12, 32e9, 1228e9, 300e9,
                         12.5e9, 4096)),
    ("v3", DeviceProfile("v3", 123e12, 32e9, 900e9, 82e9,
                         6.25e9, 1024)),
    ("v2", DeviceProfile("v2", 45e12, 16e9, 700e9, 62e9,
                         6.25e9, 512)),
]


def _env_float(name):
    v = os.environ.get(name)
    if not v:
        return None
    try:
        return float(v)
    except ValueError:
        return None


# one-slot mtime cache: the calibration file is read once per mtime,
# not once per device_profile() call (executors resolve profiles on
# every compile)
_cal_cache = {"path": None, "mtime": None, "doc": None}


def load_calibration(path=None):
    """The calibration JSON written by
    :meth:`DeviceProfile.calibrated_from`, as a dict of profile fields
    (or None). ``path`` defaults to ``$PADDLE_TPU_CALIBRATION_FILE``.
    A torn/corrupt file (truncated mid-write, non-JSON bytes, wrong
    schema, bool/NaN/inf constants) warns once per mtime and resolves
    to None — the profile falls back to the table; a stale or mangled
    calibration must never crash a serving process."""
    path = path or os.environ.get(CALIBRATION_ENV)
    if not path:
        return None
    try:
        mtime = os.stat(path).st_mtime
    except OSError:
        return None
    if _cal_cache["path"] == path and _cal_cache["mtime"] == mtime:
        return _cal_cache["doc"]
    doc = None
    why = None
    try:
        import json
        import math

        with open(path, "r", encoding="utf-8") as f:
            raw = json.load(f)
        if isinstance(raw, dict):
            doc = {}
            for k in ("name", "peak_flops", "hbm_bytes", "hbm_bw",
                      "ici_bw", "dcn_bw", "slice_chips"):
                v = raw.get(k)
                if k == "name":
                    if isinstance(v, str):
                        doc[k] = v
                elif (isinstance(v, (int, float))
                      and not isinstance(v, bool)
                      and math.isfinite(v) and v > 0):
                    doc[k] = float(v)
            if not any(k != "name" for k in doc):
                doc = None
                why = "no usable numeric field"
        else:
            why = "top-level %s, want an object" % type(raw).__name__
    except Exception as e:  # noqa: BLE001 — torn write, bad bytes, ...
        doc = None
        why = "%s: %s" % (type(e).__name__, str(e)[:120])
    if doc is None and why is not None:
        # once per mtime: the cache short-circuits until the file
        # changes again, so a bad file cannot spam a serving loop
        import warnings

        warnings.warn(
            "ignoring corrupt calibration file %s (%s); falling back "
            "to the device table" % (path, why), RuntimeWarning,
            stacklevel=2)
    _cal_cache.update(path=path, mtime=mtime, doc=doc)
    return doc


def _median(xs):
    xs = sorted(x for x in xs if x is not None)
    if not xs:
        return None
    n = len(xs)
    mid = xs[n // 2]
    return mid if n % 2 else (xs[n // 2 - 1] + mid) / 2.0


def _ledger_entries(ledger):
    """(entries, measured) from an ExecutableLedger, its snapshot()
    dict, or a bare entry list."""
    if ledger is None:
        return [], {}
    snap = getattr(ledger, "snapshot", None)
    if callable(snap):
        ledger = snap()
    if isinstance(ledger, dict):
        return (list(ledger.get("entries") or ()),
                dict(ledger.get("measured") or {}))
    return list(ledger), {}


def device_profile(device_kind=None):
    """Resolve a :class:`DeviceProfile` for a jax ``device_kind`` string
    (substring match against the table; when several keys match, the
    LONGEST — most specific — wins, so the result is independent of
    table row order), then layer the calibration file
    (``$PADDLE_TPU_CALIBRATION_FILE``, measured effective constants)
    and finally the env overrides (operator pins always win). Returns
    None when neither the table, the calibration, nor any override
    knows the device — callers must treat that as "no prediction
    possible"."""
    prof = None
    dk = (device_kind or "").lower()
    best_key = None
    for key, p in DEVICE_TABLE:
        if key in dk and (best_key is None or len(key) > len(best_key)):
            best_key = key
            prof = p.copy()
    cal = load_calibration()
    over = {
        "peak_flops": _env_float(PEAK_FLOPS_ENV),
        "hbm_bytes": _env_float(HBM_BYTES_ENV),
        "hbm_bw": _env_float(HBM_BW_ENV),
        "ici_bw": _env_float(ICI_BW_ENV),
        "dcn_bw": _env_float(DCN_BW_ENV),
        "slice_chips": _env_float(SLICE_CHIPS_ENV),
    }
    if (prof is None and cal is None
            and not any(v is not None for v in over.values())):
        return None
    if prof is None:
        prof = DeviceProfile(device_kind or "env")
    if cal is not None:
        for k, v in cal.items():
            if k != "name":
                setattr(prof, k, v)
        prof.name = "%s+cal" % prof.name
    for k, v in over.items():
        if v is not None:
            setattr(prof, k, v)
    return prof


def peak_flops(device_kind):
    """bf16 peak FLOPs/s for a device_kind, or None when the device is
    unknown (the analyzer then makes no prediction)."""
    p = device_profile(device_kind)
    return p.peak_flops if p is not None else None


def require_device_profile(device_kind):
    """:func:`device_profile` for a measurement path: an unknown device
    is an error there, never a default and never a silently dropped
    utilisation figure."""
    p = device_profile(device_kind)
    if p is None or not p.peak_flops:
        raise LookupError(
            "device_kind %r is not in analysis.costs.DEVICE_TABLE (keys "
            "%s) — add a row with its published peaks and their source"
            % (device_kind, [k for k, _ in DEVICE_TABLE]))
    return p


def ring_allreduce_seconds(n_bytes, n_shards, ici_bw):
    """Bandwidth term of one (ring or two-shot) allreduce of
    ``n_bytes`` over ``n_shards`` chips at ``ici_bw`` bytes/s per chip:
    every chip sends and receives ``2 (n-1)/n * n_bytes`` concurrently,
    so the wall time is that divided by the per-chip bandwidth. 0.0
    when there is nothing to reduce across (n < 2) or the bandwidth is
    unknown."""
    n = max(1, int(n_shards))
    if n < 2 or not ici_bw:
        return 0.0
    return 2.0 * (n - 1) / n * float(n_bytes) / float(ici_bw)


def allreduce_bandwidth(profile, group_size):
    """(bytes/s, wire) the allreduce over ``group_size`` chips rides:
    the ICI figure while the group fits one slice, the per-chip DCN
    figure once it spills over ``profile.slice_chips``. Falls back to
    ICI when the DCN figure is unknown (single-slice optimism is better
    than no prediction)."""
    if profile is None:
        return None, "ici"
    n = max(1, int(group_size))
    cap = profile.slice_chips
    if cap and n > int(cap) and profile.dcn_bw:
        return profile.dcn_bw, "dcn"
    return profile.ici_bw, "ici"


def pipeline_bubble_fraction(pp, microbatches):
    """GPipe fill/drain overhead as a fraction of useful compute:
    (pp-1)/microbatches. 0.0 for a single stage; with one microbatch a
    pp-stage schedule is fully serial (fraction pp-1)."""
    pp = max(1, int(pp))
    m = max(1, int(microbatches or 1))
    return float(pp - 1) / float(m)


def dp_grad_bytes(program, env=None):
    """fp32 bytes one data-parallel step must allreduce: the backward
    op's gradient footprint when the program trains, else the
    trainable-parameter footprint (inference dumps of a training model
    — what an equivalent training step would sync). Deterministic, so
    the comm prediction below and parallel/comms' live wire accounting
    agree on what counts."""
    import numpy as np

    gb = program.global_block()
    total = 0.0
    for op in gb.ops:
        if op.type != "backward":
            continue
        for g in op.output("Grads"):
            if env is not None and g in env:
                total += _spec_nbytes(env[g])
    if total:
        return total
    for p in gb.all_parameters():
        if not getattr(p, "trainable", True):
            continue
        shape = tuple(getattr(p, "shape", ()) or ())
        if not shape or not all(isinstance(d, int) and d > 0
                                for d in shape):
            continue
        total += float(np.prod(shape)) * 4.0
    return total


# -- per-primitive FLOP counting over a jaxpr -------------------------------

# primitives that move/reshape data without arithmetic
_ZERO_FLOP_PRIMS = frozenset({
    "reshape", "broadcast_in_dim", "transpose", "convert_element_type",
    "bitcast_convert_type", "slice", "dynamic_slice",
    "dynamic_update_slice", "concatenate", "pad", "squeeze", "rev",
    "iota", "copy", "device_put", "stop_gradient", "split",
    "gather", "expand_dims", "real", "imag", "empty",
})


def _aval_size(aval):
    n = 1
    for d in getattr(aval, "shape", ()) or ():
        n *= int(d)
    return n


def _sub_jaxprs(params):
    subs = []
    for v in params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for u in vs:
            if hasattr(u, "jaxpr"):          # ClosedJaxpr
                subs.append(u.jaxpr)
            elif hasattr(u, "eqns"):         # Jaxpr
                subs.append(u)
    return subs


def jaxpr_flops(jaxpr):
    """Deterministic FLOP count of a jaxpr: exact for matmul/conv, one
    per output element for everything arithmetic, zero for pure data
    movement. ``scan`` bodies multiply by trip count; ``while`` bodies
    count one trip (trip count is value-dependent); ``cond`` takes the
    most expensive branch."""
    total = 0.0
    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        subs = _sub_jaxprs(eqn.params)
        if subs:
            inner = [jaxpr_flops(s) for s in subs]
            if prim == "scan":
                total += float(eqn.params.get("length", 1)) * sum(inner)
            elif prim == "cond":
                total += max(inner)
            else:  # pjit / while / remat / custom_* wrappers
                total += sum(inner)
            continue
        total += _prim_flops(eqn, prim)
    return total


def _prim_flops(eqn, prim):
    out_size = max((_aval_size(v.aval) for v in eqn.outvars), default=0)
    if prim == "dot_general":
        (lhs_c, _rhs_c), _ = eqn.params["dimension_numbers"]
        lhs_shape = eqn.invars[0].aval.shape
        k = 1
        for d in lhs_c:
            k *= int(lhs_shape[d])
        return 2.0 * out_size * k
    if prim == "conv_general_dilated":
        dn = eqn.params["dimension_numbers"]
        rhs = eqn.invars[1].aval
        out_chan = int(rhs.shape[dn.rhs_spec[0]])
        # per output element: 2 * (kernel spatial x in-chan-per-group)
        return 2.0 * out_size * _aval_size(rhs) / max(out_chan, 1)
    if prim in _ZERO_FLOP_PRIMS or prim.startswith("scatter"):
        return 0.0
    if prim.startswith("reduce") or prim.startswith("arg") \
            or prim == "cumsum" or prim.startswith("cum"):
        # one op per INPUT element: reductions shrink the output
        return float(max((_aval_size(v.aval) for v in eqn.invars
                          if hasattr(v, "aval")), default=out_size))
    return float(out_size)


def _head_flops(op, env):
    """The fused vocabulary head at its most: its loop runs once per chunk
    of LABELLED rows, a count no shape gives, so the bound is every row
    labelled: one product of all rows with W and the softmax's passes
    over rows x vocabulary (the count of the pair it replaces)."""
    x, w = env[op.input("X")[0]], env[op.input("W")[0]]
    rows = _aval_size(x) // int(x.shape[-1])
    vocab, hidden = (int(d) for d in w.shape)
    return 2.0 * rows * hidden * vocab + 6.0 * rows * vocab


# ops whose lowering loops a value-dependent number of times (jaxpr_flops
# counts one trip of a `while`): costed by rule, at their upper bound
_FLOP_RULES = {"linear_softmax_with_cross_entropy": _head_flops}


# -- per-op costing over a Program ------------------------------------------

class OpCost:
    """FLOPs + bytes of one global-block op."""

    __slots__ = ("op_index", "op_type", "flops", "bytes", "op")

    def __init__(self, op_index, op_type, flops, bytes_, op=None):
        self.op_index = op_index
        self.op_type = op_type
        self.flops = flops
        self.bytes = bytes_
        self.op = op

    @property
    def intensity(self):
        """Arithmetic intensity (flops per HBM byte)."""
        if not self.bytes:
            return None
        return self.flops / self.bytes

    def to_dict(self):
        d = {"op_index": self.op_index, "op_type": self.op_type,
             "flops": round(self.flops, 1), "bytes": round(self.bytes, 1)}
        if self.intensity is not None:
            d["intensity"] = round(self.intensity, 3)
        return d


def op_costs(program, env, is_test=False, platform="cpu"):
    """Per-op FLOPs/bytes for the global block by tracing each op's
    lowering with ``jax.make_jaxpr`` over the abstract env from
    :func:`.shapes.propagate`. Ops whose inputs never resolved (or
    whose lowering cannot trace) are skipped. The ``backward`` op is
    costed analytically: 2x the FLOPs/bytes of its forward region."""
    import jax

    from ..fluid import lowering
    from ..ops.registry import LowerContext
    from . import walker

    gb = program.global_block()
    var_lookup = lowering._make_var_lookup(gb)
    rng = jax.random.PRNGKey(0)
    out = []
    fwd_flops = 0.0   # running non-backward totals (the backward region)
    fwd_bytes = 0.0
    for i, op in enumerate(gb.ops):
        if op.type == "backward":
            grads = op.output("Grads")
            grad_bytes = sum(
                _spec_nbytes(env[g]) for g in grads if g in env)
            out.append(OpCost(i, op.type, 2.0 * fwd_flops,
                              2.0 * fwd_bytes + grad_bytes, op=op))
            continue
        reads = walker._op_reads(program, op)
        if any(n not in env for n in reads):
            continue
        sub_env = {n: env[n] for n in sorted(reads)}

        def f(e, _op=op, _i=i):
            ctx = LowerContext(rng=rng, is_test=is_test, program=program,
                               platform=platform)
            ctx.run_ops = lowering.run_ops
            e = lowering.apply_op(_op, dict(e), ctx, var_lookup, op_tag=_i)
            return {n: e[n] for ns in _op.outputs.values()
                    for n in ns if n in e}

        try:
            closed = jax.make_jaxpr(f)(sub_env)
        except Exception:  # noqa: BLE001 — shapes.propagate reports these
            continue
        rule = _FLOP_RULES.get(op.type)
        flops = rule(op, env) if rule else jaxpr_flops(closed.jaxpr)
        nbytes = (sum(_spec_nbytes(env[n]) for n in reads)
                  + sum(_spec_nbytes(env[n])
                        for ns in op.outputs.values() for n in ns
                        if n in env))
        out.append(OpCost(i, op.type, flops, float(nbytes), op=op))
        fwd_flops += flops
        fwd_bytes += float(nbytes)
    return out


def _spec_nbytes(spec):
    import numpy as np

    n = 1
    for d in getattr(spec, "shape", ()) or ():
        n *= int(d)
    return n * np.dtype(spec.dtype).itemsize


# -- report -----------------------------------------------------------------

class CostReport:
    """Per-op and per-program FLOPs/bytes + roofline prediction against
    one :class:`DeviceProfile`, plus the liveness peak-HBM estimate and
    (when ``dp_shards > 1``) the interconnect leg: predicted gradient
    allreduce seconds and data-parallel scaling efficiency."""

    def __init__(self, per_op, memory=None, profile=None, dp_shards=1,
                 grad_bytes=0.0, comm_overlap_ratio=0.0):
        self.per_op = list(per_op)
        self.memory = memory            # analysis.memory.MemoryReport
        self.profile = profile          # DeviceProfile or None
        self.dp_shards = max(1, int(dp_shards))
        self.grad_bytes = float(grad_bytes)
        self.comm_overlap_ratio = min(1.0, max(0.0,
                                               float(comm_overlap_ratio)))
        self.total_flops = float(sum(c.flops for c in self.per_op))
        self.total_bytes = float(sum(c.bytes for c in self.per_op))

    @property
    def intensity(self):
        if not self.total_bytes:
            return None
        return self.total_flops / self.total_bytes

    @property
    def predicted_step_seconds(self):
        """Roofline: each op pays max(compute leg, memory leg); the
        step is their sum (sequential dependency chain)."""
        p = self.profile
        if p is None or (not p.peak_flops and not p.hbm_bw):
            return None
        t = 0.0
        for c in self.per_op:
            legs = []
            if p.peak_flops:
                legs.append(c.flops / p.peak_flops)
            if p.hbm_bw:
                legs.append(c.bytes / p.hbm_bw)
            t += max(legs)
        return t

    @property
    def predicted_mfu(self):
        p = self.profile
        t = self.predicted_step_seconds
        if not t or p is None or not p.peak_flops:
            return None
        return self.total_flops / (t * p.peak_flops)

    @property
    def bound(self):
        """Whether the program as a whole is compute- or memory-bound
        under the profile (None when unpredictable)."""
        p = self.profile
        if p is None or not p.peak_flops or not p.hbm_bw:
            return None
        return ("compute"
                if self.total_flops / p.peak_flops
                >= self.total_bytes / p.hbm_bw else "memory")

    @property
    def comm_wire(self):
        """Which wire the gradient allreduce rides: "ici" while the dp
        group fits one slice, "dcn" once it spills past the profile's
        slice_chips."""
        _, wire = allreduce_bandwidth(self.profile, self.dp_shards)
        return wire

    @property
    def predicted_comm_seconds(self):
        """Gradient-allreduce wall seconds per step over the profile's
        interconnect — ICI while the dp group fits one slice, DCN when
        it crosses slices. None when there is no dp group, no gradient
        footprint, or the bandwidth is unknown."""
        bw, _ = allreduce_bandwidth(self.profile, self.dp_shards)
        if self.dp_shards < 2 or not self.grad_bytes or not bw:
            return None
        return ring_allreduce_seconds(self.grad_bytes, self.dp_shards, bw)

    @property
    def scaling_efficiency(self):
        """Predicted dp scaling efficiency: compute time over compute
        plus the EXPOSED comm leg (comm scaled by what bucketed overlap
        cannot hide). 1.0 means free scaling; None when either leg is
        unpredictable."""
        t = self.predicted_step_seconds
        c = self.predicted_comm_seconds
        if not t or c is None:
            return None
        exposed = c * (1.0 - self.comm_overlap_ratio)
        return t / (t + exposed)

    def hottest(self, k=5):
        """Top-k ops by FLOPs, descending (stable: ties break on op
        index)."""
        return sorted(self.per_op,
                      key=lambda c: (-c.flops, c.op_index))[:k]

    def to_dict(self, top=16):
        d = {
            "n_ops_costed": len(self.per_op),
            "total_flops": round(self.total_flops, 1),
            "total_bytes": round(self.total_bytes, 1),
        }
        if self.intensity is not None:
            d["intensity"] = round(self.intensity, 3)
        if self.profile is not None:
            d["device"] = self.profile.to_dict()
        t = self.predicted_step_seconds
        if t is not None:
            d["predicted_step_seconds"] = float("%.6g" % t)
        mfu = self.predicted_mfu
        if mfu is not None:
            d["predicted_mfu"] = round(mfu, 4)
        if self.bound is not None:
            d["bound"] = self.bound
        if self.memory is not None:
            d["memory"] = self.memory.to_dict()
        if self.dp_shards > 1 and self.grad_bytes:
            comm = {
                "dp_shards": self.dp_shards,
                "grad_bytes": round(self.grad_bytes, 1),
                "overlap_ratio": round(self.comm_overlap_ratio, 4),
                "wire": self.comm_wire,
            }
            c = self.predicted_comm_seconds
            if c is not None:
                comm["predicted_allreduce_seconds"] = float("%.6g" % c)
            eff = self.scaling_efficiency
            if eff is not None:
                comm["scaling_efficiency"] = round(eff, 4)
            d["comm"] = comm
        d["hottest_ops"] = [c.to_dict() for c in self.hottest(top)]
        return d


def analyze_cost(program, env=None, feed_specs=None, state_specs=None,
                 feed_names=None, fetch_names=(), state_names=None,
                 is_test=False, platform="cpu", default_dim=None,
                 device_kind=None, param_shards=1, act_shards=1,
                 dp_shards=1, comm_overlap_ratio=0.0):
    """One-stop cost + memory analysis: propagate shapes (unless an
    ``env`` is supplied), cost every op, run the liveness peak-HBM
    estimate, and bind the device profile. With ``dp_shards > 1`` the
    report also carries the interconnect leg (gradient bytes, predicted
    allreduce seconds against the profile's ICI bandwidth, and dp
    scaling efficiency; ``comm_overlap_ratio`` is the fraction the
    bucketed backward-overlap scheduler hides — see
    parallel/comms/bucketing.py). Returns a :class:`CostReport`."""
    from . import memory, shapes

    if env is None:
        if feed_specs is None and feed_names:
            feed_specs = shapes.feed_specs_from_program(
                program, feed_names=list(feed_names),
                default_dim=default_dim)
        env, _ = shapes.propagate(
            program, feed_specs=feed_specs, state_specs=state_specs,
            is_test=is_test, platform=platform, default_dim=default_dim,
            check_declared=False)
    per_op = op_costs(program, env, is_test=is_test, platform=platform)
    mem = memory.estimate(
        program, env=env, feed_specs=feed_specs, state_specs=state_specs,
        fetch_names=fetch_names, state_names=state_names,
        default_dim=default_dim, param_shards=param_shards,
        act_shards=act_shards)
    grad_bytes = dp_grad_bytes(program, env) if int(dp_shards) > 1 else 0.0
    return CostReport(per_op, memory=mem,
                      profile=device_profile(device_kind),
                      dp_shards=dp_shards, grad_bytes=grad_bytes,
                      comm_overlap_ratio=comm_overlap_ratio)


def predict_program(program, feed_specs=None, fetch_names=(),
                    state_specs=None, device_kind=None, is_test=False,
                    default_dim=None):
    """Bench-friendly wrapper: :func:`analyze_cost` flattened to a plain
    dict (``predicted_step_seconds``, ``predicted_mfu``, ``total_flops``,
    ``total_bytes``, ``predicted_peak_hbm_bytes``)."""
    rep = analyze_cost(
        program, feed_specs=feed_specs, state_specs=state_specs,
        fetch_names=fetch_names, is_test=is_test,
        default_dim=default_dim, device_kind=device_kind)
    out = {
        "total_flops": rep.total_flops,
        "total_bytes": rep.total_bytes,
        "predicted_step_seconds": rep.predicted_step_seconds,
        "predicted_mfu": rep.predicted_mfu,
        "bound": rep.bound,
    }
    if rep.memory is not None:
        out["predicted_peak_hbm_bytes"] = rep.memory.peak_bytes
    # the profile the prediction was made under — what
    # DeviceProfile.calibrated_from's ratio fit rescales
    out["device"] = (rep.profile.to_dict()
                     if rep.profile is not None else None)
    return out
