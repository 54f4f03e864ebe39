"""`kda_scan` (the gated delta rule with a decay a channel in its chunked
form, from a carried state) and `kda_step` (one position on a slot's float32
state) against the recurrence position by position
(benchmark/reference/solar_open2_lm.py `delta_rule`), float32 against
float32 on the CPU: 1e-4; and the tile the experts' grouped products take at
Solar-Open2's widths."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import LOWERINGS, hybrid_ops

from benchmark.reference import solar_open2_lm as ref

HEADS, DIM = 4, 16
WIDTH = HEADS * DIM
RNG = np.random.default_rng(43)


def lower(op, ins, **attrs):
    ins = {k: [jnp.asarray(v)] for k, v in ins.items() if v is not None}
    out = jax.jit(lambda ins: LOWERINGS[op](None, ins, attrs))(ins)
    return {k: np.asarray(v[0]) for k, v in out.items()}


# -- the ops against the recurrence ------------------------------------------
ATTRS = dict(heads=HEADS, head_dim=DIM, beta_scale=2.0)


def kda_operands(b, t, decay=3.0):
    """Raw operands of the two ops: q, k, v as convolutions leave them, a
    raw log decay wide enough that g reaches -5 a position and far beyond
    (softplus of up to 3 x 3 times exp(A_log) up to 16), raw beta whose
    doubled sigmoid passes 1 in half the positions."""
    ins = {name: RNG.normal(size=(b, t, WIDTH)).astype(np.float32)
           for name in ("Q", "K", "V")}
    ins["G"] = decay * RNG.normal(size=(b, t, WIDTH)).astype(np.float32)
    ins["Beta"] = 2 * RNG.normal(size=(b, t, HEADS)).astype(np.float32)
    ins["ALog"] = np.log(RNG.uniform(1, 16, HEADS)).astype(np.float32)
    ins["DtBias"] = RNG.normal(size=(WIDTH,)).astype(np.float32)
    return ins


def recurrence(ins, b, stop=None):
    """Row b of the operands through the reference's token-by-token scan."""
    t = ins["Q"].shape[1]
    qf, kf, vf, g, beta = hybrid_ops._kda_inputs(
        *(jnp.asarray(ins[n][b]) for n in ("Q", "K", "V", "G", "Beta")),
        jnp.asarray(ins["ALog"]), jnp.asarray(ins["DtBias"]), ATTRS)
    assert float(g.min()) < -5 and float(beta.max()) > 1.5
    o, s = ref.delta_rule(qf, kf, vf, g, beta, stop)
    return np.asarray(o).reshape(t, WIDTH), np.asarray(s)


@pytest.mark.parametrize("t,chunk,lens", [
    (64, 16, None),            # whole chunks, sub-chunks of 16
    (50, 16, (37, 50)),        # no multiple, right-padded
    (130, 64, (70, 129)),      # the published chunk, a second group's tail
    (20, 64, (1, 20)),         # shorter than a chunk
    (7, 4, (3, 7)),            # a chunk no sub-chunk divides
])
def test_the_chunked_scan_is_the_recurrence(t, chunk, lens):
    """Strong decays (g far below -5 a position: `exp(-cumsum(g))` over a
    chunk would overflow float32) and beta up to 2; the state stops at
    `Len`."""
    ins = kda_operands(2, t)
    if lens:
        ins["Len"] = np.asarray(lens)[:, None]
    got = lower("kda_scan", ins, chunk=chunk, **ATTRS)
    assert np.isfinite(got["O"]).all() and np.isfinite(got["StateOut"]).all()
    for b in range(2):
        stop = lens[b] if lens else t
        want_o, want_s = recurrence(ins, b, stop)
        np.testing.assert_allclose(got["O"][b, :stop], want_o[:stop],
                                   atol=1e-4)
        np.testing.assert_allclose(got["StateOut"][b], want_s, atol=1e-4)


def test_a_scan_in_two_halves_from_a_carried_state_is_the_whole():
    """A prompt scanned in two halves, the second from the first's state
    and convolution windows, gives what the whole gives: the state goes in
    as the window goes into `causal_conv1d`."""
    t, cut = 48, 29
    raw = {n: RNG.normal(size=(1, t, WIDTH)).astype(np.float32)
           for n in ("Q", "K", "V")}
    taps = (0.3 * RNG.normal(size=(WIDTH, 4))).astype(np.float32)
    ins = kda_operands(1, t)

    def conv(x, state=None):
        out = lower("causal_conv1d", {"X": x, "Weight": taps, "State": state},
                    activation="silu")
        return out["Out"], out["StateOut"]

    def scan(lo, hi, state=None, windows=(None,) * 3):
        part = {k: (v[:, lo:hi] if v.ndim == 3 else v)
                for k, v in ins.items()}
        handed = []
        for name, window in zip(("Q", "K", "V"), windows):
            part[name], w_out = conv(raw[name][:, lo:hi], window)
            handed.append(w_out)
        part["State"] = state
        out = lower("kda_scan", part, chunk=16, **ATTRS)
        return out["O"], out["StateOut"], handed

    whole_o, whole_s, whole_w = scan(0, t)
    first_o, first_s, first_w = scan(0, cut)
    second_o, second_s, second_w = scan(cut, t, first_s, first_w)
    np.testing.assert_allclose(np.concatenate([first_o, second_o], 1),
                               whole_o, atol=1e-4)
    np.testing.assert_allclose(second_s, whole_s, atol=1e-4)
    for a, b in zip(second_w, whole_w):
        np.testing.assert_array_equal(a, b)


def test_a_step_after_a_scan_is_the_scan_one_longer():
    t = 33
    ins = kda_operands(2, t + 1)
    whole = lower("kda_scan", ins, chunk=16, **ATTRS)
    before = lower("kda_scan", {k: (v[:, :t] if v.ndim == 3 else v)
                                for k, v in ins.items()}, chunk=16, **ATTRS)
    last = {k: (v[:, t] if v.ndim == 3 else v) for k, v in ins.items()}
    got = lower("kda_step", dict(last, State=before["StateOut"]), **ATTRS)
    np.testing.assert_allclose(got["O"], whole["O"][:, t], atol=1e-4)
    np.testing.assert_allclose(got["StateOut"], whole["StateOut"], atol=1e-4)


# -- the tile of the experts' grouped products -------------------------------
@pytest.mark.parametrize("rows,want", [
    (64 * 8, (128, 2048, 640)),        # a step: 512 sorted rows, 40 groups
    (4096 * 8, (256, 2048, 640)),      # a call of 4,096 tokens of a prompt
])
def test_the_grouped_products_tile_at_this_models_widths(rows, want):
    """An expert's 4,096 x 1,280 matrix is 5.2 M elements, over
    GMM_TILE_ELEMENTS: the tile rule's second branch, the contracted width
    cut to a divisor of at most 2,048 and the output's columns to a divisor
    of at most 1,024 (of 1,280: 640). A step's 512 sorted rows take 128-row
    tiles; a prompt's call is judged by its static bound of 4,096 x 8 sorted
    rows (819 a group, were they all held: the rule's "many"), so it takes
    256-row tiles although an eighth of them land here."""
    assert 4096 * 1280 > hybrid_ops.GMM_TILE_ELEMENTS
    assert hybrid_ops.gmm_tiling(rows, 4096, 1280, 40) == want
    # the way back: 1,280 contracted whole, 4,096 columns in tiles of 1,024
    assert hybrid_ops.gmm_tiling(rows, 1280, 4096, 40) == (
        want[0], 1280, 1024)


# -- the Pallas kernel of the scan (ops/pallas_kda.py), interpreted ----------
KH, KD = 2, 128
KWIDTH = KH * KD
KATTRS = dict(heads=KH, head_dim=KD, beta_scale=2.0)
NAMES = ("Q", "K", "V", "G", "Beta", "ALog", "DtBias", "State", "Len")


class Ctx:
    """What a lowering reads of its context."""

    def __init__(self, platform, mesh_axes=None):
        self.platform, self.mesh_axes = platform, mesh_axes


def wide_operands(b, t, decay=1.0, seed=7, state=True, lens=None):
    """The kernel's operands at a head of 128 channels: g between -0.01
    and -30 a position at ``decay`` 1, beta up to 2."""
    rng = np.random.default_rng(seed)
    ins = {n: rng.normal(size=(b, t, KWIDTH)).astype(np.float32)
           for n in ("Q", "K", "V")}
    ins["G"] = decay * rng.normal(size=(b, t, KWIDTH)).astype(np.float32)
    ins["Beta"] = 2 * rng.normal(size=(b, t, KH)).astype(np.float32)
    ins["ALog"] = np.log(rng.uniform(1, 16, KH)).astype(np.float32)
    ins["DtBias"] = rng.normal(size=(KWIDTH,)).astype(np.float32)
    ins["State"] = (0.3 * rng.normal(size=(b, KH, KD, KD)) if state
                    else np.zeros((b, KH, KD, KD))).astype(np.float32)
    ins["Len"] = np.asarray(lens if lens is not None else [t] * b,
                            np.int32)[:, None]
    return ins


def kernel(ins, attrs=KATTRS):
    o, s = jax.jit(lambda *a: hybrid_ops._kda_scan_kernel(
        *a, attrs["heads"], attrs["head_dim"], attrs["beta_scale"], True))(
            *(jnp.asarray(ins[n]) for n in NAMES))
    return np.asarray(o), np.asarray(s)


def xla_form(ins, attrs=KATTRS):
    o, s = jax.jit(lambda *a: hybrid_ops._kda_scan_xla(*a, attrs))(
        *(jnp.asarray(ins[n]) for n in NAMES))
    return np.asarray(o), np.asarray(s)


def stepwise(ins, attrs=KATTRS):
    """`kda_step` position by position from `State`, stopping at `Len`."""
    given = {n: jnp.asarray(ins[n]) for n in NAMES}

    def one(s, at):
        rows = {n: given[n][:, at] for n in ("Q", "K", "V", "G", "Beta")}
        out = LOWERINGS["kda_step"](None, dict(
            {n: [v] for n, v in rows.items()}, State=[s],
            ALog=[given["ALog"]], DtBias=[given["DtBias"]]), attrs)
        live = (at < given["Len"])[:, :, None, None]
        return jnp.where(live, out["StateOut"][0], s), out["O"][0]

    s, o = jax.jit(lambda: jax.lax.scan(
        one, given["State"], jnp.arange(ins["Q"].shape[1])))()
    return np.asarray(jnp.swapaxes(o, 0, 1)), np.asarray(s)


def rms_gap(got, want):
    return float(np.sqrt(np.mean(np.square(got - want))
                         / np.mean(np.square(want))))


@pytest.mark.parametrize("t,state,lens", [
    (128, False, None),          # from zeros, whole chunks
    (256, True, None),           # from a carried state
    (128, True, (100, 128)),     # Len inside a chunk, past a sub-chunk's end
    (192, True, (71, 130)),      # Len inside a sub-chunk
])
def test_the_kernel_is_the_xla_form_and_the_recurrence(t, state, lens):
    ins = wide_operands(2, t, state=state, lens=lens)
    got_o, got_s = kernel(ins)
    xla_o, xla_s = xla_form(ins)
    step_o, step_s = stepwise(ins)
    for b in range(2):
        n = lens[b] if lens else t
        assert rms_gap(got_o[b, :n], xla_o[b, :n]) < 1e-5
        assert rms_gap(got_o[b, :n], step_o[b, :n]) < 1e-5
    assert rms_gap(got_s, xla_s) < 1e-5
    assert rms_gap(got_s, step_s) < 1e-5


def test_the_kernel_walks_every_pair_of_heads_of_every_sequence():
    """A grid step takes two neighbouring heads: with four heads and two
    sequences the index maps walk two pairs a sequence, each pair's beta
    from its own columns, each sequence from its own `Len`."""
    heads, t = 4, 128
    rng = np.random.default_rng(19)
    ins = {n: rng.normal(size=(2, t, heads * KD)).astype(np.float32)
           for n in ("Q", "K", "V", "G")}
    ins["Beta"] = 2 * rng.normal(size=(2, t, heads)).astype(np.float32)
    ins["ALog"] = np.log(rng.uniform(1, 16, heads)).astype(np.float32)
    ins["DtBias"] = rng.normal(size=(heads * KD,)).astype(np.float32)
    ins["State"] = (0.3 * rng.normal(size=(2, heads, KD, KD))).astype(
        np.float32)
    ins["Len"] = np.asarray([[90], [128]], np.int32)
    attrs = dict(heads=heads, head_dim=KD, beta_scale=2.0)
    got_o, got_s = kernel(ins, attrs)
    want_o, want_s = xla_form(ins, attrs)
    for b, n in enumerate((90, 128)):
        assert rms_gap(got_o[b, :n], want_o[b, :n]) < 1e-5
        for h in range(heads):
            assert rms_gap(got_s[b, h], want_s[b, h]) < 1e-5, (b, h)


def test_the_kernel_leaves_the_state_of_an_empty_run_alone():
    """`Len` 0 (a run past the prompt's end, `_kda_prompt`): the state comes
    back bit for bit; a row whose run is whole is not disturbed by it."""
    ins = wide_operands(2, 128, lens=(0, 128))
    got_o, got_s = kernel(ins)
    np.testing.assert_array_equal(got_s[0], ins["State"][0])
    _, want_s = xla_form(ins)
    assert rms_gap(got_s[1], want_s[1]) < 1e-5
    assert np.isfinite(got_o).all()


def test_the_kernel_in_two_halves_is_the_whole():
    ins = wide_operands(1, 256, lens=(230,))
    whole_o, whole_s = kernel(ins)

    def half(lo, hi, state, n):
        part = {k: (v[:, lo:hi] if v.ndim == 3 else v)
                for k, v in ins.items()}
        part["State"], part["Len"] = state, np.asarray([[n]], np.int32)
        return kernel(part)

    first_o, first_s = half(0, 128, ins["State"], 128)
    second_o, second_s = half(128, 256, first_s, 102)
    assert rms_gap(np.concatenate([first_o, second_o], 1)[:, :230],
                   whole_o[:, :230]) < 1e-5
    assert rms_gap(second_s, whole_s) < 1e-5


def test_the_kernel_at_the_edges_of_decay_and_beta():
    """A decay of -100 a position in every channel (the running sum passes
    -6,000 inside a chunk: `exp(-G)` would overflow after one position) and
    beta 1.99 with keys that hardly turn (the triangular system's entries
    near 2: a series in powers of it would cancel)."""
    t = 128
    ins = wide_operands(1, t)
    ins["ALog"] = np.log(np.full(KH, 100.0)).astype(np.float32)
    ins["G"] = np.full((1, t, KWIDTH), 50.0, np.float32)    # softplus = x
    ins["DtBias"] = np.zeros(KWIDTH, np.float32)
    got_o, got_s = kernel(ins)
    step_o, step_s = stepwise(ins)
    assert np.isfinite(got_o).all() and np.isfinite(got_s).all()
    assert rms_gap(got_o, step_o) < 1e-5 and rms_gap(got_s, step_s) < 1e-5
    slow = wide_operands(1, t, decay=0.0, seed=11)
    slow["ALog"] = np.log(np.full(KH, 1e-3)).astype(np.float32)
    slow["Beta"] = np.full((1, t, KH), 5.3, np.float32)     # 2 sigmoid: 1.99
    base = np.random.default_rng(12).normal(size=(1, 1, KWIDTH))
    slow["K"] = (base + 0.05 * slow["K"]).astype(np.float32)
    got_o, got_s = kernel(slow)
    step_o, step_s = stepwise(slow)
    assert rms_gap(got_o, step_o) < 1e-4 and rms_gap(got_s, step_s) < 1e-4


def test_the_scan_takes_the_kernel_only_where_it_fits():
    """The op chooses from what it sees: a TPU, no mesh, heads of 128
    channels, whole chunks of 64. Anything else is today's XLA form."""
    from paddle_tpu import observability as obs

    def took(ctx, heads, dim, t, **attrs):
        ins = {n: [jax.ShapeDtypeStruct((1, t, heads * dim), jnp.bfloat16)]
               for n in ("Q", "K", "V")}
        ins["G"] = [jax.ShapeDtypeStruct((1, t, heads * dim), jnp.float32)]
        ins["Beta"] = [jax.ShapeDtypeStruct((1, t, heads), jnp.float32)]
        ins["ALog"] = [jax.ShapeDtypeStruct((heads,), jnp.float32)]
        ins["DtBias"] = [jax.ShapeDtypeStruct((heads * dim,), jnp.float32)]
        before = {k: obs.counter("ops.kda_scan." + k)
                  for k in ("kernel", "xla")}
        out = jax.eval_shape(lambda ins: LOWERINGS["kda_scan"](
            ctx, ins, dict(heads=heads, head_dim=dim, beta_scale=2.0,
                           **attrs)), ins)
        assert out["O"][0].shape == (1, t, heads * dim)
        assert out["StateOut"][0].shape == (1, heads, dim, dim)
        return {k: obs.counter("ops.kda_scan." + k) - v
                for k, v in before.items()}

    tpu = Ctx("tpu")
    assert took(tpu, 2, 128, 4096) == {"kernel": 1, "xla": 0}
    assert took(tpu, 2, 128, 192) == {"kernel": 1, "xla": 0}
    for ctx, heads, dim, t, attrs in [
            (tpu, 4, 64, 4096, {}),               # the rehearsal's head
            (tpu, 2, 128, 4000, {}),              # a ragged T
            (tpu, 2, 128, 4096, {"chunk": 32}),   # another chunk
            (Ctx("cpu"), 2, 128, 4096, {}),
            (None, 2, 128, 4096, {}),
            (Ctx("tpu", {"dp": "dp"}), 2, 128, 4096, {})]:
        assert took(ctx, heads, dim, t, **attrs) == {"kernel": 0, "xla": 1}


def test_the_kernels_gradient_is_the_xla_forms():
    ins = wide_operands(1, 128, lens=(111,))
    given = [jnp.asarray(ins[n]) for n in NAMES]
    mix = jnp.asarray(np.random.default_rng(5).normal(size=(1, 128, KWIDTH)),
                      jnp.float32)

    def loss(scan):
        def f(*diff):
            o, s = scan(*diff, given[-1])
            return jnp.sum(o * mix) + jnp.sum(s * s)
        return f

    args = KATTRS["heads"], KATTRS["head_dim"], KATTRS["beta_scale"]
    got = jax.jit(jax.grad(loss(lambda *a: hybrid_ops._kda_scan_kernel(
        *a, *args, True)), argnums=tuple(range(8))))(*given[:8])
    want = jax.jit(jax.grad(loss(lambda *a: hybrid_ops._kda_scan_xla(
        *a, KATTRS)), argnums=tuple(range(8))))(*given[:8])
    for name, a, b in zip(NAMES, got, want):
        assert rms_gap(np.asarray(a), np.asarray(b)) < 1e-4, name


def test_every_product_of_the_kernel_is_float32_at_highest():
    """The configuration states the scan's products float32 at matmul
    precision `highest`: every `dot_general` inside the `pallas_call` has
    float32 operands and that precision, whatever the inputs' dtype."""
    ins = wide_operands(1, 128)
    given = [jnp.asarray(ins[n], jnp.bfloat16 if n in "QKV" else None)
             for n in NAMES]
    jaxpr = jax.make_jaxpr(lambda *a: hybrid_ops._kda_scan_kernel(
        *a, KH, KD, 2.0))(*given)

    def eqns(j):
        for e in j.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    calls = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert calls
    dots = [e for c in calls for e in eqns(c.params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) >= 5
    for e in dots:
        assert all(v.aval.dtype == jnp.float32 for v in e.invars), e
        assert e.params["precision"] in (
            jax.lax.Precision.HIGHEST,
            (jax.lax.Precision.HIGHEST, jax.lax.Precision.HIGHEST)), e
        assert e.params["preferred_element_type"] == jnp.float32
