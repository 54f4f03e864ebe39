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
