"""The lowerings of paddle_tpu/ops/hybrid_ops.py against the equations
written out in numpy (float64), at tiny sizes on the CPU. Inputs are
float32, so the tolerances are those of float32 sums (1e-4 relative to the
result's scale), not of bfloat16."""
import numpy as np
import pytest

import jax.numpy as jnp

from paddle_tpu.ops import LOWERINGS

RNG = np.random.default_rng(7)


def lower(op, ins, **attrs):
    ins = {k: [jnp.asarray(v)] for k, v in ins.items() if v is not None}
    return {k: np.asarray(v[0]) for k, v in
            LOWERINGS[op](None, ins, attrs).items()}


def close(got, want, tol=1e-4):
    want = np.asarray(want, np.float64)
    scale = max(1.0, float(np.abs(want).max()))
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) <= tol * scale


def silu(x):
    return x / (1.0 + np.exp(-x))


def softplus(x):
    return np.log1p(np.exp(x))


@pytest.mark.parametrize("groups,gated", [(1, False), (4, False), (4, True)])
def test_rms_norm(groups, gated):
    x = RNG.normal(size=(3, 5, 16)).astype(np.float32)
    w = RNG.normal(size=16).astype(np.float32)
    z = RNG.normal(size=x.shape).astype(np.float32) if gated else None
    got = lower("rms_norm", {"X": x, "Scale": w, "Gate": z},
                epsilon=1e-5, groups=groups)["Y"]
    y = x.astype(np.float64) * (silu(z.astype(np.float64)) if gated else 1)
    yg = y.reshape(3, 5, groups, 16 // groups)
    yg = yg / np.sqrt((yg ** 2).mean(-1, keepdims=True) + 1e-5)
    close(got, yg.reshape(x.shape) * w)


def test_relu_squared_and_dense_acc32():
    x = RNG.normal(size=(4, 6)).astype(np.float32)
    close(lower("relu_squared", {"X": x})["Out"], np.maximum(x, 0) ** 2)
    w = RNG.normal(size=(6, 5)).astype(np.float32)
    out = LOWERINGS["dense_acc32"](
        None, {"X": [jnp.asarray(x, jnp.bfloat16)],
               "W": [jnp.asarray(w, jnp.bfloat16)]}, {})["Out"][0]
    assert out.dtype == jnp.float32     # the accumulator, not a rounding
    close(np.asarray(out),
          np.asarray(jnp.asarray(x, jnp.bfloat16), np.float64)
          @ np.asarray(jnp.asarray(w, jnp.bfloat16), np.float64), 1e-5)


def conv_written_out(x, w, b, before=None):
    bsz, t, c = x.shape
    k = w.shape[1]
    full = np.concatenate(
        [np.zeros((bsz, k - 1, c)) if before is None else before, x], 1)
    out = np.zeros((bsz, t, c))
    for pos in range(t):
        for j in range(k):
            out[:, pos] += full[:, pos + j] * w[:, j]
    return silu(out + b), full


def test_causal_conv1d_carries_its_window():
    x = RNG.normal(size=(2, 9, 6)).astype(np.float32)
    w = RNG.normal(size=(6, 4)).astype(np.float32)
    b = RNG.normal(size=6).astype(np.float32)
    whole = lower("causal_conv1d", {"X": x, "Weight": w, "Bias": b},
                  activation="silu")
    want, full = conv_written_out(x, w, b)
    close(whole["Out"], want)
    close(whole["StateOut"], full[:, -3:])
    # the same in two calls, the window handed from the first to the second
    first = lower("causal_conv1d", {"X": x[:, :5], "Weight": w, "Bias": b},
                  activation="silu")
    second = lower("causal_conv1d", {"X": x[:, 5:], "Weight": w, "Bias": b,
                                     "State": first["StateOut"]},
                   activation="silu")
    close(np.concatenate([first["Out"], second["Out"]], 1), want)
    close(second["StateOut"], full[:, -3:])


@pytest.mark.parametrize("lens", [(9, 4), (2, 1)])
def test_causal_conv1d_hands_over_the_window_of_the_last_real_token(lens):
    x = RNG.normal(size=(2, 9, 6)).astype(np.float32)
    w = RNG.normal(size=(6, 4)).astype(np.float32)
    got = lower("causal_conv1d", {
        "X": x, "Weight": w, "Len": np.asarray(lens)[:, None]})["StateOut"]
    for row, n in enumerate(lens):
        real = np.concatenate([np.zeros((3, 6)), x[row, :n]], 0)
        close(got[row], real[-3:])


SSM = dict(heads=4, head_dim=3, groups=2, state=5)


def ssm_inputs(bsz, t):
    h, p, g, n = SSM["heads"], SSM["head_dim"], SSM["groups"], SSM["state"]
    return {"XBC": RNG.normal(size=(bsz, t, h * p + 2 * g * n)
                              ).astype(np.float32),
            "Dt": RNG.normal(size=(bsz, t, h)).astype(np.float32),
            "DtBias": RNG.uniform(-3, -1, h).astype(np.float32),
            "ALog": np.log(RNG.uniform(1, 16, h)).astype(np.float32),
            "D": RNG.normal(size=h).astype(np.float32)}


def recurrence(ins, lens=None, hs=None):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T; y_t = h_t C_t + D x_t,
    position by position; -> (y, state after position len - 1)."""
    h, p, g, n = SSM["heads"], SSM["head_dim"], SSM["groups"], SSM["state"]
    xbc = ins["XBC"].astype(np.float64)
    bsz, t, _ = xbc.shape
    x = xbc[..., :h * p].reshape(bsz, t, h, p)
    bm = np.repeat(xbc[..., h * p:h * p + g * n].reshape(bsz, t, g, n),
                   h // g, axis=2)
    cm = np.repeat(xbc[..., h * p + g * n:].reshape(bsz, t, g, n),
                   h // g, axis=2)
    dt = softplus(ins["Dt"].astype(np.float64) + ins["DtBias"])
    a = -np.exp(ins["ALog"].astype(np.float64))
    hs = np.zeros((bsz, h, p, n)) if hs is None else hs.astype(np.float64)
    y = np.zeros((bsz, t, h, p))
    final = hs.copy()
    for pos in range(t):
        hs = (np.exp(dt[:, pos] * a)[..., None, None] * hs
              + (dt[:, pos, :, None] * x[:, pos])[..., None]
              * bm[:, pos, :, None, :])
        y[:, pos] = ((hs * cm[:, pos, :, None, :]).sum(-1)
                     + ins["D"][:, None] * x[:, pos])
        for row in range(bsz):
            if lens is None or pos == lens[row] - 1:
                final[row] = hs[row]
    return y.reshape(bsz, t, h * p), final


@pytest.mark.parametrize("t,chunk", [(16, 4), (16, 16), (13, 4), (5, 128)])
def test_chunked_scan_is_the_recurrence(t, chunk):
    ins = ssm_inputs(2, t)
    got = lower("mamba2_scan", ins, chunk=chunk, **SSM)
    y, final = recurrence(ins)
    close(got["Y"], y)
    close(got["StateOut"], final)


def test_padded_scan_stops_the_state_at_the_last_real_token():
    ins, lens = ssm_inputs(2, 12), (7, 12)
    got = lower("mamba2_scan", dict(ins, Len=np.asarray(lens)[:, None]),
                chunk=4, **SSM)
    y, final = recurrence(ins, lens)
    close(got["StateOut"], final)
    for row, n in enumerate(lens):       # real positions are not disturbed
        close(got["Y"][row, :n], y[row, :n])
    # and it is NOT the state at the bucket's end
    assert np.abs(got["StateOut"][0] - recurrence(ins)[1][0]).max() > 1e-3


def test_a_step_after_a_scan_is_the_recurrence_one_position_on():
    ins = ssm_inputs(3, 9)
    before = lower("mamba2_scan", {k: (v[:, :8] if v.ndim == 3 else v)
                                   for k, v in ins.items()},
                   chunk=4, **SSM)
    step = lower("mamba2_step", dict(
        {k: (v[:, 8] if v.ndim == 3 else v) for k, v in ins.items()},
        State=before["StateOut"]), **SSM)
    y, final = recurrence(ins)
    close(step["Y"], y[:, 8])
    close(step["StateOut"], final)


def attention_written_out(q, k, v, heads, kv_heads, seen):
    bsz, tq, _ = q.shape
    dh = q.shape[-1] // heads
    out = np.zeros((bsz, tq, heads, dh))
    for b in range(bsz):
        for h in range(heads):
            kv = h // (heads // kv_heads)
            qh = q[b].reshape(tq, heads, dh)[:, h].astype(np.float64)
            kh = k[b].reshape(-1, kv_heads, dh)[:, kv].astype(np.float64)
            vh = v[b].reshape(-1, kv_heads, dh)[:, kv].astype(np.float64)
            s = qh @ kh.T / np.sqrt(dh)
            s = np.where(seen[b], s, -np.inf)
            p = np.exp(s - s.max(-1, keepdims=True))
            out[b, :, h] = (p / p.sum(-1, keepdims=True)) @ vh
    return out.reshape(bsz, tq, heads * dh)


def test_gqa_attention_causal_and_over_a_slot_cache():
    heads, kv_heads, dh, t = 4, 2, 3, 7
    q = RNG.normal(size=(2, t, heads * dh)).astype(np.float32)
    k = RNG.normal(size=(2, t, kv_heads * dh)).astype(np.float32)
    v = RNG.normal(size=(2, t, kv_heads * dh)).astype(np.float32)
    causal = np.tril(np.ones((t, t), bool))
    got = lower("gqa_attention", {"Q": q, "K": k, "V": v},
                heads=heads, kv_heads=kv_heads)["Out"]
    want = attention_written_out(q, k, v, heads, kv_heads, [causal, causal])
    close(got, want)
    # one query row per slot over the cache, each slot at its own position
    pos = np.asarray([[4], [6]])
    one = np.stack([q[0, 4:5], q[1, 6:7]])
    got = lower("gqa_attention", {"Q": one, "K": k, "V": v, "Pos": pos},
                heads=heads, kv_heads=kv_heads)["Out"]
    close(got[0, 0], want[0, 4])
    close(got[1, 0], want[1, 6])


def route_written_out(x, gate, bias, k, scale):
    s = 1.0 / (1.0 + np.exp(-(x.astype(np.float64) @ gate)))
    idx = np.argsort(-(s + bias), -1, kind="stable")[:, :k]
    w = np.take_along_axis(s, idx, -1)
    return idx, w / (w.sum(-1, keepdims=True) + 1e-20) * scale


def test_sigmoid_topk_routing():
    x = RNG.normal(size=(9, 8)).astype(np.float32)
    gate = RNG.normal(size=(8, 16)).astype(np.float32)
    bias = (0.1 * RNG.normal(size=16)).astype(np.float32)
    got = lower("moe_route_topk", {"X": x, "Gate": gate, "Bias": bias},
                k=4, scale=2.5)
    idx, w = route_written_out(x, gate, bias, 4, 2.5)
    assert (got["Index"] == idx).all() and got["Index"].dtype == np.int32
    close(got["Weight"], w)
    # the weights of the chosen sum to the scale: normalised over all k
    close(got["Weight"].sum(-1), np.full(9, 2.5))


def experts_written_out(x, idx, w, w1, w2, first, live=None):
    out = np.zeros((x.shape[0], w2.shape[-1]))
    load = np.zeros(w1.shape[0], int)
    for t in range(x.shape[0]):
        if live is not None and not live[t]:
            continue
        for j in range(idx.shape[1]):
            e = idx[t, j] - first
            if 0 <= e < w1.shape[0]:
                hid = np.maximum(x[t].astype(np.float64) @ w1[e], 0) ** 2
                out[t] += w[t, j] * (hid @ w2[e])
                load[e] += 1
    return out, load


@pytest.mark.parametrize("first,masked", [(0, False), (4, False), (12, True)])
def test_held_experts_compute_their_share_and_count_it(first, masked):
    x = RNG.normal(size=(10, 6)).astype(np.float32)
    gate = RNG.normal(size=(6, 16)).astype(np.float32)
    idx, w = route_written_out(x, gate, np.zeros(16), 4, 5.0)
    w1 = RNG.normal(size=(4, 6, 7)).astype(np.float32)
    w2 = RNG.normal(size=(4, 7, 6)).astype(np.float32)
    live = (np.arange(10) % 3 != 0) if masked else None
    got = lower("held_experts_ffn", {
        "X": x, "Index": idx.astype(np.int32), "Weight": w.astype(np.float32),
        "W1": w1, "W2": w2,
        "Live": None if live is None else live[:, None].astype(np.float32)},
        first_expert=first)
    want, load = experts_written_out(x, idx, w, w1, w2, first, live)
    close(got["Out"], want)
    assert got["Counts"].tolist() == [int(load.sum()), int(load.max()),
                                      int((load > 0).sum())]


def test_the_shares_of_all_held_ranges_sum_to_the_whole_layer():
    """No token is dropped and nothing is normalised after the cut: four
    chips' parts of a 16-expert layer add up to the layer over all 16."""
    x = RNG.normal(size=(12, 6)).astype(np.float32)
    gate = RNG.normal(size=(6, 16)).astype(np.float32)
    idx, w = route_written_out(x, gate, np.zeros(16), 4, 5.0)
    w1 = RNG.normal(size=(16, 6, 7)).astype(np.float32)
    w2 = RNG.normal(size=(16, 7, 6)).astype(np.float32)
    parts, held = [], 0
    for first in (0, 4, 8, 12):
        got = lower("held_experts_ffn", {
            "X": x, "Index": idx.astype(np.int32),
            "Weight": w.astype(np.float32), "W1": w1[first:first + 4],
            "W2": w2[first:first + 4]}, first_expert=first)
        parts.append(got["Out"])
        held += int(got["Counts"][0])
    close(sum(parts), experts_written_out(x, idx, w, w1, w2, 0)[0])
    assert held == 12 * 4       # every assignment landed on exactly one chip


def test_the_kernel_of_the_tpu_branch_equals_the_ragged_dot(monkeypatch):
    """`grouped_dot(platform="tpu")` is the Pallas `gmm` with one expert's
    whole matrix a tile; run in interpret mode here (the chip runs it
    compiled), the held-experts sum through it equals the one through
    `lax.ragged_dot` to float32 rounding, counts included."""
    import functools

    from jax.experimental.pallas.ops.tpu import megablox

    from paddle_tpu.ops import hybrid_ops

    monkeypatch.setattr(megablox, "gmm",
                        functools.partial(megablox.gmm, interpret=True))
    t, k, held, d, f = 24, 4, 4, 128, 256
    x = jnp.asarray(RNG.normal(size=(t, d)), jnp.bfloat16)
    idx = jnp.asarray(np.stack([RNG.permutation(16)[:k] for _ in range(t)]),
                      jnp.int32)
    wt = jnp.asarray(RNG.random((t, k)), jnp.float32)
    w1 = jnp.asarray(0.1 * RNG.normal(size=(held, d, f)), jnp.bfloat16)
    w2 = jnp.asarray(0.1 * RNG.normal(size=(held, f, d)), jnp.bfloat16)
    live = jnp.asarray(np.arange(t) % 5 != 0)
    got, n_got = hybrid_ops.held_experts_sum(x, idx, wt, w1, w2, 4, live,
                                             platform="tpu")
    want, n_want = hybrid_ops.held_experts_sum(x, idx, wt, w1, w2, 4, live)
    close(np.asarray(got), np.asarray(want), 1e-5)
    assert n_got.tolist() == n_want.tolist() and int(n_want[0]) > 0


@pytest.mark.parametrize("k,n,refused", [
    (96, 256, True), (128, 200, True), (2048, 2048, False)])
def test_the_tpu_branch_refuses_widths_its_tile_cannot_take(k, n, refused):
    """No silent second kernel on the chip: a width that is no multiple of
    the kernel's 128 raises where the program is lowered. A matrix too
    large for one whole-matrix tile is no longer refused (PR 32): the tile
    follows the shape, and the kernel, run in interpret mode here, still
    equals the ragged dot."""
    import functools

    from jax.experimental.pallas.ops.tpu import megablox

    from paddle_tpu.ops import hybrid_ops

    xs = jnp.asarray(RNG.normal(size=(8, k)), jnp.bfloat16)
    w = jnp.asarray(0.1 * RNG.normal(size=(2, k, n)), jnp.bfloat16)
    sizes = jnp.asarray([4, 4], jnp.int32)
    want = hybrid_ops.grouped_dot(xs, w, sizes)
    assert want.shape == (8, n)
    if refused:
        with pytest.raises(ValueError, match="multiples of 128"):
            hybrid_ops.grouped_dot(xs, w, sizes, "tpu")
        return
    assert k * n > hybrid_ops.GMM_TILE_ELEMENTS
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(megablox, "gmm",
                   functools.partial(megablox.gmm, interpret=True))
        got = hybrid_ops.grouped_dot(xs, w, sizes, "tpu")
    close(np.asarray(got), np.asarray(want), 1e-5)
