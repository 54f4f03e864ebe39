"""The Laguna decoder (models/laguna.py) and what it brought to the ops
(rotary from a slot's position, over part of a head, YaRN; a window in
`gqa_attention` and a ring in the step; softmax routing; gated held experts
in a served program) against the plain reference
(benchmark/reference/laguna_lm.py) and against the equations written out in
numpy, at a tiny size on the CPU; `rows` and `ring` state in one SlotCache;
the refusals of what needs rows alone; the other served programs unmoved.

Tolerance of the logit comparisons: the system holds bfloat16 weights, K/V
and residual stream (2**-8 relative per rounding, a few roundings per
layer, 5 layers), the reference float32 over the same bfloat16 weights. At
this size a position's logits differ by 0.05-0.09 of their standard
deviation (the median over a sequence's positions is held to 0.1), and by
0.3-0.5 at the few positions where two experts' router scores tie within
bfloat16's rounding and the system chooses the other one (at most four of
about twenty may pass 0.2). What a fault in this family's own mechanisms
reads (a ring written one row off, a window one position too long, a
dropped gate or YaRN factor) is in tests/benchmark_tests/test_laguna_cell.py.
"""
import hashlib
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.fluid.lowering import build_step_fn
from paddle_tpu.models import gpt, laguna, nemotron_h
from paddle_tpu.ops import LOWERINGS, hybrid_ops
from paddle_tpu.ops.registry import LowerContext
from paddle_tpu.serving.decode import SlotCache, kv_slot_bytes

from benchmark.reference import laguna_lm as ref

LIMIT = 0.2
CACHE_LEN, WINDOW = 64, 8
FULL, SLIDING = "full_attention", "sliding_attention"
YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 4,
        "original_max_position_embeddings": 16, "beta_slow": 1,
        "beta_fast": 4, "attention_factor": 1.2, "partial_rotary_factor": 0.5}
PUBLISHED_YARN = {"rope_theta": 500000, "rope_type": "yarn", "factor": 128,
                  "original_max_position_embeddings": 8192, "beta_slow": 1,
                  "beta_fast": 32, "attention_factor": 1.4852030263919618,
                  "partial_rotary_factor": 0.5}
M = dict(hidden_size=64, head_dim=16, num_key_value_heads=2, vocab_size=211,
         intermediate_size=96, moe_intermediate_size=32,
         shared_expert_intermediate_size=32, num_experts=4,
         num_experts_per_tok=3, sliding_window=WINDOW, rms_norm_eps=1e-6,
         moe_routed_scaling_factor=2.5, gating="per-head",
         layer_types=[FULL, SLIDING, SLIDING, SLIDING, FULL],
         mlp_layer_types=["dense"] + ["sparse"] * 4,
         num_attention_heads_per_layer=[4, 6, 6, 6, 4],
         rope_parameters={FULL: YARN, SLIDING: {
             "rope_type": "default", "rope_theta": 10000,
             "partial_rotary_factor": 1}},
         router_experts=16, first_expert=4, initializer_range=0.08)
RNG = np.random.default_rng(11)


def lower(op, ins, **attrs):
    ins = {k: [jnp.asarray(v)] for k, v in ins.items() if v is not None}
    return {k: np.asarray(v[0]) for k, v in
            LOWERINGS[op](None, ins, attrs).items()}


def close(got, want, tol=1e-4):
    want = np.asarray(want, np.float64)
    scale = max(1.0, float(np.abs(want).max()))
    assert got.shape == want.shape
    assert float(np.abs(got - want).max()) <= tol * scale


def gap(got, want):
    return float(np.abs(got - want).max() / want.std())


def digest(text):
    """sha256 of a lowered program's StableHLO, source locations (and a
    kernel's serialised body, which carries them) taken out."""
    text = re.sub(r"loc\(.*?\)", "", text)
    text = re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", text)
    text = re.sub(r"#loc\d* = .*\n", "", text)
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def window_lowerings():
    """[kernel, banded]: the process's counts of window calls longer than
    their window that lowered to the Pallas kernel / the banded blocks."""
    from paddle_tpu import observability as obs

    return [obs.counter("ops.gqa_attention.window_" + path)
            for path in ("kernel", "banded")]


@pytest.fixture(scope="module")
def model():
    cfg = laguna.LagunaConfig.from_hf(M, router_experts=16, first_expert=4)
    return cfg, ref.make_weights(M, 2147483659)


def build(cfg, fn, *args):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fn(cfg, *args)
        return fluid.default_main_program(), v


@pytest.fixture(scope="module")
def programs(model):
    """The prefill (bucket 16) and step programs as the engine builds them,
    with the logits fetched last."""
    cfg, w = model
    pprog, pv = build(cfg, laguna.build_prefill, 16, CACHE_LEN)
    sprog, sv = build(cfg, laguna.build_step, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"],
                        pv["fetch_vars"] + [pv["logits"]], scope=w,
                        name="prefill_16")
    step = Predictor(sprog, sv["feed_names"],
                     sv["fetch_vars"] + [sv["logits"]], scope=w,
                     name="decode_step", donate_feeds=sv["cache_feed_names"])
    return prefill, pv, step, sv


# -- the ops ---------------------------------------------------------------
def rates_written_out(r, head_dim):
    """ISSUE 35's formula, as written."""
    dim = int(head_dim * r["partial_rotary_factor"])
    i = np.arange(dim // 2)
    f = float(r["rope_theta"]) ** (2.0 * i / dim)
    if r["rope_type"] != "yarn":
        return 1.0 / f, 1.0, dim

    def c(n):
        return (dim * math.log(r["original_max_position_embeddings"]
                               / (2 * math.pi * n))
                / (2 * math.log(r["rope_theta"])))

    low = min(max(math.floor(c(r["beta_fast"])), 0), dim - 1)
    high = min(max(math.ceil(c(r["beta_slow"])), 0), dim - 1)
    ramp = np.clip((i - low) / (high - low), 0, 1)
    return ((1 - ramp) / f + ramp / (r["factor"] * f),
            r["attention_factor"], dim)


@pytest.mark.parametrize("r,head_dim", [(PUBLISHED_YARN, 128), (YARN, 16)])
def test_yarn_rates_follow_the_formula(r, head_dim):
    want, factor, dim = rates_written_out(r, head_dim)
    yarn = (r["factor"], r["original_max_position_embeddings"],
            r["beta_fast"], r["beta_slow"], r["attention_factor"])
    got, got_factor = hybrid_ops.rotary_inv_freq(dim, r["rope_theta"], yarn)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got_factor == factor
    m = dict(M, head_dim=head_dim, rope_parameters={FULL: r})
    ref_rates, ref_factor, ref_dim = ref.rope_rates(m, FULL)
    np.testing.assert_allclose(ref_rates, want, rtol=1e-6)
    assert (ref_factor, ref_dim) == (factor, dim)
    if r is PUBLISHED_YARN:
        # the fastest pairs keep their rate, the slowest turn 128 x slower
        plain = 500000.0 ** (-2.0 * np.arange(32) / 64)
        assert got[0] == pytest.approx(plain[0])
        assert got[-1] == pytest.approx(plain[-1] / 128, rel=1e-5)
        assert 0 < np.sum((got < plain * 0.999) & (got > plain / 127)) < 32


@pytest.mark.parametrize("kind", [FULL, SLIDING])
def test_rotary_from_pos_is_rotary_over_a_sequence(kind):
    """A step's one row at its slot's position = that position's row of the
    whole sequence = the reference's `rotary`; a full layer's head keeps its
    second half unturned and gets the factor on the first alone."""
    cfg = laguna.LagunaConfig.from_hf(M, 16, 4)
    theta, rot, yarn = cfg.rope[kind]
    x = RNG.normal(size=(2, 40, 3, 16)).astype(np.float32)
    attrs = dict(theta=theta, rotary_dim=rot, yarn=yarn)
    whole = lower("rotary_embedding", {"X": x}, **attrs)["Out"]
    for b in range(2):
        close(whole[b], ref.rotary(jnp.asarray(x[b]), jnp.arange(40), M,
                                   kind), 1e-5)
    pos = np.asarray([[37], [5]])
    one = lower("rotary_embedding",
                {"X": np.stack([x[0, 37:38], x[1, 5:6]]), "Pos": pos},
                **attrs)["Out"]
    close(one[0, 0], whole[0, 37], 1e-6)
    close(one[1, 0], whole[1, 5], 1e-6)
    if kind == FULL:
        assert rot == 8 and np.array_equal(whole[..., 8:], x[..., 8:])
        # position 0: no turn, only YaRN's factor
        close(whole[:, 0, :, :8], 1.2 * x[:, 0, :, :8], 1e-6)


def attention_written_out(q, k, v, nh, nkv, window=None):
    """q (T, nh * dh), k/v (T, nkv * dh), float64, one sequence."""
    t, dh = q.shape[0], q.shape[1] // nh
    out = np.zeros((t, nh, dh))
    for h in range(nh):
        g = h // (nh // nkv)
        s = q[:, h * dh:(h + 1) * dh] @ k[:, g * dh:(g + 1) * dh].T / dh ** .5
        i, j = np.arange(t)[:, None], np.arange(t)[None, :]
        seen = j <= i
        if window:
            seen &= i - j < window
        p = np.exp(np.where(seen, s, -np.inf))
        out[:, h] = (p / p.sum(-1, keepdims=True)) @ v[:, g * dh:(g + 1) * dh]
    return out.reshape(t, nh * dh)


@pytest.mark.parametrize("t,window", [(40, 8), (37, 8), (8, 8), (5, 8),
                                      (24, 0)])
def test_window_attention_over_a_prompt(t, window):
    """Banded blocks (also where the window does not divide the length),
    the plain causal mask where the window never binds."""
    q = RNG.normal(size=(2, t, 6 * 16)).astype(np.float32)
    k = RNG.normal(size=(2, t, 2 * 16)).astype(np.float32)
    v = RNG.normal(size=(2, t, 2 * 16)).astype(np.float32)
    got = lower("gqa_attention", {"Q": q, "K": k, "V": v}, heads=6,
                kv_heads=2, window=window)["Out"]
    for b in range(2):
        close(got[b], attention_written_out(
            q[b].astype(np.float64), k[b].astype(np.float64),
            v[b].astype(np.float64), 6, 2, window or None), 1e-4)


def test_a_window_over_a_slot_cache_is_refused():
    x = np.zeros((1, 1, 32), np.float32)
    with pytest.raises(ValueError, match="ring"):
        lower("gqa_attention", {"Q": x, "K": x, "V": x,
                                "Pos": np.zeros((1, 1), np.int64)},
              heads=2, kv_heads=2, window=8)


def _bf16_exact(a):
    return np.asarray(jnp.asarray(a, jnp.bfloat16).astype(jnp.float32))


@pytest.mark.parametrize("dtype,tol", [("float32", 1e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("t", [40, 37, 16, 9])
def test_the_window_kernel_against_the_equations_and_the_banded_blocks(
        t, dtype, tol):
    """The path the TPU takes for a sequence longer than its window
    (`window_attn_fwd`, one call, the op's own layout), interpreted: a
    length the window divides, one it does not, one block more than the
    window (whole and padded), 6 query heads over 2 key/value heads.
    bfloat16: the operands are bfloat16 values, the kernel rounds its
    probabilities (before their sum divides) and its output, the blocks
    theirs (after): one rounding of 2**-8 apart."""
    q, k, v = (_bf16_exact(RNG.normal(size=(2, t, n * 16)))
               for n in (6, 2, 2))
    args = [jnp.asarray(a, dtype) for a in (q, k, v)]
    got = hybrid_ops._window_gqa(*args, 6, 2, WINDOW, True)
    assert got.dtype == args[0].dtype and got.shape == q.shape
    got = np.asarray(got.astype(jnp.float32))
    close(got, hybrid_ops._banded_gqa(*args, 6, 2, WINDOW).astype(
        jnp.float32), tol)
    for b in range(2):
        close(got[b], attention_written_out(
            q[b].astype(np.float64), k[b].astype(np.float64),
            v[b].astype(np.float64), 6, 2, WINDOW), max(tol, 1e-4))


@pytest.mark.parametrize("t", [32, 21])
def test_the_window_kernels_gradients_are_the_banded_blocks_own(t):
    """Forward only: under `append_backward` a window layer behaves as the
    plain blocks do, the kernel's `custom_vjp` hands the cotangent to
    `jax.vjp` of them."""
    q, k, v = (jnp.asarray(RNG.normal(size=(2, t, n * 16)), jnp.float32)
               for n in (6, 2, 2))
    w = jnp.asarray(RNG.normal(size=q.shape), jnp.float32)
    got = jax.grad(lambda *a: jnp.sum(w * hybrid_ops._window_gqa(
        *a, 6, 2, WINDOW, True)), (0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(w * hybrid_ops._banded_gqa(
        *a, 6, 2, WINDOW)), (0, 1, 2))(q, k, v)
    for g, e in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(e))


def _attention_text(platform, t, heads=4, kv_heads=2, dh=128, window=0,
                    mesh_axes=None, pos=False, batch=1):
    """The StableHLO of one `gqa_attention` call for `platform`."""
    q = jax.ShapeDtypeStruct((batch, t, heads * dh), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((batch, t, kv_heads * dh), jnp.bfloat16)
    p = jax.ShapeDtypeStruct((batch, 1), jnp.int32)
    ctx = LowerContext(platform=platform)
    ctx.mesh_axes = mesh_axes
    attrs = {"heads": heads, "kv_heads": kv_heads}
    if window:
        attrs["window"] = window

    def f(q, k, v, p):
        ins = {"Q": [q[:, :1] if pos else q], "K": [k], "V": [v]}
        if pos:
            ins["Pos"] = [p]
        return LOWERINGS["gqa_attention"](ctx, ins, attrs)["Out"][0]
    return jax.jit(f).trace(q, kv, kv, p).lower(
        lowering_platforms=(platform,)).as_text()


WINDOW_PATHS = {
    # what of a call decides, and which of the two paths it lowers
    "the cell's window on the chip": (dict(platform="tpu"), "kernel"),
    "a length the window does not divide": (dict(platform="tpu", t=1100),
                                            "kernel"),
    "the CPU": (dict(platform="cpu"), "banded"),
    "a sharded program": (dict(platform="tpu", mesh_axes={"dp": "dp"}),
                          "banded"),
    "a window that does not tile": (dict(platform="tpu", window=200),
                                    "banded"),
    "a head that does not tile": (dict(platform="tpu", dh=64), "banded"),
    "no longer than the window": (dict(platform="tpu", t=512), None),
    "a step over a ring": (dict(platform="tpu", t=512, window=0, pos=True),
                           None),
}


@pytest.mark.parametrize("case", sorted(WINDOW_PATHS))
def test_the_window_kernel_is_taken_from_what_the_op_sees(case):
    """A window, a sequence longer than it, no `Pos`, a TPU, no mesh, a
    head size and a window that are multiples of 128: the kernel; any
    other sequence longer than its window the banded blocks; no caller
    sets anything. The two lowering counters count what was taken."""
    call, path = WINDOW_PATHS[case]
    before = window_lowerings()
    text = _attention_text(**dict(dict(t=1024, window=512), **call))
    assert [n - b for n, b in zip(window_lowerings(), before)] == [
        int(path == "kernel"), int(path == "banded")]
    assert ("window_attn_fwd" in text) == (path == "kernel")
    assert ("tpu_custom_call" in text) == (path == "kernel")


ATTENTION_DIGESTS = {
    # read on the parent commit (PR 37's tree) with `_attention_text`:
    # LFM2's causal call of the training cell (flash from 1,024 on) and the
    # hybrid's prefill call at its longest bucket, on both platforms
    ("lfm2", "tpu"): "7aaa81309855852c",
    ("lfm2", "cpu"): "8d41e7a61ea22f96",
    ("nemotron_h", "tpu"): "0a219c6d3ba28eb3",
    ("nemotron_h", "cpu"): "0a219c6d3ba28eb3",
}
ATTENTION_CALLS = {"lfm2": dict(t=4096, heads=32, kv_heads=8, dh=64, batch=4),
                   "nemotron_h": dict(t=512, heads=32, kv_heads=2, dh=128)}


@pytest.mark.parametrize("family,platform", sorted(ATTENTION_DIGESTS))
def test_the_causal_calls_without_a_window_lower_unchanged(family, platform):
    """What the branch for the band must not move: a causal call without a
    window lowers to the text it had on the parent commit (LFM2's through
    the flash kernels on the TPU, the hybrid's short one through XLA), and
    counts on neither window counter."""
    before = window_lowerings()
    text = _attention_text(platform, **ATTENTION_CALLS[family])
    assert window_lowerings() == before
    assert "window_attn_fwd" not in text
    assert ("flash_fwd" in text) == ((family, platform) == ("lfm2", "tpu"))
    assert ("tpu_custom_call" in text) == ("flash_fwd" in text)
    assert digest(text) == ATTENTION_DIGESTS[(family, platform)]


@pytest.mark.parametrize("plen", [3, 8, 9, 21])
def test_the_ring_a_prefill_hands_over_and_a_step_goes_on_with(plen):
    """`kv_ring_gather` of a prompt, then steps that write row `pos mod
    window` and attend over the columns `<= pos`: before the wrap and after
    it, each step's output is the windowed attention over the whole
    sequence at that position."""
    t = plen + 2 * WINDOW
    q = RNG.normal(size=(t, 6 * 16)).astype(np.float32)
    k = RNG.normal(size=(t, 2 * 16)).astype(np.float32)
    v = RNG.normal(size=(t, 2 * 16)).astype(np.float32)
    want = attention_written_out(*(a.astype(np.float64) for a in (q, k, v)),
                                 6, 2, WINDOW)
    padded = 24

    def ring_of(x):
        rows = np.zeros((1, padded, x.shape[1]), np.float32)
        rows[0, :plen] = x[:plen]
        rows[0, plen:] = 99.0                      # the bucket's padding
        return lower("kv_ring_gather",
                     {"X": rows, "Len": np.asarray([[plen]])},
                     window=WINDOW)["Out"]

    rk, rv = ring_of(k), ring_of(v)
    for j in range(WINDOW):                        # what the hand-over holds
        p = plen - 1 - ((plen - 1 - j) % WINDOW)
        np.testing.assert_array_equal(rk[0, j], k[p] if p >= 0 else 0 * k[0])
    for p in range(plen, t):
        at = np.asarray([[p % WINDOW]])
        rk = lower("decode_cache_write", {"Cache": rk, "Value": k[None, p:p + 1],
                                          "Pos": at}, per_row=True)["Out"]
        rv = lower("decode_cache_write", {"Cache": rv, "Value": v[None, p:p + 1],
                                          "Pos": at}, per_row=True)["Out"]
        got = lower("gqa_attention",
                    {"Q": q[None, p:p + 1], "K": rk, "V": rv,
                     "Pos": np.asarray([[p]])}, heads=6, kv_heads=2)["Out"]
        close(got[0, 0], want[p], 1e-4)


def test_softmax_routing_is_the_scaled_softmax_over_the_chosen_logits():
    x = RNG.normal(size=(9, 12)).astype(np.float32)
    g = RNG.normal(size=(12, 16)).astype(np.float32)
    out = lower("moe_route_topk", {"X": x, "Gate": g}, k=3, scale=2.5,
                score_func="softmax")
    logits = x.astype(np.float64) @ g.astype(np.float64)
    idx = np.argsort(-logits, -1)[:, :3]
    assert np.array_equal(np.sort(out["Index"], -1), np.sort(idx, -1))
    chosen = np.take_along_axis(logits, out["Index"].astype(np.int64), -1)
    e = np.exp(chosen - chosen.max(-1, keepdims=True))
    close(out["Weight"], 2.5 * e / e.sum(-1, keepdims=True), 1e-5)
    # the reference's dense form holds the same weights
    w = np.asarray(ref.route(jnp.asarray(x), {"moe.gate.w": jnp.asarray(g)},
                             dict(M, num_experts_per_tok=3), lambda a: a))
    close(np.take_along_axis(w, out["Index"].astype(np.int64), -1),
          out["Weight"], 1e-5)
    assert np.count_nonzero(w) == 9 * 3


def test_gated_held_experts_route_no_dead_row():
    """A served program's `Live` mask on the gated layer: a dead slot (a
    prompt's padding) lands on no expert and is counted nowhere."""
    t, k, d, f, held = 6, 3, 16, 8, 4
    x = RNG.normal(size=(t, d)).astype(np.float32)
    idx = RNG.integers(0, 8, (t, k)).astype(np.int32)
    wt = RNG.random((t, k)).astype(np.float32)
    w1, w3 = (RNG.normal(size=(held, d, f)).astype(np.float32)
              for _ in range(2))
    w2 = RNG.normal(size=(held, f, d)).astype(np.float32)
    live = np.asarray([1, 1, 0, 1, 0, 1], np.float32)[:, None]
    ins = {"X": x, "Index": idx, "Weight": wt, "W1": w1, "W2": w2, "W3": w3}
    every = lower("held_experts_ffn", ins, first_expert=2)
    some = lower("held_experts_ffn", dict(ins, Live=live), first_expert=2)
    dead = live[:, 0] == 0
    assert not some["Out"][dead].any() and every["Out"][dead].any()
    close(some["Out"][~dead], every["Out"][~dead], 1e-5)
    here = (idx >= 2) & (idx < 6)
    assert some["Counts"][0] == here[~dead].sum() < every["Counts"][0]


# -- the model against the reference ----------------------------------------
def test_the_checkpoint_of_the_reference_is_the_models_own(model):
    cfg, w = model
    shapes = laguna.param_shapes(cfg)
    assert set(w) == set(shapes)
    assert all(tuple(w[n].shape) == tuple(s) and str(w[n].dtype) == d
               for n, (s, d) in shapes.items())


def test_the_declaration_holds_rows_and_rings(model):
    cfg, _ = model
    state = cfg.decode_model(CACHE_LEN).state
    assert [(e.name, e.kind, e.shape) for e in state] == [
        ("%s_%d" % (part, i), "rows" if kind == FULL else "ring",
         (CACHE_LEN if kind == FULL else WINDOW, 32))
        for i, kind in enumerate(M["layer_types"]) for part in "kv"]
    assert cfg.decode_model(CACHE_LEN).slot_bytes("ring") == 6 * 8 * 32 * 2
    assert kv_slot_bytes(cfg, CACHE_LEN) == (4 * 64 + 6 * 8) * 32 * 2


@pytest.mark.parametrize("plen", [1, 5, 11, 16])
def test_padded_prefill_then_steps_follow_the_reference(model, programs,
                                                        plen):
    """A prompt shorter than its bucket (padded with another token), then 2
    x window + 3 teacher-forced steps from the rows and rings the prefill
    handed over (the ring wraps at least twice): every position's logits
    against the reference's full forward pass."""
    cfg, w = model
    prefill, pv, step, sv = programs
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 3)
    seq = np.random.default_rng(plen).integers(1, 211, plen + 2 * WINDOW + 3)
    want = np.asarray(ref.logits_at(w, seq.astype(np.int32),
                                    np.arange(len(seq)), M))
    ids = np.full((1, 16), 7, np.int64)
    ids[0, :plen] = seq[:plen]
    outs = prefill.run({pv["feed_names"][0]: ids,
                        pv["feed_names"][1]: np.asarray([[plen]])},
                       return_numpy=False)
    cache.write_slot(1, *outs[1:-1])
    tok, pos = np.zeros((3, 1), np.int64), np.zeros((3, 1), np.int64)
    gaps = [gap(np.asarray(outs[-1])[0], want[plen - 1])]
    for t in range(plen, len(seq)):
        tok[1, 0], pos[1, 0] = seq[t], t
        o, in_place = cache.run(step, sv["cache_feed_names"],
                                {sv["feed_names"][0]: tok,
                                 sv["feed_names"][1]: pos})
        assert in_place                 # all ten buffers donated, none copied
        gaps.append(gap(np.asarray(o[-1])[1], want[t]))
    # where a sparse layer's last chosen and first unchosen expert score
    # within bfloat16's rounding of each other the system may choose the
    # other one, and at this size one expert moves that position's logits
    # by half a standard deviation: a few positions may, most may not
    assert np.median(gaps) <= LIMIT / 2, gaps
    assert np.sum(np.asarray(gaps) > LIMIT) <= 4, gaps
    counts = np.asarray(o[-2])
    # one live slot at position t: t + 1 rows of each full layer, the whole
    # ring of each window layer; every column of three slots gone over
    assert counts[-2] == 2 * len(seq) + 3 * WINDOW
    assert counts[-1] == 3 * (2 * CACHE_LEN + 3 * WINDOW)


def test_each_layers_attention_block_is_the_references(model):
    """What `window_gap` compares on the chip: the attention block of every
    layer over the SYSTEM's own stream, prefill rows and step rows, against
    the reference's block over the same stream."""
    cfg, w = model
    pprog, pv = build(cfg, laguna.build_prefill, 32, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"],
                        pv["attn_in"] + pv["attn_out"], scope=w,
                        name="prefill_32_layers")
    ids = np.random.default_rng(3).integers(1, 211, (1, 32))
    outs = [np.asarray(o, np.float32)[0] for o in prefill.run(
        {pv["feed_names"][0]: ids, pv["feed_names"][1]: np.asarray([[32]])})]
    rows = np.arange(WINDOW, 32)
    for i in range(5):
        want = ref.attention_at(w, i, outs[i], rows, M)
        assert ref.rms_gap(outs[5 + i][rows], want) < 0.02, i


def test_a_dropped_gate_is_nothing_like_the_reference(model, monkeypatch):
    cfg, w = model
    monkeypatch.setattr(laguna.layers, "sigmoid", lambda g: laguna.layers.scale(
        g, scale=0.0, bias=1.0))
    pprog, pv = build(cfg, laguna.build_prefill, 32, CACHE_LEN)
    monkeypatch.undo()
    bad = Predictor(pprog, pv["feed_names"], pv["attn_in"] + pv["attn_out"],
                    scope=w, name="prefill_32_no_gate")
    ids = np.random.default_rng(3).integers(1, 211, (1, 32))
    outs = [np.asarray(o, np.float32)[0] for o in bad.run(
        {pv["feed_names"][0]: ids, pv["feed_names"][1]: np.asarray([[32]])})]
    rows = np.arange(WINDOW, 32)
    assert ref.rms_gap(outs[5][rows],
                       ref.attention_at(w, 0, outs[0], rows, M)) > 0.5


def test_through_the_engine_tokens_counters_and_reused_slots(model):
    """Served through DecodeEngine with fewer slots than requests: every
    served token lies within LIMIT of the reference's best at its position,
    the step's counts arrive, nothing is copied."""
    from paddle_tpu import observability as obs

    cfg, w = model
    kernel, banded = window_lowerings()
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=[16, 32], name="lg-test",
                               adopt_params=True)
    try:
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, 211, n) for n in (27, 4, 13, 32, 9)]
        streams = [eng.submit(p, max_new=20) for p in prompts]
        for p, s in zip(prompts, streams):
            toks = list(s.result(timeout=120))
            seq = np.zeros(CACHE_LEN, np.int32)
            seq[:len(p) + 20] = list(p) + toks
            at = len(p) - 1 + np.arange(20)
            gaps = ref.token_gaps(ref.logits_at(w, seq, at, M), toks)
            assert np.sum(gaps > LIMIT) <= 2, gaps
        st = eng.stats()
        assert st["cache_copy_steps"] == 0 and st["step_errors"] == 0
        assert st["moe_assignments_total"] > st["moe_assignments_held"] > 0
        assert st["kv_rows_read"] > st["kv_rows_live"] > 0
        assert st["state_bytes_ring"] == 2 * 6 * WINDOW * 32 * 2
        assert st["state_bytes_rows"] == 2 * 4 * CACHE_LEN * 32 * 2
        assert st["state_bytes_fixed"] == 0
        # three window layers in each of the two prefill programs, on the
        # CPU through the banded blocks; the step's rings are no window call
        assert st["window_banded_lowerings"] - banded == 6
        assert st["window_kernel_lowerings"] == kernel
        assert "ops_gqa_attention_window_banded" in obs.render_prom()
    finally:
        eng.stop(drain=False, timeout=5)


def test_the_four_chips_shares_add_up_to_the_uncut_layer(model):
    """One sparse layer of the system, told each of the four held ranges in
    turn, against the reference's layer over all 16 experts: the routed
    parts add, the shared expert counts once."""
    whole = dict(M, num_experts=16, first_expert=0,
                 layer_types=[SLIDING], mlp_layer_types=["sparse"],
                 num_attention_heads_per_layer=[6])
    w = ref.make_weights(whole, 5)
    bw = {k: v.astype(jnp.float32) for k, v in ref.layer_weights(w, 0).items()}
    h = jnp.asarray(np.random.default_rng(5).normal(size=(9, 64)),
                    jnp.bfloat16)
    hf = h.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.feed_forward(hf, bw, whole, True, lambda a: a)
        shared = np.asarray(ref.swiglu(
            hf, bw["moe.shared.w1.w"], bw["moe.shared.w3.w"],
            bw["moe.shared.w2.w"], lambda a: a))
    want, total = np.asarray(want), 0.0
    for first in (0, 4, 8, 12):
        part = laguna.LagunaConfig.from_hf(
            dict(whole, num_experts=4), router_experts=16,
            first_expert=first)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.data("x", shape=[9, 64], dtype="bfloat16")
            y = laguna._feed_forward(x, part, 0, None, [], [])
            prog = fluid.default_main_program()
        scope = dict(w)
        for leaf in ("w1", "w3", "w2"):
            name = "lg0.moe.experts." + leaf
            scope[name] = w[name][first:first + 4]
        out = Predictor(prog, ["x"], [y], scope=scope).run({"x": h})[0]
        total = total + np.asarray(out, np.float32)
    got = total - 3 * shared
    # four bfloat16 outputs summed, each within 2**-8 of its own scale
    assert np.abs(got - want).max() <= 0.03 * np.abs(want).max()


# -- what a ring cannot do -------------------------------------------------
@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_pool", {"prefix_pool": object()}),
    ("session_tier", {"session_tier": object()}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("draft", {"draft": object()}),
    ("role='decode'", {"role": "decode"}),
])
def test_what_needs_rows_alone_refuses_a_ring(model, feature, kwargs):
    cfg, w = model
    with pytest.raises(ValueError, match="ring of a window layer") as e:
        serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                             auto_start=False, **kwargs)
    assert feature in str(e.value)


def test_the_wire_and_the_prefill_replica_refuse_a_ring(model):
    from paddle_tpu.serving.disagg.prefill import PrefillEngine

    cfg, w = model
    with pytest.raises(ValueError, match="ring of a window layer"):
        PrefillEngine(cfg, w, cache_len=CACHE_LEN, auto_start=False)
    with pytest.raises(ValueError, match="ring of a window layer"):
        kv_slot_bytes(cfg, CACHE_LEN, "int8")
    eng = serving.DecodeEngine(cfg, w, slots=1, cache_len=CACHE_LEN,
                               prompt_buckets=[8], auto_start=False,
                               adopt_params=True)
    with pytest.raises(ValueError, match="ring of a window layer"):
        eng.submit_prefilled(object())


def test_what_the_config_names_and_the_file_does_not_build_is_refused():
    for key, value in (("gating", "per-element"), ("norm_topk_prob", False),
                       ("moe_router_logit_softcapping", 30.0),
                       ("moe_apply_router_weight_on_input", True)):
        with pytest.raises(ValueError, match=key):
            laguna.LagunaConfig.from_hf(dict(M, **{key: value}), 16, 4)


# -- what must not move for the other served programs ------------------------
def _step_digest(cfg, builder, cache_len, platform):
    """:func:`digest` of the step program's StableHLO for `platform`."""
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = builder(cfg, cache_len)
        prog = fluid.default_main_program()
    names = [x.name for x in v["fetch_vars"]]
    step = build_step_fn(prog, v["feed_names"], names, is_test=True,
                         platform=platform)
    block = prog.global_block()
    params = {x.name: jax.ShapeDtypeStruct(tuple(x.shape),
                                           jnp.dtype(str(x.dtype)))
              for x in prog.list_vars() if getattr(x, "persistable", False)}
    feeds = {n: jax.ShapeDtypeStruct(
        tuple(3 if d is None or d < 0 else d for d in block.var(n).shape),
        jnp.dtype("int32" if str(block.var(n).dtype) == "int64"
                  else str(block.var(n).dtype)))
        for n in v["feed_names"]}
    text = jax.jit(lambda p, f: step(p, f, jax.random.PRNGKey(0))[0]).trace(
        params, feeds).lower(lowering_platforms=(platform,)).as_text()
    return digest(text)


NH = dict(hybrid_override_pattern="ME*EM", vocab_size=211, hidden_size=64,
          num_attention_heads=2, num_key_value_heads=1, head_dim=16,
          mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
          conv_kernel=4, chunk_size=8, n_routed_experts=8,
          num_experts_per_tok=4, moe_latent_size=128,
          moe_intermediate_size=128, moe_shared_expert_intermediate_size=96,
          routed_scaling_factor=5.0, layer_norm_epsilon=1e-5)
STEP_DIGESTS = {
    # read on the parent commit (PR 34's tree) with this very function: a
    # PR that means to change one of these programs writes its new digest
    # here and says so
    ("nemotron_h", "cpu"): "27100799a82c7e51",
    ("nemotron_h", "tpu"): "f2956dc990f34c6d",
    ("gpt", "cpu"): "65479259cd241dcc",
    ("gpt", "tpu"): "65479259cd241dcc",
}


@pytest.mark.parametrize("family,platform", sorted(STEP_DIGESTS))
def test_the_other_served_step_programs_lower_unchanged(family, platform):
    """The hybrid's and GPT's decode steps share `gqa_attention`,
    `moe_route_topk`, `held_experts_ffn` and `decode_cache_write` with this
    model: what this PR added to those ops leaves their programs as they
    lowered on the parent commit."""
    if family == "gpt":
        cfg, builder = gpt.gpt_tiny(), gpt.build_gpt_decode_step
    else:
        cfg = nemotron_h.NemotronHConfig.from_hf(NH, router_experts=32,
                                                 first_expert=8)
        builder = nemotron_h.build_step
    assert _step_digest(cfg, builder, 32, platform) == STEP_DIGESTS[
        (family, platform)]
