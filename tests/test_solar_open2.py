"""The Solar-Open2 decoder (models/solar_open2.py) and what it brought to the
ops, built into programs (`kda_scan` over a prompt, `kda_step` on a slot's
float32 state; the ops alone are tests/test_kda_ops.py), against the plain
reference (benchmark/reference/solar_open2_lm.py: the recurrence position by
position) at a tiny size on the CPU, one softmax layer and ONE delta-rule
layer after it (`gqa_interval` 1: the programs compile in half the time of
the published three); `rows` beside `fixed` windows and states in one
SlotCache; the share test; the refusals of what cuts, shares or rolls back a
sequence's state.

Tolerance of the logit comparisons: the system holds bfloat16 weights,
windows, K/V and residual stream (2**-8 relative per rounding, a few
roundings a layer), the reference float32 over the same bfloat16 weights; at
this size a position's logits differ by 0.02-0.06 of their standard
deviation while no router's choice has tipped.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.models import solar_open2 as solar
from paddle_tpu.models.decode_utils import require_rows_only
from paddle_tpu.serving.decode import SlotCache

from benchmark.reference import solar_open2_lm as ref

LIMIT = 0.2
CACHE_LEN, LAYERS = 64, 2
HEADS, DIM = 4, 16                       # the delta rule's heads
WIDTH = HEADS * DIM
M = dict(model_type="solar_open2", hidden_size=64, num_attention_heads=4,
         num_key_value_heads=2, head_dim=16, num_hidden_layers=48,
         linear_attn_config={"short_conv_kernel_size": 4, "head_dim": DIM,
                             "num_heads": HEADS, "num_kv_heads": None},
         gqa_layers=[0], gqa_interval=1, use_rope=False, use_gqa_gate=True,
         kda_use_full_proj=False, kda_allow_neg_eigval=True,
         first_k_dense_replace=0, intermediate_size=96,
         moe_intermediate_size=32, n_shared_experts=1, n_routed_experts=4,
         num_experts_per_tok=3, norm_topk_prob=True, routed_scaling_factor=1,
         vocab_size=211, rms_norm_eps=1e-5, tie_word_embeddings=False,
         max_position_embeddings=1048576, router_experts=16, first_expert=4,
         initializer_range=0.08)
N_STATE = 2 + 4                          # K, V; three windows and S


def gap(got, want):
    return float(np.abs(got - want).max() / want.std())


@pytest.fixture(scope="module")
def model():
    cfg = solar.SolarOpen2Config.from_hf(M, router_experts=16, first_expert=4)
    return cfg, ref.make_weights(M, 2147483693)


def build(cfg, fn, *args):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fn(cfg, *args)
        return fluid.default_main_program(), v


@pytest.fixture(scope="module")
def programs(model):
    """The prefill (bucket 16) and step programs as the engine builds them,
    with what the engine does not fetch fetched last."""
    cfg, w = model
    pprog, pv = build(cfg, solar.build_prefill, 16, CACHE_LEN)
    sprog, sv = build(cfg, solar.build_step, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"],
                        pv["fetch_vars"] + pv["attn_in"] + pv["attn_out"]
                        + [pv["logits"]], scope=w, name="solar_prefill_16")
    step = Predictor(sprog, sv["feed_names"],
                     sv["fetch_vars"] + sv["attn_in"] + sv["attn_out"]
                     + [sv["logits"]], scope=w, name="solar_step",
                     donate_feeds=sv["cache_feed_names"])
    return prefill, pv, step, sv


# -- the model against the reference ----------------------------------------
def test_the_checkpoint_of_the_reference_is_the_models_own(model):
    cfg, w = model
    shapes = solar.param_shapes(cfg)
    assert set(w) == set(shapes)
    assert all(tuple(w[n].shape) == tuple(s) and str(w[n].dtype) == d
               for n, (s, d) in shapes.items())


def test_the_declaration_holds_rows_beside_windows_and_states(model):
    cfg, _ = model
    decl = cfg.decode_model(CACHE_LEN)
    want = [("k_0", "rows", (CACHE_LEN, 32)), ("v_0", "rows", (CACHE_LEN, 32))]
    want += [("conv_%s_1" % p, "fixed", (3, WIDTH)) for p in "qkv"]
    want.append(("kda_1", "fixed", (HEADS, DIM, DIM)))
    assert [(e.name, e.kind, e.shape) for e in decl.state] == want
    assert decl.rows_are_kv
    assert decl.slot_bytes("rows") == 2 * CACHE_LEN * 32 * 2
    assert decl.slot_bytes("fixed") == (3 * 3 * WIDTH * 2
                                        + HEADS * DIM * DIM * 4)
    # the published period: rows for one layer in four
    four = solar.SolarOpen2Config.from_hf(dict(M, gqa_interval=3), 16, 4)
    kinds = [e.kind for e in four.decode_model(CACHE_LEN).state]
    assert kinds == ["rows"] * 2 + ["fixed"] * 12 and four.num_layers == 4
    with pytest.raises(ValueError, match="over the model's 1048576"):
        cfg.decode_model(1048577)


@pytest.mark.parametrize("plen", [1, 11, 16])
def test_padded_prefill_then_steps_follow_the_reference(model, programs,
                                                        plen):
    """A prompt shorter than its bucket (padded with another token), then
    30 teacher-forced steps through the rows, windows and states the
    prefill handed over: every position's logits against the reference's
    full forward pass."""
    cfg, w = model
    prefill, pv, step, sv = programs
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 3)
    seq = np.random.default_rng(plen).integers(1, 211, plen + 30)
    want = np.asarray(ref.logits_at(w, seq.astype(np.int32),
                                    np.arange(len(seq)), M))
    ids = np.full((1, 16), 7, np.int64)
    ids[0, :plen] = seq[:plen]
    outs = prefill.run({pv["feed_names"][0]: ids,
                        pv["feed_names"][1]: np.asarray([[plen]])},
                       return_numpy=False)
    assert all(np.asarray(o)[0, plen:].any() == 0 for o in outs[1:3])
    cache.write_slot(1, *outs[1:1 + N_STATE])
    tok, pos = np.zeros((3, 1), np.int64), np.zeros((3, 1), np.int64)
    gaps = [gap(np.asarray(outs[-1])[0], want[plen - 1])]
    for t in range(plen, len(seq)):
        tok[1, 0], pos[1, 0] = seq[t], t
        o, in_place = cache.run(step, sv["cache_feed_names"],
                                {sv["feed_names"][0]: tok,
                                 sv["feed_names"][1]: pos})
        assert in_place                 # all six buffers donated
        gaps.append(gap(np.asarray(o[-1])[1], want[t]))
    # where the system's bfloat16 stream tips a router's choice at one
    # position (3 of 16 experts, a handful of positions in forty), that
    # position's key and value differ, and a state of 16 x 16 carries the
    # difference to every later position (a softmax over many keys would
    # dilute it): 0.06-0.35 with a flip behind, 0.7 at the worst, where a
    # state or a window not handed over reads 3-5. Each mixer is held
    # tightly on the system's own stream (below).
    assert np.median(gaps) <= 0.5 and max(gaps) <= 1.5, (
        np.median(gaps), max(gaps))
    counts = np.asarray(o[1 + N_STATE])
    # one live slot at position t: its delta-rule state, all three slots'
    # updated; t + 1 K/V rows, every column of three slots read
    t = len(seq) - 1
    assert list(counts[-4:]) == [1, 3, t + 1, 3 * CACHE_LEN]


def test_each_layers_mixer_is_the_references_on_the_same_stream(model,
                                                                programs):
    """What each layer's mixer adds, through the prefill's scan and flash
    or dense attention and through the step's state and rows, against the
    reference's block (the recurrence position by position) over the
    system's OWN stream, so that only this block's arithmetic differs; and
    the state the fill hands over against the reference's at the prompt's
    real end."""
    cfg, w = model
    prefill, pv, step, sv = programs
    plen, n_new = 13, 12
    seq = np.random.default_rng(23).integers(1, 211, plen + n_new)
    ids = np.full((1, 16), 7, np.int64)
    ids[0, :plen] = seq[:plen]
    outs = prefill.run({pv["feed_names"][0]: ids,
                        pv["feed_names"][1]: np.asarray([[plen]])},
                       return_numpy=False)
    handed = outs[1:1 + N_STATE]
    rest = [np.asarray(a, np.float32)[0, :plen]
            for a in outs[1 + N_STATE:-1]]
    stream = [[a] for a in rest[:LAYERS]]
    added = [[a] for a in rest[LAYERS:]]
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 1)
    cache.write_slot(0, *handed)
    for t in range(plen, plen + n_new):
        o, _ = cache.run(step, sv["cache_feed_names"],
                         {sv["feed_names"][0]: np.asarray([[seq[t]]]),
                          sv["feed_names"][1]: np.asarray([[t]])})
        rest = [np.asarray(a, np.float32) for a in o[2 + N_STATE:]]
        for i in range(LAYERS):
            stream[i].append(rest[i])
            added[i].append(rest[LAYERS + i])
    every = np.arange(plen + n_new)
    for i in range(LAYERS):
        want, state = ref.mixer_at(w, i, np.concatenate(stream[i]), every, M,
                                   stop=plen)
        got = np.concatenate(added[i])
        for path, rows in (("fill", every[:plen]), ("step", every[plen:])):
            assert ref.rms_gap(got[rows], np.asarray(want)[rows]) < 0.03, \
                (i, path)
        if state is not None:
            got = np.asarray(handed[-1])[0]
            assert ref.rms_gap(got.reshape(-1, DIM),
                               np.asarray(state).reshape(-1, DIM)) < 0.03, i


def test_a_step_goes_on_where_a_longer_prefill_would_be(model, programs):
    """The step's path against the scan's on the same positions: a prompt
    of 32 through the prefill program, against its first 16 positions
    through the prefill program and the other 16 through steps. The first
    two layers see the same stream either way but for bfloat16's rounding
    of what the first adds."""
    cfg, w = model
    pprog, pv = build(cfg, solar.build_prefill, 32, CACHE_LEN)
    whole = Predictor(pprog, pv["feed_names"], pv["attn_out"][:2], scope=w,
                      name="solar_prefill_32_first")
    seq = np.random.default_rng(17).integers(1, 211, 32)
    want = whole.run({pv["feed_names"][0]: seq[None],
                      pv["feed_names"][1]: np.asarray([[32]])})
    prefill, pv16, step, sv = programs
    outs = prefill.run({pv16["feed_names"][0]: seq[None, :16],
                        pv16["feed_names"][1]: np.asarray([[16]])},
                       return_numpy=False)
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 1)
    cache.write_slot(0, *outs[1:1 + N_STATE])
    for t in range(16, 32):
        o, _ = cache.run(step, sv["cache_feed_names"],
                         {sv["feed_names"][0]: np.asarray([[seq[t]]]),
                          sv["feed_names"][1]: np.asarray([[t]])})
        rest = o[2 + N_STATE + LAYERS:]
        for layer in (0, 1):
            assert ref.rms_gap(
                np.asarray(rest[layer], np.float32),
                np.asarray(want[layer], np.float32)[0, t][None]) < 0.03


def test_through_the_engine_tokens_counters_and_reused_slots(model):
    """Served through DecodeEngine with fewer slots than requests: the
    served tokens lie near the reference's best at their positions, the
    step's counts arrive, nothing is copied."""
    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=[32], name="solar-test",
                               adopt_params=True)
    try:
        rng = np.random.default_rng(9)
        prompts = [rng.integers(1, 211, n) for n in (27, 4, 13, 32, 9)]
        streams = [eng.submit(p, max_new=20) for p in prompts]
        gaps = []
        for p, s in zip(prompts, streams):
            toks = list(s.result(timeout=120))
            seq = np.zeros(CACHE_LEN, np.int32)
            seq[:len(p) + 20] = list(p) + toks
            at = len(p) - 1 + np.arange(20)
            gaps.append(ref.token_gaps(ref.logits_at(w, seq, at, M), toks))
        gaps = np.concatenate(gaps)
        assert np.median(gaps) <= 0.01 and gaps.max() <= LIMIT, gaps
        st = eng.stats()
        assert st["cache_copy_steps"] == 0 and st["step_errors"] == 0
        assert st["moe_assignments_total"] > st["moe_assignments_held"] > 0
        assert st["kda_states_updated"] == st["steps"] * 2
        assert st["kda_states_updated"] >= st["kda_states_live"] > 0
        assert st["kv_rows_read"] == st["steps"] * 2 * CACHE_LEN
        assert st["kv_rows_read"] > st["kv_rows_live"] > 0
        decl = cfg.decode_model(CACHE_LEN)
        assert st["state_bytes_rows"] == 2 * decl.slot_bytes("rows")
        assert st["state_bytes_fixed"] == 2 * decl.slot_bytes("fixed")
        assert st["state_bytes_ring"] == 0
    finally:
        eng.stop(drain=False, timeout=5)


def test_the_eight_chips_shares_add_up_to_the_uncut_layer():
    """The share test: one layer's second half of the system, told each of
    the eight held ranges in turn (at the published sizes: eight ranges of
    forty), against the reference's layer over all 16 experts: the routed
    parts add, the shared expert counts once."""
    whole = dict(M, n_routed_experts=16, first_expert=0)
    w = ref.make_weights(whole, 5)
    bw = {k: v.astype(jnp.float32) for k, v in ref.layer_weights(w, 1).items()}
    h = jnp.asarray(np.random.default_rng(5).normal(size=(9, 64)),
                    jnp.bfloat16)
    hf = h.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.feed_forward(hf, bw, whole, lambda a: a)
        shared = np.asarray(ref.swiglu(
            hf, bw["moe.shared.w1.w"], bw["moe.shared.w3.w"],
            bw["moe.shared.w2.w"], lambda a: a))
    want, scope = np.asarray(want), dict(w)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.data("x", shape=[9, 64], dtype="bfloat16")
        parts = []
        for first in range(0, 16, 2):
            part = solar.SolarOpen2Config.from_hf(
                dict(whole, n_routed_experts=2), router_experts=16,
                first_expert=first)
            # each share's held experts under a name of its own: layer
            # `first + 1` of one program, whose other leaves are layer 1's
            for name, leaf in ref.layer_weights(w, 1).items():
                held = name.startswith("moe.experts.")
                scope["so%d.%s" % (first + 1, name)] = (
                    leaf[first:first + 2] if held else leaf)
            parts.append(solar._feed_forward(x, part, first + 1, None, [],
                                             []))
        prog = fluid.default_main_program()
    outs = Predictor(prog, ["x"], parts, scope=scope).run({"x": h})
    total = sum(np.asarray(o, np.float32) for o in outs)
    got = total - 7 * shared
    # eight bfloat16 outputs summed, each within 2**-8 of its own scale
    assert np.abs(got - want).max() <= 0.04 * np.abs(want).max()


# -- what cuts, shares, quantises or rolls back a sequence's state -----------
@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_pool", {"prefix_pool": object()}),
    ("session_tier", {"session_tier": object()}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("draft", {"draft": object()}),
    ("role='decode'", {"role": "decode"}),
])
def test_what_needs_rows_alone_refuses_a_delta_rule_state(model, feature,
                                                          kwargs):
    cfg, w = model
    with pytest.raises(ValueError, match="fixed-size state") as e:
        serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                             auto_start=False, **kwargs)
    said = str(e.value)
    assert feature in said and "conv_q_1" in said
    # what such a state would need of the feature, by mechanism
    assert "snapshot" in said and "rolled back only to a copy" in said


def test_the_wire_and_the_prefill_replica_refuse_a_delta_rule_state(model):
    from paddle_tpu.serving.disagg.prefill import PrefillEngine

    cfg, w = model
    with pytest.raises(ValueError, match="fixed-size state"):
        PrefillEngine(cfg, w, cache_len=CACHE_LEN, auto_start=False)
    eng = serving.DecodeEngine(cfg, w, slots=1, cache_len=CACHE_LEN,
                               prompt_buckets=[8], auto_start=False,
                               adopt_params=True)
    with pytest.raises(ValueError, match="delta-rule state"):
        eng.submit_prefilled(object())
    with pytest.raises(ValueError, match="ring has written over"):
        ring = cfg.decode_model(CACHE_LEN)
        ring.state = [e._replace(kind="ring") for e in ring.state[:2]]
        require_rows_only(ring, "anything")


def test_what_the_config_names_and_the_file_does_not_build_is_refused():
    for key, value in (("use_rope", True), ("use_gqa_gate", False),
                       ("kda_use_full_proj", True),
                       ("first_k_dense_replace", 1),
                       ("norm_topk_prob", False),
                       ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match=key):
            solar.SolarOpen2Config.from_hf(dict(M, **{key: value}), 16, 4)
    with pytest.raises(ValueError, match="num_kv_heads"):
        solar.SolarOpen2Config.from_hf(dict(M, linear_attn_config=dict(
            M["linear_attn_config"], num_kv_heads=2)), 16, 4)
    one = solar.SolarOpen2Config.from_hf(
        dict(M, kda_allow_neg_eigval=False), 16, 4)
    assert one.beta_scale == 1.0
