"""The hybrid decoder (models/nemotron_h.py) through SlotCache and
DecodeEngine against the plain reference's full forward pass, at a tiny
size on the CPU; state of two kinds in one SlotCache; the refusals of what
needs rows alone; GPT through the same declaration.

Tolerance of the logit comparisons: the system holds bfloat16 weights and a
bfloat16 residual stream (2**-8 relative per rounding, a few roundings per
block, 5 blocks), the reference float32 over the same bfloat16 weights. At
this size the largest difference read over many positions is 0.026 of the
position's logit standard deviation; the limit is 0.08. A state handed over
at the wrong position, a stale state or a wrong expert share reads 0.3-1.5.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.models import gpt, nemotron_h as nh
from paddle_tpu.models.decode_utils import StateEntry
from paddle_tpu.serving.decode import SlotCache, kv_slot_bytes

from benchmark.reference import nemotron_h_lm as ref

LIMIT = 0.08
CACHE_LEN = 64
M = dict(hybrid_override_pattern="ME*EM", vocab_size=211, hidden_size=64,
         num_attention_heads=2, num_key_value_heads=1, head_dim=16,
         mamba_num_heads=4, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
         conv_kernel=4, chunk_size=8, n_routed_experts=8,
         num_experts_per_tok=4, moe_latent_size=32, moe_intermediate_size=48,
         moe_shared_expert_intermediate_size=96, routed_scaling_factor=5.0,
         layer_norm_epsilon=1e-5, time_step_min=0.001, time_step_max=0.1,
         time_step_floor=1e-4, router_experts=32, first_expert=8)


@pytest.fixture(scope="module")
def model():
    cfg = nh.NemotronHConfig.from_hf(M, router_experts=32, first_expert=8)
    return cfg, ref.make_weights(M, 2147483659)


def build(cfg, fn, *args):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fn(cfg, *args)
        return fluid.default_main_program(), v


@pytest.fixture(scope="module")
def programs(model):
    """The prefill (bucket 16) and step programs as the engine builds
    them, with the logits fetched last."""
    cfg, w = model
    pprog, pv = build(cfg, nh.build_prefill, 16, CACHE_LEN)
    sprog, sv = build(cfg, nh.build_step, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"],
                        pv["fetch_vars"] + [pv["logits"]], scope=w,
                        name="prefill_16")
    step = Predictor(sprog, sv["feed_names"],
                     sv["fetch_vars"] + [sv["logits"]], scope=w,
                     name="decode_step", donate_feeds=sv["cache_feed_names"])
    return prefill, pv, step, sv


def gap(got, want):
    return float(np.abs(got - want).max() / want.std())


def test_the_checkpoint_of_the_reference_is_the_models_own(model):
    cfg, w = model
    shapes = nh.param_shapes(cfg)
    assert set(w) == set(shapes)
    for name, (shape, dtype) in shapes.items():
        assert tuple(w[name].shape) == tuple(shape), name
        assert str(w[name].dtype) == dtype, name


def test_the_declaration_holds_state_of_two_kinds(model):
    cfg, _ = model
    state = cfg.decode_model(CACHE_LEN).state
    assert [(e.name, e.kind) for e in state] == [
        ("conv_0", "fixed"), ("ssm_0", "fixed"), ("k_2", "rows"),
        ("v_2", "rows"), ("conv_4", "fixed"), ("ssm_4", "fixed")]
    by = {e.name: e for e in state}
    assert by["conv_0"].shape == (3, cfg.conv_dim)
    assert by["ssm_0"].shape == (4, 8, 16) and by["ssm_0"].dtype == np.float32
    assert by["k_2"].shape == (CACHE_LEN, 16)       # kv_heads * head_dim wide
    assert by["k_2"].dtype == jnp.bfloat16
    # the sums the engine's accounting is made of
    model_ = cfg.decode_model(CACHE_LEN)
    rows = 2 * CACHE_LEN * 16 * 2
    fixed = 2 * (3 * cfg.conv_dim * 2 + 4 * 8 * 16 * 4)
    assert model_.slot_bytes("rows") == rows
    assert model_.slot_bytes("fixed") == fixed
    assert kv_slot_bytes(cfg, CACHE_LEN) == rows + fixed


@pytest.mark.parametrize("plen", [1, 5, 11, 16])
def test_padded_prefill_then_steps_follow_the_reference(model, programs,
                                                        plen):
    """A prompt shorter than its bucket (padded with another token), then
    teacher-forced steps from the state the prefill handed over: every
    position's logits against the reference's full forward pass."""
    cfg, w = model
    prefill, pv, step, sv = programs
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 3)
    seq = np.random.default_rng(plen).integers(1, 211, plen + 12)
    want = np.asarray(ref.logits_at(w, seq.astype(np.int32),
                                    np.arange(len(seq)), M))
    ids = np.full((1, 16), 7, np.int64)
    ids[0, :plen] = seq[:plen]
    outs = prefill.run({pv["feed_names"][0]: ids,
                        pv["feed_names"][1]: np.asarray([[plen]])},
                       return_numpy=False)
    assert gap(np.asarray(outs[-1])[0], want[plen - 1]) <= LIMIT
    assert int(np.asarray(outs[0])[0, 0]) == int(want[plen - 1].argmax())
    cache.write_slot(1, *outs[1:-1])
    tok, pos = np.zeros((3, 1), np.int64), np.zeros((3, 1), np.int64)
    for t in range(plen, len(seq)):
        tok[1, 0], pos[1, 0] = seq[t], t
        o, in_place = cache.run(step, sv["cache_feed_names"],
                                {sv["feed_names"][0]: tok,
                                 sv["feed_names"][1]: pos})
        assert in_place                 # all six buffers donated, none copied
        assert gap(np.asarray(o[-1])[1], want[t]) <= LIMIT, t


def test_a_reused_slot_keeps_nothing_of_its_last_sequence(model, programs):
    cfg, w = model
    prefill, pv, step, sv = programs
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 2)
    tok, pos = np.zeros((2, 1), np.int64), np.zeros((2, 1), np.int64)
    rng = np.random.default_rng(3)
    for plen in (14, 4):                # a long sequence, then a short one
        seq = rng.integers(1, 211, plen + 6)
        want = np.asarray(ref.logits_at(w, seq.astype(np.int32),
                                        np.arange(len(seq)), M))
        ids = np.zeros((1, 16), np.int64)
        ids[0, :plen] = seq[:plen]
        outs = prefill.run({pv["feed_names"][0]: ids,
                            pv["feed_names"][1]: np.asarray([[plen]])},
                           return_numpy=False)
        cache.write_slot(0, *outs[1:-1])
        for t in range(plen, len(seq)):
            tok[0, 0], pos[0, 0] = seq[t], t
            o, _ = cache.run(step, sv["cache_feed_names"],
                             {sv["feed_names"][0]: tok,
                              sv["feed_names"][1]: pos})
            assert gap(np.asarray(o[-1])[0], want[t]) <= LIMIT, (plen, t)
    # K/V rows past the short sequence are zero again: written whole
    k = cache.read_slot(0)[2]
    assert not np.asarray(k, np.float32)[10:].any()


def test_through_the_engine_tokens_counters_and_reused_slots(model):
    """Served through DecodeEngine with fewer slots than requests: every
    served token lies within LIMIT of the reference's best at its position,
    and the step's counts arrive in stats()."""
    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=[8, 16], name="nh-test",
                               adopt_params=True)
    try:
        assert all(eng._params[k] is w[k] for k in w)   # adopted, not copied
        rng = np.random.default_rng(11)
        prompts = [rng.integers(1, 211, n) for n in (3, 16, 9, 5, 12)]
        streams = [eng.submit(p, max_new=10) for p in prompts]
        served = [(list(p), s.result(60)) for p, s in zip(prompts, streams)]
        gaps = ref.served_gaps(w, served, M, seq_len=CACHE_LEN, out_len=10)
        assert max(g.max() for g in gaps) <= LIMIT
        st = eng.stats()
        assert st["cache_copy_steps"] == 0 and st["prefills"] == 5
        # 8 of 32 experts held: about a quarter of the assignments land here
        assert st["moe_assignments_total"] % (4 * 2) == 0
        assert 0 < st["moe_assignments_held"] < st["moe_assignments_total"]
        assert st["moe_expert_load_max_sum"] >= st["steps"]
        model_ = cfg.decode_model(CACHE_LEN)
        assert st["state_bytes_rows"] == 2 * model_.slot_bytes("rows")
        assert st["state_bytes_fixed"] == 2 * model_.slot_bytes("fixed")
        assert eng.slot_bytes() == model_.slot_bytes()
    finally:
        eng.stop(drain=False, timeout=5)


def test_the_four_chips_shares_add_up_to_the_uncut_layer(model):
    """One expert layer of the system, told each of the four held ranges in
    turn, against the reference's layer over all 32 experts: the routed
    parts add (the up-projection is linear), the shared expert counts
    once."""
    cfg, _ = model
    whole = dict(M, n_routed_experts=32, first_expert=0)
    w = ref.make_weights(dict(whole, hybrid_override_pattern="E"), 5)
    bw = {k[len("nh0."):]: v.astype(jnp.float32) for k, v in w.items()
          if k.startswith("nh0.")}
    h = jnp.asarray(np.random.default_rng(5).normal(size=(9, 64)),
                    jnp.bfloat16)
    hf = h.astype(jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.moe_parts(hf, bw, whole, lambda a: a)[0])
        shared = np.asarray(ref.blocks.matmul(
            ref.relu2(ref.blocks.matmul(hf, bw["moe.shared.fc1.w"],
                                        lambda a: a)),
            bw["moe.shared.fc2.w"], lambda a: a))
    total = 0.0
    for first in (0, 8, 16, 24):
        part = nh.NemotronHConfig.from_hf(M, router_experts=32,
                                          first_expert=first)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.data("x", shape=[9, 64], dtype="bfloat16")
            y, _, _ = nh._moe(x, part, "nh0.moe", None)
            prog = fluid.default_main_program()
        scope = dict(w)
        for leaf in ("w1", "w2"):
            name = "nh0.moe.experts." + leaf
            scope[name] = w[name][first:first + 8]
        out = Predictor(prog, ["x"], [y], scope=scope).run({"x": h})[0]
        total = total + np.asarray(out, np.float32)
    got = total - 3 * shared
    # four bfloat16 outputs summed, each within 2**-8 of its own scale
    assert np.abs(got - want).max() <= 0.03 * np.abs(want).max()


@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_pool", {"prefix_pool": object()}),
    ("session_tier", {"session_tier": object()}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("draft", {"draft": object()}),
    ("role='decode'", {"role": "decode"}),
])
def test_what_needs_rows_alone_refuses_fixed_state(model, feature, kwargs):
    cfg, w = model
    with pytest.raises(ValueError, match="fixed-size state") as e:
        serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                             auto_start=False, **kwargs)
    assert feature in str(e.value)


def test_the_wire_and_the_prefill_replica_refuse_fixed_state(model):
    from paddle_tpu.serving.disagg.prefill import PrefillEngine

    cfg, w = model
    with pytest.raises(ValueError, match="fixed-size state"):
        PrefillEngine(cfg, w, cache_len=CACHE_LEN, auto_start=False)
    with pytest.raises(ValueError, match="fixed-size state"):
        kv_slot_bytes(cfg, CACHE_LEN, "int8")
    eng = serving.DecodeEngine(cfg, w, slots=1, cache_len=CACHE_LEN,
                               prompt_buckets=[8], auto_start=False,
                               adopt_params=True)
    with pytest.raises(ValueError, match="fixed-size state"):
        eng.submit_prefilled(object())


def test_gpt_declares_its_rows_and_serves_through_the_same_door():
    cfg = gpt.gpt_tiny()
    model_ = cfg.decode_model(32)
    assert [e.name for e in model_.state] == ["k_0", "k_1", "v_0", "v_1"]
    assert all(e == StateEntry(e.name, (32, cfg.hidden), np.float32, "rows")
               for e in model_.state)
    assert model_.slot_bytes("fixed") == 0
    assert kv_slot_bytes(cfg, 32) == 2 * 2 * 32 * cfg.hidden * 4
    q = cfg.decode_model(32, "int8")
    assert len(q.state) == 8 and q.state[-1].shape == (32, 1)
    assert kv_slot_bytes(cfg, 32, "int8") == 2 * 2 * 32 * (cfg.hidden + 4)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        sv = model_.build_step(cfg, 32)
    # one donated feed per declared entry, in the declaration's order
    assert [n.split("_", 2)[2] for n in sv["cache_feed_names"]] == [
        e.name for e in model_.state]
    rng = np.random.default_rng(0)
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        prog_vars = model_.build_prefill(cfg, 8, 32)
        scope = {v.name: rng.normal(0, 0.05, v.shape).astype(np.float32)
                 for v in fluid.default_main_program().list_vars()
                 if getattr(v, "persistable", False)}
    del prog_vars
    # the one door: the engine builds from the declaration's builders
    assert model_.build_prefill is gpt.build_gpt_prefill
    assert model_.build_step is gpt.build_gpt_decode_step
    eng = serving.DecodeEngine(cfg, scope, slots=2, cache_len=32,
                               prompt_buckets=[8], name="gpt-door")
    try:
        assert len(eng.generate(np.arange(1, 7), max_new=6)) == 6
        st = eng.stats()
        assert st["cache_copy_steps"] == 0
        assert st["state_bytes_fixed"] == 0
        assert st["state_bytes_rows"] == 2 * kv_slot_bytes(cfg, 32)
    finally:
        eng.stop(drain=False, timeout=5)
