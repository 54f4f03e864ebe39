"""The GLM-5 decoder (models/glm_moe_dsa.py) and what it brought to the ops
(interleaved rotary pairs; `dsa_select`, the indexer's exact selection as a
prompt's threshold mask and as a step's kept columns; `mla_attention`, a
prompt's expanded path and a step's absorbed path over gathered cache rows)
against the plain reference (benchmark/reference/glm5_lm.py) and against the
equations written out in numpy, at a tiny size on the CPU; two `rows`
entries a layer of unequal widths in one SlotCache; the refusals of what
knows rows only as K and V of one width.

Tolerance of the logit comparisons: the system holds bfloat16 weights,
caches and residual stream (2**-8 relative per rounding, a few roundings per
layer, 3 layers), the reference float32 over the same bfloat16 weights. At
this size a position's logits differ by 0.02-0.04 of their standard
deviation while every earlier position is kept (the first `index_topk`
positions: held to LIMIT each). Beyond that a position keeps 8 of its 9-46
predecessors, and where the eighth and ninth indexer scores lie within
bfloat16's rounding of each other the system may keep the other one: one key
of eight moves that position's logits by 0.2-3 standard deviations, so there
the median over a sequence's positions is held, and the selection itself is
held exactly where it is computed from the same numbers (float32 inputs,
`test_the_selection_is_the_exact_top_k`; the system's own stream,
`test_each_layers_attention_block_is_the_references`).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.models import glm_moe_dsa as glm
from paddle_tpu.ops import LOWERINGS, hybrid_ops
from paddle_tpu.ops.registry import LowerContext
from paddle_tpu.serving.decode import SlotCache, kv_slot_bytes

from benchmark.reference import glm5_lm as ref

LIMIT = 0.2
CACHE_LEN, TOPK, LAYERS = 64, 8, 3
LATENT = 128     # a latent row's 16 + 4 values, zeros up to the chip's lanes
ROW = LATENT + 8                    # and the indexer's row beside it
M = dict(model_type="glm_moe_dsa", hidden_size=64, num_attention_heads=4,
         q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
         qk_rope_head_dim=4, v_head_dim=16, index_n_heads=16,
         index_head_dim=8, index_topk=TOPK, intermediate_size=96,
         moe_intermediate_size=32, n_shared_experts=1, n_routed_experts=4,
         num_experts_per_tok=3, vocab_size=211, num_hidden_layers=LAYERS,
         first_k_dense_replace=1, rms_norm_eps=1e-5,
         routed_scaling_factor=2.5, num_nextn_predict_layers=0,
         rope_parameters={"rope_theta": 1000000, "rope_type": "default"},
         router_experts=16, first_expert=4, initializer_range=0.08)
RNG = np.random.default_rng(13)


def lower(op, ins, **attrs):
    ins = {k: [jnp.asarray(v)] for k, v in ins.items() if v is not None}
    return {k: np.asarray(v[0]) for k, v in
            LOWERINGS[op](None, ins, attrs).items()}


def gap(got, want):
    return float(np.abs(got - want).max() / want.std())


@pytest.fixture(scope="module")
def model():
    cfg = glm.GlmMoeDsaConfig.from_hf(M, router_experts=16, first_expert=4)
    return cfg, ref.make_weights(M, 2147483659)


def build(cfg, fn, *args):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fn(cfg, *args)
        return fluid.default_main_program(), v


@pytest.fixture(scope="module")
def programs(model):
    """The prefill (bucket 16) and step programs as the engine builds them,
    with what the engine does not fetch fetched last."""
    cfg, w = model
    pprog, pv = build(cfg, glm.build_prefill, 16, CACHE_LEN)
    sprog, sv = build(cfg, glm.build_step, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"],
                        pv["fetch_vars"] + [pv["logits"]], scope=w,
                        name="glm_prefill_16")
    extra = sv["attn_in"] + sv["attn_out"] + sv["selected"] + [sv["logits"]]
    step = Predictor(sprog, sv["feed_names"], sv["fetch_vars"] + extra,
                     scope=w, name="glm_step",
                     donate_feeds=sv["cache_feed_names"])
    return prefill, pv, step, sv


# -- the ops against the equations ------------------------------------------
@pytest.mark.parametrize("rot,with_pos", [(8, False), (4, False), (4, True)])
def test_interleaved_rotary_is_the_references(rot, with_pos):
    """Pairs (x[2i], x[2i + 1]) of the first `rot` dimensions, the turned
    pair handed on at (i, i + rot/2); from the row index or from `pos`."""
    x = RNG.normal(size=(2, 5, 3, 8)).astype(np.float32)
    pos = np.asarray([[7], [31]]) if with_pos else None
    got = lower("rotary_embedding", {"X": x, "Pos": pos}, theta=1e6,
                rotary_dim=rot, interleaved=True)["Out"]
    for b in range(2):
        at = np.arange(5) + (int(pos[b, 0]) if with_pos else 0)
        want = ref.rotary(jnp.asarray(x[b]), jnp.asarray(at), M, rot)
        np.testing.assert_allclose(got[b], np.asarray(want), atol=2e-6)
    # a pair keeps its length, and differs from the half-split pairing
    np.testing.assert_allclose(np.linalg.norm(got, axis=-1),
                               np.linalg.norm(x, axis=-1), rtol=1e-5)
    half = lower("rotary_embedding", {"X": x, "Pos": pos}, theta=1e6,
                 rotary_dim=rot)["Out"]
    assert np.abs(half - got).max() > 0.1


def test_the_kth_largest_is_exact_without_a_sort():
    x = RNG.normal(size=(6, 40)).astype(np.float32)
    x[0, :5] = -np.inf
    x[1, 3] = 0.0
    x[1, 4] = -0.0
    x[2] = -np.abs(x[2])
    keys = hybrid_ops._ordered_bits(jnp.asarray(x))
    assert (np.argsort(np.asarray(keys), -1, kind="stable")
            == np.argsort(np.where(x == 0, 0.0, x), -1, kind="stable")).all()
    for k in (1, 7, 40):
        got = np.asarray(hybrid_ops._kth_largest(keys, k))
        want = np.asarray(hybrid_ops._ordered_bits(
            jnp.asarray(np.sort(x, -1)[:, -k])))
        assert (got == want).all(), k
    assert (np.asarray(hybrid_ops._kth_largest(keys, 41)) == 0).all()


def index_scores_written_out(q, k, w, heads):
    """(T, T) float64: sum_j w[t, j] relu(q[t, j] . k[s])."""
    t = q.shape[0]
    per_head = np.einsum("tjd,sd->tjs", q.reshape(t, heads, -1).astype(
        np.float64), k.astype(np.float64))
    return (np.maximum(per_head, 0) * w[:, :, None]).sum(1)


@pytest.mark.parametrize("t,block", [(32, 4), (24, 128), (6, 128)])
def test_the_selection_is_the_exact_top_k(t, block, monkeypatch):
    """Float32 inputs, so the op and the written-out equations score alike:
    a prompt's mask keeps exactly the `topk` visible keys of largest score
    (all of them below `topk`), through one run of queries and through
    DSA_TIERS runs of blocks; a step's kept columns are the same set."""
    monkeypatch.setattr(hybrid_ops, "DSA_QUERY_BLOCK", block)
    assert hybrid_ops._tiers(t) == ((4, 4) if block == 4 else (t, 1))
    heads, d = 16, 8     # a key all of whose heads score below 0 scores 0.0
    rng = np.random.default_rng(t)      # exactly: with 16 heads none ties
    q = rng.normal(size=(2, t, heads * d)).astype(np.float32)
    k = rng.normal(size=(2, t, d)).astype(np.float32)
    w = rng.normal(size=(2, t, heads)).astype(np.float32)
    mask = lower("dsa_select", {"Q": q, "K": k, "W": w}, heads=heads,
                 topk=TOPK)["Selected"]
    assert mask.shape == (2, t, t) and mask.dtype == np.int8
    for b in range(2):
        scores = index_scores_written_out(q[b], k[b], w[b], heads)
        for row in range(t):
            want = np.argsort(-scores[row, :row + 1],
                              kind="stable")[:TOPK]
            assert set(np.flatnonzero(mask[b, row])) == set(want), (b, row)
            # the step over a cache that holds these keys and more
            cache = np.concatenate([k[b], rng.normal(size=(5, d))], 0)
            kept = lower("dsa_select", {
                "Q": q[b, row][None, None], "K": cache[None].astype(
                    np.float32), "W": w[b, row][None, None],
                "Pos": np.asarray([[row]])}, heads=heads,
                topk=TOPK)["Selected"]
            assert kept.shape == (1, TOPK) and kept.dtype == np.int32
            assert set(kept[0][kept[0] >= 0]) == set(want)
            assert (kept[0] >= 0).sum() == min(row + 1, TOPK)


def test_the_absorbed_path_is_the_expanded_path():
    """`mla_attention` over one float32 sequence: a prompt's expanded path
    (through tiers of blocks) and, position by position, a step's absorbed
    path over a cache that holds the same latent rows, each with the same
    selection; and both are the attention written out."""
    heads, nope, rope, vd, rank, t = 3, 6, 2, 5, 7, 32
    q = RNG.normal(size=(1, t, heads * (nope + rope))).astype(np.float32)
    lat = RNG.normal(size=(1, t, rank + rope)).astype(np.float32)
    wuk = RNG.normal(size=(rank, heads * nope)).astype(np.float32)
    wuv = RNG.normal(size=(rank, heads * vd)).astype(np.float32)
    mask = np.tril(RNG.random((t, t)) < 0.4)
    mask[np.arange(t), RNG.integers(0, np.arange(t) + 1)] = True
    attrs = dict(heads=heads, nope_dim=nope, rope_dim=rope, v_dim=vd)
    expanded = lower("mla_attention", {
        "Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv,
        "Selected": mask[None].astype(np.int8)}, **attrs)["Out"][0]
    k_nope = (lat[0, :, :rank] @ wuk).reshape(t, heads, nope)
    v = (lat[0, :, :rank] @ wuv).reshape(t, heads, vd)
    qh = q[0].reshape(t, heads, nope + rope)
    # the cache holds the same rows and more, each with zeros after it
    cache = np.concatenate([lat, RNG.normal(size=(1, 9, rank + rope))], 1)
    cache = np.concatenate([cache, np.zeros((1, t + 9, 3))], 2)
    for row in range(t):
        kept = np.flatnonzero(mask[row])
        s = (np.einsum("hd,khd->hk", qh[row, :, :nope], k_nope[kept])
             + qh[row, :, nope:] @ lat[0, kept, rank:].T) * (nope + rope) ** -0.5
        p = np.exp(s - s.max(-1, keepdims=True))
        want = np.einsum("hk,khd->hd", p / p.sum(-1, keepdims=True),
                         v[kept]).reshape(-1)
        np.testing.assert_allclose(expanded[row], want, atol=2e-5)
        cols = np.full((1, 20), -1, np.int32)
        cols[0, :len(kept)] = RNG.permutation(kept)
        absorbed = lower("mla_attention", {
            "Q": q[:, row:row + 1], "Latent": cache.astype(np.float32),
            "Wuk": wuk, "Wuv": wuv, "Selected": cols,
            "Pos": np.asarray([[row]])}, **attrs)["Out"][0, 0]
        np.testing.assert_allclose(absorbed, want, atol=2e-5)


def test_the_kept_keys_kernel_against_the_blocks(monkeypatch):
    """The Pallas kernel (interpreted here) against the blocks through XLA
    that the CPU takes, over one selection: float32 to rounding, and rows
    that keep nothing in the first tiles of keys they see."""
    from paddle_tpu.ops import pallas_attention

    rng = np.random.default_rng(2)
    heads, nope, rope, vd, rank, t = 2, 64, 64, 128, 16, 512
    q = rng.normal(size=(1, t, heads * (nope + rope))).astype(np.float32)
    lat = rng.normal(size=(1, t, rank + rope)).astype(np.float32)
    wuk = rng.normal(size=(rank, heads * nope)).astype(np.float32)
    wuv = rng.normal(size=(rank, heads * vd)).astype(np.float32)
    mask = np.tril(rng.random((t, t)) < 0.3)
    mask[np.arange(t), np.arange(t)] = True
    mask[300:, :256] = False
    ins = {"Q": q, "Latent": lat, "Wuk": wuk, "Wuv": wuv,
           "Selected": mask[None].astype(np.int8)}
    attrs = dict(heads=heads, nope_dim=nope, rope_dim=rope, v_dim=vd)
    blocks = lower("mla_attention", ins, **attrs)["Out"]
    real = pallas_attention.kept_keys_attention
    monkeypatch.setattr(hybrid_ops, "KEPT_BLOCK", 128)
    monkeypatch.setattr(
        pallas_attention, "kept_keys_attention",
        lambda *a, **kw: real(*a, **dict(kw, interpret=True)))
    ctx = LowerContext(platform="tpu")
    ctx.mesh_axes = None
    kernel = np.asarray(LOWERINGS["mla_attention"](
        ctx, {k: [jnp.asarray(v)] for k, v in ins.items()}, attrs)["Out"][0])
    np.testing.assert_allclose(kernel, blocks, atol=2e-5)
    with pytest.raises(ValueError, match="tiles"):
        real(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q),
             jnp.asarray(mask[None].astype(np.int8)), heads, 1.0, block=96)


def _prefill_attention_text(platform, t, nope=192, rope=64, vd=256,
                            mesh_axes=None):
    """The StableHLO of one prompt's `mla_attention` call for `platform`."""
    heads, rank = 4, 128
    ctx = LowerContext(platform=platform)
    ctx.mesh_axes = mesh_axes

    def sds(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype)

    def f(q, lat, wuk, wuv, sel):
        return LOWERINGS["mla_attention"](
            ctx, {"Q": [q], "Latent": [lat], "Wuk": [wuk], "Wuv": [wuv],
                  "Selected": [sel]},
            dict(heads=heads, nope_dim=nope, rope_dim=rope, v_dim=vd))["Out"][0]
    return jax.jit(f).trace(
        sds(1, t, heads * (nope + rope)), sds(1, t, rank + rope + 64),
        sds(rank, heads * nope), sds(rank, heads * vd),
        sds(1, t, t, dtype=jnp.int8)).lower(
            lowering_platforms=(platform,)).as_text()


KEPT_PATHS = {
    # what of a call decides, and which of the two paths it lowers
    "the cell's prompt on the chip": (dict(platform="tpu"), "kernel"),
    "the CPU": (dict(platform="cpu"), "blocks"),
    "a sharded program": (dict(platform="tpu", mesh_axes={"dp": "dp"}),
                          "blocks"),
    "a length the tile does not divide": (dict(platform="tpu", t=1280),
                                          "blocks"),
    "a head that does not tile": (dict(platform="tpu", nope=64, rope=32),
                                  "blocks"),
}


@pytest.mark.parametrize("case", sorted(KEPT_PATHS))
def test_the_kept_keys_kernel_is_taken_from_what_the_op_sees(case):
    """A prompt, a TPU, no mesh, a length the tile divides and head widths
    that are multiples of 128: the kernel; anything else the blocks through
    XLA; no caller sets anything. The two lowering counters count."""
    from paddle_tpu import observability as obs

    def counts():
        return [obs.counter("ops.mla_attention.kept_" + path)
                for path in ("kernel", "blocks")]

    call, path = KEPT_PATHS[case]
    before = counts()
    text = _prefill_attention_text(**dict(dict(t=1024), **call))
    assert [n - b for n, b in zip(counts(), before)] == [
        int(path == "kernel"), int(path == "blocks")]
    assert ("kept_keys_attn_fwd" in text) == (path == "kernel")
    assert ("tpu_custom_call" in text) == (path == "kernel")


# -- the model against the reference ----------------------------------------
def test_the_checkpoint_of_the_reference_is_the_models_own(model):
    cfg, w = model
    shapes = glm.param_shapes(cfg)
    assert set(w) == set(shapes)
    assert all(tuple(w[n].shape) == tuple(s) and str(w[n].dtype) == d
               for n, (s, d) in shapes.items())


def test_the_declaration_holds_latent_and_indexer_rows(model):
    cfg, _ = model
    decl = cfg.decode_model(CACHE_LEN)
    assert [(e.name, e.kind, e.shape) for e in decl.state] == [
        (name % i, "rows", (CACHE_LEN, width)) for i in range(LAYERS)
        for name, width in (("lat_%d", LATENT), ("idx_%d", 8))]
    assert not decl.rows_are_kv
    assert kv_slot_bytes(cfg, CACHE_LEN) == LAYERS * CACHE_LEN * ROW * 2
    assert decl.slot_bytes("rows") == decl.slot_bytes()


@pytest.mark.parametrize("plen", [1, 5, 11, 16])
def test_padded_prefill_then_steps_follow_the_reference(model, programs,
                                                        plen):
    """A prompt shorter than its bucket (padded with another token), then
    30 teacher-forced steps through the two caches the prefill handed over:
    every position's logits against the reference's full forward pass."""
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
    assert all(np.asarray(o)[0, plen:].any() == 0 for o in outs[1:-1])
    cache.write_slot(1, *outs[1:-1])
    tok, pos = np.zeros((3, 1), np.int64), np.zeros((3, 1), np.int64)
    gaps = [gap(np.asarray(outs[-1])[0], want[plen - 1])]
    n_state = 2 * LAYERS
    for t in range(plen, len(seq)):
        tok[1, 0], pos[1, 0] = seq[t], t
        o, in_place = cache.run(step, sv["cache_feed_names"],
                                {sv["feed_names"][0]: tok,
                                 sv["feed_names"][1]: pos})
        assert in_place                 # all six buffers donated, none copied
        gaps.append(gap(np.asarray(o[-1])[1], want[t]))
    # position plen - 1 + j is entry j; the first TOPK positions keep all
    early = [g for j, g in enumerate(gaps) if plen - 1 + j < TOPK]
    assert all(g <= LIMIT for g in early), gaps
    assert np.median(gaps) <= LIMIT / 4, gaps
    counts = np.asarray(o[1 + n_state])
    # one live slot at position t: t + 1 latent rows a layer, of which the
    # indexer keeps 8; every column of three slots scored, 8 rows a slot
    # gathered
    t = len(seq) - 1
    assert list(counts[-4:]) == [LAYERS * 3 * CACHE_LEN, LAYERS * TOPK,
                                 LAYERS * (t + 1), LAYERS * 3 * TOPK]


def test_each_layers_attention_block_is_the_references(model, programs):
    """What `latent_gap` and the selection's overlap compare on the chip:
    the attention block of every layer over the SYSTEM's own stream,
    through the prefill program (the expanded path over a threshold mask)
    and through the step program (the absorbed path over gathered rows),
    against the reference's block over the same stream. Where both kept
    the same keys the two blocks agree to bfloat16's rounding; where the
    eighth and ninth scores nearly tie the system may keep the other key,
    in one row of ten at this size, never another number of keys."""
    cfg, w = model
    pprog, pv = build(cfg, glm.build_prefill, 32, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"], pv["fetch_vars"]
                        + pv["attn_in"] + pv["attn_out"] + pv["selected"],
                        scope=w, name="glm_prefill_32_layers")
    seq = np.random.default_rng(3).integers(1, 211, 44)
    outs = prefill.run({pv["feed_names"][0]: seq[None, :32],
                        pv["feed_names"][1]: np.asarray([[32]])},
                       return_numpy=False)
    n_state = 2 * LAYERS
    rest = [np.asarray(o)[0] for o in outs[1 + n_state:]]
    streams = [[r.astype(np.float32)] for r in rest[:LAYERS]]
    added = [[r.astype(np.float32)] for r in rest[LAYERS:2 * LAYERS]]
    kept = [[r > 0] for r in rest[2 * LAYERS:]]
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 1)
    cache.write_slot(0, *outs[1:1 + n_state])
    _, _, step, sv = programs
    for t in range(32, len(seq)):
        o, _ = cache.run(step, sv["cache_feed_names"],
                         {sv["feed_names"][0]: np.asarray([[seq[t]]]),
                          sv["feed_names"][1]: np.asarray([[t]])})
        rest = [np.asarray(x) for x in o[2 + n_state:]]
        for i in range(LAYERS):
            streams[i].append(rest[i].astype(np.float32))
            added[i].append(rest[LAYERS + i].astype(np.float32))
            cols = rest[2 * LAYERS + i][0]
            assert cols.max() <= t and (cols >= 0).sum() == TOPK
            kept[i].append(
                np.isin(np.arange(CACHE_LEN), cols[cols >= 0])[None])
    rows = np.arange(len(seq))
    flips = 0
    for i in range(LAYERS):
        stream = np.zeros((CACHE_LEN, 64), np.float32)
        stream[:len(seq)] = np.concatenate(streams[i], 0)
        want, want_kept = ref.attention_at(w, i, stream, rows, M)
        want, want_kept = np.asarray(want), np.asarray(want_kept)
        got = np.concatenate(added[i], 0)
        got_kept = np.zeros((len(seq), CACHE_LEN), bool)
        got_kept[:32, :32] = kept[i][0]
        got_kept[32:] = np.concatenate(kept[i][1:], 0)
        share, count_gap = ref.overlap(got_kept, want_kept)
        assert count_gap == 0 and share >= 1 - 1.0 / TOPK, (i, share)
        same = (got_kept == want_kept).all(-1)
        flips += int((~same).sum())
        for path, part in (("prefill", slice(0, 32)), ("step", slice(32, 44))):
            ok = same[part]
            assert ref.rms_gap(got[part][ok], want[part][ok]) < 0.02, (i, path)
    assert flips <= 0.1 * LAYERS * len(seq)


def test_a_step_goes_on_where_a_longer_prefill_would_be(model, programs):
    """The step's absorbed path against the prefill's expanded path on the
    same positions: a prompt of 32 through the prefill program, against its
    first 16 positions through the prefill program and the other 16 through
    steps. The first layer sees the same stream either way: it keeps the
    same keys, and what its attention adds agrees to bfloat16's rounding."""
    cfg, w = model
    pprog, pv = build(cfg, glm.build_prefill, 32, CACHE_LEN)
    whole = Predictor(pprog, pv["feed_names"],
                      [pv["attn_out"][0], pv["selected"][0]], scope=w,
                      name="glm_prefill_32_first")
    seq = np.random.default_rng(17).integers(1, 211, 32)
    want, want_kept = whole.run({pv["feed_names"][0]: seq[None],
                                 pv["feed_names"][1]: np.asarray([[32]])})
    prefill, pv16, step, sv = programs
    outs = prefill.run({pv16["feed_names"][0]: seq[None, :16],
                        pv16["feed_names"][1]: np.asarray([[16]])},
                       return_numpy=False)
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 1)
    cache.write_slot(0, *outs[1:-1])
    n_state = 2 * LAYERS
    for t in range(16, 32):
        o, _ = cache.run(step, sv["cache_feed_names"],
                         {sv["feed_names"][0]: np.asarray([[seq[t]]]),
                          sv["feed_names"][1]: np.asarray([[t]])})
        rest = o[2 + n_state:]
        cols = np.asarray(rest[2 * LAYERS])[0]
        assert set(cols) == set(np.flatnonzero(np.asarray(want_kept)[0, t]))
        assert ref.rms_gap(np.asarray(rest[LAYERS], np.float32),
                           np.asarray(want, np.float32)[0, t][None]) < 0.02


def test_through_the_engine_tokens_counters_and_reused_slots(model):
    """Served through DecodeEngine with fewer slots than requests: the
    served tokens lie near the reference's best at their positions, the
    step's counts arrive, nothing is copied."""
    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=[16, 32], name="glm-test",
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
        # a greedy token is the system's own best: where a kept key differs
        # its logits differ, but its first choice rarely lies far down the
        # reference's list
        assert np.median(gaps) <= 0.01 and np.mean(gaps > LIMIT) <= 0.15, gaps
        st = eng.stats()
        assert st["cache_copy_steps"] == 0 and st["step_errors"] == 0
        assert st["moe_assignments_total"] > st["moe_assignments_held"] > 0
        assert st["dsa_rows_scored"] > st["latent_rows_live"] > 0
        assert st["latent_rows_live"] > st["dsa_rows_selected"] > 0
        assert st["latent_rows_read"] >= st["dsa_rows_selected"]
        assert st["latent_rows_read"] == st["steps"] * LAYERS * 2 * TOPK
        assert st["state_bytes_rows"] == 2 * LAYERS * CACHE_LEN * ROW * 2
        assert st["state_bytes_fixed"] == st["state_bytes_ring"] == 0
    finally:
        eng.stop(drain=False, timeout=5)


def test_a_stopped_engine_holds_none_of_its_slots_state(model):
    """`stop()` frees the slots' buffers once the loop has ended: whoever
    still holds the engine (a handler thread, a lease) holds no device
    state, and a reference check finds the room."""
    import gc

    import jax

    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=[16], name="glm-stop",
                               adopt_params=True)
    stream = eng.submit(np.arange(1, 9), max_new=CACHE_LEN - 8)
    next(stream.tokens(timeout=120))
    shapes = {tuple(b.shape) for b in eng._cache.bufs}
    assert shapes == {(2, CACHE_LEN, LATENT), (2, CACHE_LEN, 8)}
    eng.stop(drain=False, timeout=30)
    assert eng._cache.bufs is None
    assert eng.stats()["state_bytes_rows"] == 2 * LAYERS * CACHE_LEN * ROW * 2
    gc.collect()
    assert not [a for a in jax.live_arrays() if tuple(a.shape) in shapes]


def test_the_four_chips_shares_add_up_to_the_uncut_layer():
    """The share test: one sparse layer of the system, told each of the
    four held ranges in turn (at the published sizes: sixteen ranges of
    sixteen), against the reference's layer over all 16 experts: the routed
    parts add, the shared expert counts once."""
    whole = dict(M, n_routed_experts=16, first_expert=0, num_hidden_layers=2)
    w = ref.make_weights(whole, 5)
    bw = {k: v.astype(jnp.float32) for k, v in ref.layer_weights(w, 1).items()}
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
        part = glm.GlmMoeDsaConfig.from_hf(
            dict(whole, n_routed_experts=4), router_experts=16,
            first_expert=first)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            x = fluid.data("x", shape=[9, 64], dtype="bfloat16")
            y = glm._feed_forward(x, part, 1, None, [], [])
            prog = fluid.default_main_program()
        scope = dict(w)
        for leaf in ("w1", "w3", "w2"):
            name = "glm1.moe.experts." + leaf
            scope[name] = w[name][first:first + 4]
        out = Predictor(prog, ["x"], [y], scope=scope).run({"x": h})[0]
        total = total + np.asarray(out, np.float32)
    got = total - 3 * shared
    # four bfloat16 outputs summed, each within 2**-8 of its own scale
    assert np.abs(got - want).max() <= 0.03 * np.abs(want).max()


def test_a_long_prompts_routed_layer_runs_in_calls_of_a_few_rows(
        model, monkeypatch):
    """A prompt longer than MOE_PROMPT_ROWS takes the routed layer in calls
    of that many tokens (their sorted buffers are sized by the tokens of a
    call): the same numbers as one call."""
    cfg, w = model
    ids = np.random.default_rng(4).integers(1, 211, (1, 32))
    feeds = {"glm_prefill_ids": ids, "glm_prefill_len": np.asarray([[29]])}

    def routed_parts(name):
        prog, pv = build(cfg, glm.build_prefill, 32, CACHE_LEN)
        ops = [op.type for op in prog.global_block().ops]
        return ops.count("held_experts_ffn"), Predictor(
            prog, pv["feed_names"], pv["moe_routed"] + [pv["logits"]],
            scope=w, name=name).run(feeds)

    calls, one = routed_parts("glm_prefill_32_one_call")
    monkeypatch.setattr(glm.blocks, "MOE_PROMPT_ROWS", 8)
    cut_calls, cut = routed_parts("glm_prefill_32_four_calls")
    assert (calls, cut_calls) == (LAYERS - 1, 4 * (LAYERS - 1))
    for a, b in zip(one, cut):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), atol=1e-6)


# -- what knows rows only as K and V of one width ----------------------------
@pytest.mark.parametrize("feature,kwargs", [
    ("prefix_pool", {"prefix_pool": object()}),
    ("session_tier", {"session_tier": object()}),
    ("kv_dtype", {"kv_dtype": "int8"}),
    ("draft", {"draft": object()}),
    ("role='decode'", {"role": "decode"}),
])
def test_what_knows_k_and_v_alone_refuses_latent_rows(model, feature, kwargs):
    cfg, w = model
    with pytest.raises(ValueError, match="not K and V of one width") as e:
        serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                             auto_start=False, **kwargs)
    assert feature in str(e.value)
    assert "lat 128 wide" in str(e.value) and "idx 8 wide" in str(e.value)


def test_the_wire_and_the_prefill_replica_refuse_latent_rows(model):
    from paddle_tpu.serving.disagg.prefill import PrefillEngine

    cfg, w = model
    with pytest.raises(ValueError, match="not K and V of one width"):
        PrefillEngine(cfg, w, cache_len=CACHE_LEN, auto_start=False)
    with pytest.raises(ValueError, match="not K and V of one width"):
        kv_slot_bytes(cfg, CACHE_LEN, "int8")
    eng = serving.DecodeEngine(cfg, w, slots=1, cache_len=CACHE_LEN,
                               prompt_buckets=[8], auto_start=False,
                               adopt_params=True)
    with pytest.raises(ValueError, match="not K and V of one width"):
        eng.submit_prefilled(object())


def test_k_and_v_rows_of_one_width_are_still_taken():
    """The models whose rows are K and V say nothing and are taken as
    before: the refusal is of the new case alone."""
    from paddle_tpu.models import gpt
    from paddle_tpu.models.decode_utils import require_rows_only

    for kv_dtype in ("fp32", "int8"):
        model = gpt.gpt_tiny().decode_model(16, kv_dtype)
        assert model.rows_are_kv
        require_rows_only(model, "anything")


def test_what_the_config_names_and_the_file_does_not_build_is_refused():
    for key, value in (("scoring_func", "softmax"), ("n_group", 8),
                       ("topk_method", "greedy"), ("norm_topk_prob", False),
                       ("rope_interleave", False),
                       ("indexer_rope_interleave", False),
                       ("num_nextn_predict_layers", 1),
                       ("attention_bias", True)):
        with pytest.raises(ValueError, match=key):
            glm.GlmMoeDsaConfig.from_hf(dict(M, **{key: value}), 16, 4)
    with pytest.raises(ValueError, match="rope_type"):
        glm.GlmMoeDsaConfig.from_hf(dict(M, rope_parameters={
            "rope_theta": 1e6, "rope_type": "yarn"}), 16, 4)
