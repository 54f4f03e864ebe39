"""The Kimi-VL decoder and vision tower (models/kimi_vl.py), and what they
brought to the ops (`mla_attention` without a selection, `bicubic_table`,
`rotary_2d`, `tower_attention`) and to the engine (requests that carry
images: a tower unit a turn, media rows spliced into the fill), against the
plain reference (benchmark/reference/kimi_vl.py) at a tiny size on the CPU.

Tolerances: the system holds bfloat16 weights, cache and residual streams,
the reference float32 over the same bfloat16 weights; at this size a
position's logits differ by 0.02-0.04 of their standard deviation (LIMIT
0.2) and a block's rows by 0.6-1.3% of their root mean square (ROWS 0.03).
"""
import base64
import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.models import kimi_vl as kimi
from paddle_tpu.ops import vision_ops
from paddle_tpu.serving.decode import SlotCache
from paddle_tpu.serving.media import decode_images

from benchmark.reference import kimi_vl as ref

LIMIT, ROWS = 0.2, 0.03
CACHE_LEN, LAYERS, PATCH, MEDIA = 64, 3, 2, 210
V = dict(num_hidden_layers=2, hidden_size=32, num_attention_heads=2,
         intermediate_size=48, patch_size=PATCH, init_pos_emb_height=8,
         init_pos_emb_width=8, merge_kernel_size=[2, 2], in_token_limit=64)
M = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=None,
         kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
         v_head_dim=16, intermediate_size=96, moe_intermediate_size=32,
         n_shared_experts=2, n_routed_experts=8, num_experts_per_tok=3,
         vocab_size=211, num_hidden_layers=LAYERS, first_k_dense_replace=1,
         rms_norm_eps=1e-5, routed_scaling_factor=2.446, rope_theta=800000,
         rope_scaling=None, scoring_func="sigmoid", topk_method="noaux_tc",
         n_group=1, topk_group=1, norm_topk_prob=True, vision_config=V,
         media_placeholder_token_id=MEDIA, initializer_range=0.08)
RNG = np.random.default_rng(17)


def gap(got, want):
    return float(np.abs(got - want).max() / want.std())


def f32(x):
    return np.asarray(x).astype(np.float32)


@pytest.fixture(scope="module")
def model():
    return kimi.KimiVlConfig.from_hf(M), ref.make_weights(M, 2147483659)


@pytest.fixture()
def chunks_of_8():
    was, kimi.CHUNK_ROWS = kimi.CHUNK_ROWS, 8
    yield 8
    kimi.CHUNK_ROWS = was


def build(cfg, fn, *args):
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = fn(cfg, *args)
        return fluid.default_main_program(), v


def image(h, w, seed=0):
    return np.random.default_rng(seed).integers(
        0, 256, (PATCH * h, PATCH * w, 3), dtype=np.uint8)


def tower_rows(cfg, w, pixels, extra=(), pick=None):
    """One image through the system's tower program of its bucket; `extra`
    names further fetches (of a per-block list, block `pick`'s)."""
    h, wd = pixels.shape[0] // PATCH, pixels.shape[1] // PATCH
    enc = cfg.decode_model(CACHE_LEN).encoder
    b = enc.bucket_for(h * wd)
    prog, tv = build(cfg, kimi.build_tower, b)
    pred = Predictor(prog, tv["feed_names"],
                     tv["fetch_vars"] + [
                         tv[k] if pick is None else tv[k][pick]
                         for k in extra], scope=w,
                     name="tower_%d" % b)
    fed = np.zeros((1, b, 3 * PATCH * PATCH), np.uint8)
    fed[0, :h * wd] = enc.patchify(pixels)
    out = pred.run([fed, np.asarray([[h, wd]], np.int64)])
    return [np.asarray(o) for o in out]


def a_request(grids=((4, 6), (2, 2)), plen=29, seed=5):
    """-> (prompt with the placeholder runs written in, images)."""
    rng = np.random.default_rng(seed)
    images = [image(h, w, seed + k) for k, (h, w) in enumerate(grids)]
    prompt = rng.integers(1, MEDIA - 1, plen)
    at = 3
    for h, w in grids:
        prompt[at:at + h * w // 4] = MEDIA
        at += h * w // 4 + 5
    return prompt, images


def media_of(cfg, w, images):
    """The system's media buffer and the reference's rows."""
    buf = np.zeros((cfg.media_rows, cfg.hidden), np.float32)
    at = 0
    for px in images:
        rows, = tower_rows(cfg, w, px)
        buf[at:at + len(rows)] = f32(rows)
        at += px.shape[0] * px.shape[1] // (4 * PATCH * PATCH)
    return (jnp.asarray(buf, jnp.bfloat16),
            jnp.concatenate(ref.tower_rows(w, images, M)[0], 0))


def index_of(prompt, rows):
    marked = prompt == MEDIA
    at = np.full((1, rows), -1, np.int32)
    at[0, :len(prompt)] = np.where(marked, np.cumsum(marked) - 1, -1)
    return at


# -- shapes and the table's resize -------------------------------------------
def test_a_checkpoint_holds_what_the_reference_makes(model):
    cfg, w = model
    shapes = kimi.param_shapes(cfg)
    assert set(shapes) == set(w)
    for name, (shape, dtype) in shapes.items():
        assert tuple(w[name].shape) == tuple(shape), name
        assert str(w[name].dtype) == dtype, name


def test_bicubic_weights_are_torchs_by_hand():
    """8 samples to 4: source 2 o + 0.5, offset 0.5 from the second tap,
    cubic convolution with a = -0.75: taps (-3/32, 19/32, 19/32, -3/32);
    the first row's tap off the edge falls on sample 0."""
    want = np.zeros((4, 8))
    for o in range(4):
        for k, wk in zip(range(2 * o - 1, 2 * o + 3),
                         (-3 / 32, 19 / 32, 19 / 32, -3 / 32)):
            want[o, min(max(k, 0), 7)] += wk
    assert want[0, 0] == 0.5
    np.testing.assert_allclose(ref.bicubic_matrix(4, 8), want, atol=1e-12)
    got = np.asarray(vision_ops.bicubic_matrix(8, jnp.int32(4), 8))[:4]
    np.testing.assert_allclose(got, want, atol=1e-6)
    # the same size: every tap's weight is 0 or 1; Keys' kernel is another
    np.testing.assert_allclose(ref.bicubic_matrix(8, 8), np.eye(8))
    np.testing.assert_allclose(
        np.asarray(vision_ops.bicubic_matrix(8, jnp.int32(8), 8)), np.eye(8))
    keys = np.asarray(jax.image.resize(jnp.eye(8), (4, 8), "bicubic"))
    assert np.abs(keys - want).max() > 0.02
    # 6 from 8: source (o + 0.5) 4 / 3 - 0.5, the taps written out
    o = 2
    src = 2.5 * 4 / 3 - 0.5
    t = src - np.floor(src)
    a = -0.75
    taps = [((a * (t + 1) - 5 * a) * (t + 1) + 8 * a) * (t + 1) - 4 * a,
            ((a + 2) * t - (a + 3)) * t * t + 1,
            ((a + 2) * (1 - t) - (a + 3)) * (1 - t) ** 2 + 1,
            ((a * (2 - t) - 5 * a) * (2 - t) + 8 * a) * (2 - t) - 4 * a]
    row = np.asarray(vision_ops.bicubic_matrix(8, jnp.int32(6), 8))[o]
    np.testing.assert_allclose(row[1:5], taps, atol=1e-6)
    assert abs(sum(taps) - 1) < 1e-12


@pytest.mark.parametrize("grid", [(8, 8), (4, 6), (6, 4)],
                         ids=["table_as_it_is", "resized", "not_square"])
def test_tower_rows_are_the_references(model, grid):
    cfg, w = model
    px = image(*grid, seed=grid[0])
    rows, table = tower_rows(cfg, w, px, extra=("table",))
    want, want_table = ref.tower_rows(w, [px], M)
    n = grid[0] * grid[1]
    assert ref.rms_gap(f32(rows)[:n // 4], want[0]) <= ROWS
    h, wd = grid
    merged = np.asarray(want_table[0]).reshape(h // 2, 2, wd // 2, 2, -1)
    merged = merged.transpose(0, 2, 1, 3, 4).reshape(n, -1)
    np.testing.assert_allclose(table[:n], merged, atol=1e-6)
    assert not table[n:].any()
    if grid == (8, 8):
        np.testing.assert_array_equal(
            merged, f32(w["kimi.vit.pos"]).reshape(4, 2, 4, 2, -1).transpose(
                0, 2, 1, 3, 4).reshape(64, -1))


def test_each_tower_fault_moves_the_rows(model):
    """What the controls plant in the reference is seen by `tower_gap`, and
    the reference's tower stays ONE compiled program over every grid and
    every fault of the table or the rotary term (they are arguments): a
    benchmark run's check writes no program a grid to the XLA cache."""
    _, w = model
    px = [image(4, 6, 3)]
    want = ref.tower_rows(w, px, M)[0][0]
    for fault in ("resize_keys", "table_cropped", "no_rope_2d",
                  "rope_2d_swapped"):
        got = ref.tower_rows(w, px, dict(M, fault=fault))[0][0]
        assert ref.rms_gap(got, want) > 1e-3, fault
    ref.tower_rows(w, [image(8, 8), image(2, 6)], M)
    fn = ref._tower_fn(ref._freeze({"vision_config": M["vision_config"]}),
                       "float32")
    assert fn._cache_size() == 1


def test_a_blocks_attention_over_the_systems_own_stream(model):
    """`tower_attn_gap`: what a tower block's attention adds, the system's
    program against the reference's block over the SAME stream; without the
    2-D rotary term, or with rows and columns swapped, the reference's block
    lies far off (in the projector's rows the same faults are a few
    roundings)."""
    cfg, w = model
    h, wd = 6, 4
    px = image(h, wd, 11)
    _, z, a = tower_rows(cfg, w, px, extra=("attn_in", "attn_out"),
                         pick=1)

    def row_major(x):
        x = f32(x).reshape(-1, x.shape[-1])[:h * wd]
        return x.reshape(h // 2, wd // 2, 2, 2, -1).transpose(
            0, 2, 1, 3, 4).reshape(h * wd, -1)

    z, a = row_major(z), row_major(a)
    sound = ref.rms_gap(a, ref.tower_attention_at(w, 1, z, h, wd, M))
    assert sound <= 0.01, sound
    for fault in ("no_rope_2d", "rope_2d_swapped"):
        off = ref.rms_gap(a, ref.tower_attention_at(
            w, 1, z, h, wd, dict(M, fault=fault)))
        assert off > 4 * sound, (fault, off, sound)


# -- the decoder -------------------------------------------------------------
@pytest.fixture(scope="module")
def programs(model):
    cfg, w = model
    pprog, pv = build(cfg, kimi.build_prefill, 32, CACHE_LEN)
    sprog, sv = build(cfg, kimi.build_step, CACHE_LEN)
    prefill = Predictor(
        pprog, pv["feed_names"], pv["fetch_vars"] + [pv["logits"]]
        + pv["attn_in"][:1] + pv["moe_routed"], scope=w, name="kimi_p32")
    step = Predictor(sprog, sv["feed_names"],
                     sv["fetch_vars"] + [sv["logits"]], scope=w,
                     name="kimi_step", donate_feeds=sv["cache_feed_names"])
    return prefill, pv, step, sv


def test_prefill_then_steps_through_the_slot_cache(model, programs):
    """A prompt with two images of unlike grids, then 12 teacher-forced
    steps through the latent rows the prefill handed over: every position's
    logits against the reference's full forward pass; the spliced rows are
    the projector's, the others the embedding's; with every expert held
    the routed layer gives the whole sum."""
    cfg, w = model
    prefill, pv, step, sv = programs
    prompt, images = a_request()
    plen = len(prompt)
    buf, media = media_of(cfg, w, images)
    seq = np.concatenate([prompt, RNG.integers(1, MEDIA - 1, 12)])
    want = np.asarray(ref.logits_at(w, seq, np.arange(len(seq)), M,
                                    media=media))
    ids = np.full((1, 32), 7, np.int64)
    ids[0, :plen] = prompt
    outs = prefill.run([ids, np.asarray([[plen]], np.int64), buf,
                        index_of(prompt, 32)], return_numpy=False)
    state = outs[1:1 + LAYERS]
    assert all(not np.asarray(s)[0, plen:].any() for s in state)
    x0 = f32(outs[2 + LAYERS])[0, :plen]
    marked = prompt == MEDIA
    assert ref.rms_gap(x0[marked], media) <= ROWS
    np.testing.assert_array_equal(
        x0[~marked], f32(w["kimi.emb"])[prompt[~marked]])
    _, _, parts = ref.forward(w, prompt, M, media=media)
    for got, part in zip(outs[3 + LAYERS:], parts):
        # per position: where two experts' scores nearly tie bfloat16 may
        # choose the other one; an expert left out would move every row
        errors = ref.routed_errors(f32(got)[:plen], part)
        assert len(errors) == plen and np.median(errors) <= ROWS
    cache = SlotCache(jax, cfg.decode_model(CACHE_LEN), 3)
    cache.write_slot(1, *state)
    tok, pos = np.zeros((3, 1), np.int64), np.zeros((3, 1), np.int64)
    gaps = [gap(np.asarray(outs[1 + LAYERS])[0], want[plen - 1])]
    for t in range(plen, len(seq)):
        tok[1, 0], pos[1, 0] = seq[t], t
        o, in_place = cache.run(step, sv["cache_feed_names"],
                                {sv["feed_names"][0]: tok,
                                 sv["feed_names"][1]: pos})
        assert in_place
        gaps.append(gap(np.asarray(o[-1])[1], want[t]))
    assert max(gaps) <= LIMIT, gaps
    counts = np.asarray(o[1 + LAYERS])
    # per sparse layer: 3 assignments of the one live token, all on held
    # experts; then the live rows and the rows gone over
    assert list(counts[[0, 4]]) == [3, 3]
    assert list(counts[-2:]) == [LAYERS * len(seq), LAYERS * 3 * CACHE_LEN]


def test_a_chunked_fill_hands_over_the_one_shot_fills_rows(model, programs):
    """Four chunks of 8 against one pass of 32: the token, the logits and
    every latent row bit for bit (no float32 state is carried)."""
    cfg, w = model
    prefill, pv, _, _ = programs
    prompt, images = a_request()
    plen = len(prompt)
    buf, _ = media_of(cfg, w, images)
    ids = np.zeros((1, 32), np.int64)
    ids[0, :plen] = prompt
    index = index_of(prompt, 32)
    one = prefill.run([ids, np.asarray([[plen]], np.int64), buf, index],
                      return_numpy=False)
    cprog, cv = build(cfg, kimi.build_chunk, 8, CACHE_LEN)
    chunk = Predictor(cprog, cv["feed_names"],
                      cv["fetch_vars"] + [cv["logits"]], scope=w,
                      name="kimi_c8", donate_feeds=cv["cache_feed_names"])
    state = [jnp.zeros((1, CACHE_LEN, cfg.latent_width), jnp.bfloat16)
             for _ in range(LAYERS)]
    for at in range(0, plen, 8):
        n = min(8, plen - at)
        out = chunk.run([ids[:, at:at + 8], np.asarray([[n]], np.int64),
                         np.asarray([[at]], np.int64), buf,
                         index[:, at:at + 8]] + state, return_numpy=False)
        state = list(out[1:1 + LAYERS])
    assert int(np.asarray(out[0])[0, 0]) == int(np.asarray(one[0])[0, 0])
    np.testing.assert_array_equal(np.asarray(out[-1]),
                                  np.asarray(one[1 + LAYERS]))
    for got, want in zip(state, one[1:1 + LAYERS]):
        np.testing.assert_array_equal(f32(got), f32(want))


# -- requests that carry images ----------------------------------------------
def body_of(prompt, images, max_new=6):
    return {"prompt": [int(t) for t in prompt], "max_new_tokens": max_new,
            "images": [{"grid": [px.shape[0] // PATCH, px.shape[1] // PATCH],
                        "pixels": base64.b64encode(px.tobytes()).decode()}
                       for px in images]}


def post(server, body):
    req = urllib.request.Request(
        server.url + "/v1/models/kimi:generate",
        data=json.dumps(dict(body, stream=False)).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=60) as resp:
            return resp.status, json.loads(resp.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read())


def expected_tokens(w, prompt, media, n):
    seq, at = np.zeros(CACHE_LEN, np.int64), len(prompt)
    seq[:at] = prompt                 # one length: one compiled reference
    for _ in range(n):
        logits = np.asarray(ref.logits_at(w, seq, [at - 1], M,
                                          media=media))[0]
        seq[at] = int(logits.argmax())
        at += 1
    return [int(t) for t in seq[len(prompt):at]]


def test_the_engine_serves_requests_with_images(model, chunks_of_8):
    """Over HTTP: a request with two images beside a live text-only stream
    (its fill goes a tower unit, then a chunk, a turn), one alone (its
    bucket's program as one unit), a text-only one; the tokens are the
    reference's greedy ones; the counters and spans say what ran; what is
    wrong with a request is refused in words."""
    from paddle_tpu import observability as obs

    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=3, cache_len=CACHE_LEN,
                               prompt_buckets=(16, 32), name="kimi")
    assert {r["program"] for r in eng.warmup()} == {
        "step", "prefill", "chunk", "tower"}
    registry = serving.ModelRegistry()
    registry.publish("kimi", eng)
    server = serving.ServingServer(registry).start()
    try:
        prompt, images = a_request()
        _, media = media_of(cfg, w, images)
        want = expected_tokens(w, prompt, media, 6)
        # alone: no slot is live, the bucket's program is the one unit
        code, doc = post(server, body_of(prompt, images))
        assert (code, doc["tokens"]) == (200, want), doc
        s = eng.stats()
        assert (s["tower_runs"], s["media_images"], s["media_patches"],
                s["media_rows"], s["tower_pad_patches"]) == (
                    2, 2, 28, 7, 32 + 16 - 28)
        assert s["fill_rows"] == len(prompt) and not s.get("fill_chunks")
        # beside a live stream: chunks of 8
        # a fixed text, not the module's generator: 40 greedy tokens of a
        # bfloat16 program against the float32 reference hold for a text
        # without a near tie, whatever tests ran before this one
        text = np.asarray([191, 90, 133, 128, 19, 154, 11, 4, 122])
        live = eng.submit(text, max_new=40)
        next(live.tokens())
        code, doc = post(server, body_of(prompt, images))
        assert (code, doc["tokens"]) == (200, want), doc
        assert live.result(30) == expected_tokens(w, text, None, 40)
        s = eng.stats()
        assert s["fill_chunks"] == 4 and s["tower_runs"] == 4
        assert s["chunked_fills"] == 1
        assert 100.0 * s["media_rows"] / s["fill_rows"] == pytest.approx(
            100.0 * 14 / (2 * len(prompt) + 9))
        assert len(obs.spans("serving.decode.tower")) >= 4
        assert len(obs.spans("serving.decode.media_prepare")) >= 2
        # refused, in words
        for change, words in (
                (lambda b: b["prompt"].__setitem__(0, MEDIA), "marks 8"),
                (lambda b: b["images"].pop(), "give 6 rows"),
                (lambda b: b["images"][0].update(grid=[3, 8]),
                 "a whole number of the 2 x 2"),
                # 32 patches fit a bucket, but the table has 8 columns and
                # the program would give columns 8-15 the last one's row
                (lambda b: b["images"][0].update(grid=[2, 16]),
                 "at most 8 x 8 (the sides of the encoder's position table)"),
                (lambda b: b["images"][0].update(grid=[16, 2]),
                 "position table"),
                (lambda b: b["images"][0].update(grid=[8, 10]), "at most"),
                (lambda b: b["images"][0].update(grid=[4, 4]), "bytes"),
                (lambda b: b["images"][0].update(pixels="!!"), "base64"),
                (lambda b: b.update(images=[b["images"][0]] * 5),
                 "at most 4")):
            bad = body_of(prompt, images)
            change(bad)
            code, doc = post(server, bad)
            assert code == 400 and words in doc["error"], (words, doc)
        code, doc = post(server, {"prompt": [5, MEDIA, 6],
                                  "max_new_tokens": 2})
        assert code == 400 and "marks 1" in doc["error"]
        # the engine's own callers are told the same (no handler before it)
        wide = np.zeros((32, 3 * PATCH * PATCH), np.uint8)
        with pytest.raises(ValueError, match="image 0: a grid of 2 x 16 "
                           "patches: a side holds at most 8 x 8"):
            eng.submit([5] + [MEDIA] * 8 + [6], max_new=2,
                       media=[(wide, (2, 16))])
        assert eng.stats()["live_slots"] == 0
    finally:
        server.stop(close_registry=False)
        eng.stop(drain=False, timeout=10)


def test_a_cancelled_fill_frees_its_rows(model, chunks_of_8):
    cfg, w = model
    eng = serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                               prompt_buckets=(16, 32), name="kimi_c",
                               auto_start=False)
    try:
        prompt, images = a_request()
        enc = eng.media_encoder
        media = decode_images(body_of(prompt, images)["images"], enc)
        h = eng.submit(prompt, max_new=4, media=media)
        eng._admit()
        assert eng._fill is not None and eng._fill.media is not None
        eng._fill_unit()
        assert eng._fill.image == 1 and eng._fill.media_at == 6
        h.cancel()
        eng._sweep_cancelled()
        assert eng._fill is None and h.finish_reason == "cancelled"
        # images for what keeps a prompt as ids alone, or without an encoder
        with pytest.raises(ValueError, match="at most 4"):
            eng.submit(prompt, media=media * 3)
    finally:
        eng.stop(drain=False, timeout=10)


@pytest.mark.parametrize("feature", [dict(kv_dtype="int8"),
                                     dict(role="decode")])
def test_row_wise_features_refuse_the_latent_cache(model, feature):
    """What cuts, quantises or ships rows knows K and V of one width."""
    cfg, w = model
    with pytest.raises(ValueError, match="not K and V of one width"):
        serving.DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                             prompt_buckets=(16,), name="kimi_r",
                             auto_start=False, **feature)


def test_images_are_refused_where_a_prompt_is_ids_alone(model):
    """A model without an encoder takes no images; a model with one refuses
    them together with what keeps or re-runs a prompt as token ids."""
    import types

    from paddle_tpu.serving.decode import DecodeEngine

    cfg, _ = model
    prompt, images = a_request()
    enc = cfg.decode_model(CACHE_LEN).encoder
    media = decode_images(body_of(prompt, images)["images"], enc)

    def engine(encoder, **features):
        it = types.SimpleNamespace(
            name="stub", _model=types.SimpleNamespace(encoder=encoder),
            _prefix_pool=None, _session_tier=None, _draft=None)
        it.__dict__.update(features)
        return it

    with pytest.raises(ValueError, match="takes no images"):
        DecodeEngine._check_media(engine(None), prompt, media, None)
    for feature, words in ((dict(_prefix_pool=object()), "a prefix pool"),
                           (dict(_draft=object()), "a draft")):
        with pytest.raises(ValueError, match=words):
            DecodeEngine._check_media(engine(enc, **feature), prompt, media,
                                      None)
    with pytest.raises(ValueError, match="a session"):
        DecodeEngine._check_media(engine(enc, _session_tier=object()),
                                  prompt, media, "s1")
    got, index = DecodeEngine._check_media(engine(enc), prompt, media, None)
    assert len(got) == 2 and (index >= 0).sum() == 7
    assert list(index[index >= 0]) == list(range(7))
    with pytest.raises(ValueError, match="must be a list"):
        decode_images({"grid": [2, 2]}, enc)
