"""The hybrid decode step at its published widths, compiled for a described
TPU v5e (no chip attached, nothing runs): the chip's compiler accepts it,
every declared state buffer is aliased to its fetch (updated in place, no
second copy of 2.86 GB), and the arguments fit one chip's memory.

The topology is described inside a fixture: only the worker that runs this
file loads the TPU's library. A compile that passes is not a chip run."""
import json
import os
import re

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here: skip
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)
    return SingleDeviceSharding(topo.devices[0])


def test_the_step_at_published_widths_aliases_all_its_state(one_chip):
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import nemotron_h as nh

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "nemotron_3_super_120b_a12b.json")))
    cfg = nh.NemotronHConfig.from_hf(
        doc, router_experts=doc["reduced_from"]["n_routed_experts"])
    slots, cache_len = doc["serving"]["slots"], doc["serving"]["cache_len"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = nh.build_step(cfg, cache_len)
        prog = fluid.default_main_program()
    step = build_step_fn(prog, v["feed_names"],
                         [x.name for x in v["fetch_vars"]], is_test=True,
                         platform="tpu")
    names = v["cache_feed_names"]
    decl = cfg.decode_model(cache_len).state

    def fwd(state, feeds, donated):
        feeds = dict(feeds)
        feeds.update(zip(names, donated))
        return step(state, feeds, jax.random.PRNGKey(0))[0]

    params = {k: sds(s, d) for k, (s, d) in nh.param_shapes(cfg).items()}
    feeds = {"nh_step_tok": sds((slots, 1), "int32"),
             "nh_step_pos": sds((slots, 1), "int32")}
    donated = tuple(sds((slots,) + tuple(e.shape), e.dtype) for e in decl)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(fwd, donate_argnums=(2,)).lower(
            params, feeds, donated).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    mem = compiled.memory_analysis()
    state_bytes = slots * sum(e.nbytes for e in decl)
    assert mem.alias_size_in_bytes >= state_bytes       # all 12, in place
    assert mem.temp_size_in_bytes < 256e6               # nothing state-sized
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 11e9 < held < 14e9                           # of the chip's 16 GB
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in params.values())
    assert round(weights / 1e9, 1) == 9.3


def test_lagunas_step_and_longest_prefill_at_published_widths(one_chip):
    """Laguna-S-2.1's cut (3,002 M parameters, 64 slots x 8,704) for the
    described chip. The step, compiled whole (its assertions need the
    compiled object): every declared buffer, rows and rings, aliased to its
    fetch (4.97 GB updated in place), no cache-sized scratch, arguments +
    scratch under the chip's 16 GB. The 8,192 prefill, lowered (the form
    Solar-Open2's take below): the grouped kernels (three a sparse layer),
    the flash kernel of the two full layers and the band's kernel of the
    three window layers are in it, no (T, T) array is and no block of
    float32 scores (a window layer's stay in the kernel), no scatter adds
    rows into the prompt's `[8192, 3072]` (the gated experts' sum back is a
    read a token). The chip's compiler is handed its Pallas calls alone, at
    the operand shapes the lowered text names. The whole prefill compiled
    takes 44 s here; read by hand (PR 46) it holds 1.78 GB of scratch (the
    gated experts' buffers at the static bound of 81,920 rows) beside
    6.00 GB of weights and 4.97 of state, and the cell
    `laguna_code_context_decode` runs it on the chip in every window."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import laguna
    from paddle_tpu.ops import hybrid_ops

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "laguna_s_2_1.json")))
    cfg = laguna.LagunaConfig.from_hf(
        doc, router_experts=doc["reduced_from"]["num_experts"],
        first_expert=doc["share"]["first_expert"])
    slots, cache_len = doc["serving"]["slots"], doc["serving"]["cache_len"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {k: sds(s, d) for k, (s, d) in laguna.param_shapes(cfg).items()}
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in params.values())
    assert round(weights / 1e9, 2) == 6.00
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = laguna.build_step(cfg, cache_len)
        prog = fluid.default_main_program()
    step = build_step_fn(prog, v["feed_names"],
                         [x.name for x in v["fetch_vars"]], is_test=True,
                         platform="tpu")
    names = v["cache_feed_names"]
    decl = cfg.decode_model(cache_len).state

    def fwd(state, feeds, donated):
        feeds = dict(feeds)
        feeds.update(zip(names, donated))
        return step(state, feeds, jax.random.PRNGKey(0))[0]

    feeds = {"lg_step_tok": sds((slots, 1), "int32"),
             "lg_step_pos": sds((slots, 1), "int32")}
    donated = tuple(sds((slots,) + tuple(e.shape), e.dtype) for e in decl)
    compiled = _no_cache_compile(jax.jit(fwd, donate_argnums=(2,)).lower(
        params, feeds, donated))
    mem = compiled.memory_analysis()
    state_bytes = slots * sum(e.nbytes for e in decl)
    assert round(state_bytes / 1e9, 2) == 4.97
    assert mem.alias_size_in_bytes >= state_bytes       # all 10, in place
    assert mem.temp_size_in_bytes < 512e6               # nothing cache-sized
    assert 10.5e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 12e9
    assert compiled.as_text().count("tpu_custom_call") == 4 * 3

    bucket = 8192
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = laguna.build_prefill(cfg, bucket, cache_len)
        prog = fluid.default_main_program()
    prefill = build_step_fn(prog, v["feed_names"],
                            [x.name for x in v["fetch_vars"]], is_test=True,
                            platform="tpu")
    lowered = jax.jit(
        lambda state, feeds: prefill(state, feeds, jax.random.PRNGKey(0))[0]
    ).lower(params, {"lg_prefill_ids": sds((1, bucket), "int32"),
                     "lg_prefill_len": sds((1, 1), "int32")})
    text = lowered.as_text()
    # jax lowers a function once and calls it: the calls are counted
    assert len(re.findall(r"call @gmm\w*\(", text)) == 4 * 3
    assert text.count('kernel_name = "flash_fwd"') == 2
    assert text.count('kernel_name = "window_attn_fwd"') == 3
    hlo = lowered.as_text(dialect="hlo")       # the same program, HLO's names
    assert re.search(r"bf16\[1,8192,3072\]", hlo)
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", hlo)
    assert not re.search(r"f32\[(?:\d+,)*512,1024\]", hlo)
    # the gated experts' sum back into the tokens reads, a token at a time
    assert " scatter(" in hlo and not _row_scatters(hlo, "8192,3072")

    kv = sds((1, bucket, cfg.kv_heads * cfg.head_dim), "bfloat16")
    kinds = sorted(set(zip(cfg.layer_types, cfg.heads_per_layer)))
    assert len(kinds) == 2
    for kind, heads in kinds:
        q = sds((1, bucket, heads * cfg.head_dim), "bfloat16")
        assert "tensor<1x%dx%dxbf16>" % q.shape[1:] in text
        if kind == laguna.WINDOW:
            kernel = "window_attn_fwd"
            alone = jax.jit(lambda q, k, v: hybrid_ops._window_gqa(
                q, k, v, heads, cfg.kv_heads, cfg.window))
        else:
            kernel = "flash_fwd"
            alone = jax.jit(lambda q, k, v: hybrid_ops._flash_gqa(
                q, k, v, heads, cfg.kv_heads))
        assert kernel in _no_cache_compile(alone.lower(q, kv, kv)).as_text()
    _grouped_products_alone(sds, text, bucket * cfg.top_k, cfg)


def test_glm5s_step_and_longest_prefill_at_published_widths(one_chip):
    """GLM-5's cut (3,910 M parameters, 16 slots x 17,408) for the
    described chip. The step, compiled whole (its assertions need the
    compiled object): both declared buffers of every layer, the latent rows
    (640 wide) and the indexer's rows, aliased to their fetches (2.14 GB
    updated in place), no cache-sized scratch (a 576-wide cache is laid out
    with positions minor and copied into row order and back every step:
    3.2 GB of scratch), arguments + scratch under the chip's 16 GB, the
    three grouped kernels of each of the four sparse layers in it. The
    16,384 prefill, lowered (the form Solar-Open2's take below): those
    kernels once a call of the routed layer (four calls of 4,096 tokens a
    layer), the kept-keys kernel once a layer, no (heads, T, T) array (the
    selection is int8, a block of queries a row). The chip's compiler is
    handed its Pallas calls alone, at the operand shapes the lowered text
    names. The whole prefill compiled takes 61 s here; read by hand (PR 46)
    it holds 3.79 GB of scratch (the keys and values of 64 heads expanded,
    1.1 GB, q, the int8 selection, 0.27 GB, the routed layer's sorted
    buffers) beside 7.82 GB of weights and 2.14 of state, and the cell
    `glm5_agent_context_decode` runs it on the chip in every window."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from benchmark import costs_glm5
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import glm_moe_dsa as glm
    from paddle_tpu.ops import hybrid_ops
    from paddle_tpu.ops.pallas_attention import kept_keys_attention

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "glm_5.json")))
    doc["model"] = {k: v for k, v in doc.items()
                    if not isinstance(v, (dict, list))}
    cfg = glm.GlmMoeDsaConfig.from_hf(
        costs_glm5.sizes(doc),
        router_experts=doc["reduced_from"]["n_routed_experts"],
        first_expert=doc["share"]["first_expert"])
    slots, cache_len = doc["serving"]["slots"], doc["serving"]["cache_len"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {k: sds(s, d) for k, (s, d) in glm.param_shapes(cfg).items()}
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in params.values())
    assert round(weights / 1e9, 2) == 7.82
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = glm.build_step(cfg, cache_len)
        prog = fluid.default_main_program()
    step = build_step_fn(prog, v["feed_names"],
                         [x.name for x in v["fetch_vars"]], is_test=True,
                         platform="tpu")
    names = v["cache_feed_names"]
    decl = cfg.decode_model(cache_len).state

    def fwd(state, feeds, donated):
        feeds = dict(feeds)
        feeds.update(zip(names, donated))
        return step(state, feeds, jax.random.PRNGKey(0))[0]

    feeds = {"glm_step_tok": sds((slots, 1), "int32"),
             "glm_step_pos": sds((slots, 1), "int32")}
    donated = tuple(sds((slots,) + tuple(e.shape), e.dtype) for e in decl)
    compiled = _no_cache_compile(jax.jit(fwd, donate_argnums=(2,)).lower(
        params, feeds, donated))
    mem = compiled.memory_analysis()
    state_bytes = slots * sum(e.nbytes for e in decl)
    assert round(state_bytes / 1e9, 2) == 2.14
    assert mem.alias_size_in_bytes >= state_bytes       # all 10, in place
    assert mem.temp_size_in_bytes < 256e6               # nothing cache-sized
    assert 9.5e9 < mem.argument_size_in_bytes + mem.temp_size_in_bytes < 11e9
    assert compiled.as_text().count("tpu_custom_call") == 4 * 3

    bucket = 16384
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = glm.build_prefill(cfg, bucket, cache_len)
        prog = fluid.default_main_program()
    prefill = build_step_fn(prog, v["feed_names"],
                            [x.name for x in v["fetch_vars"]], is_test=True,
                            platform="tpu")
    lowered = jax.jit(
        lambda state, feeds: prefill(state, feeds, jax.random.PRNGKey(0))[0]
    ).lower(params, {"glm_prefill_ids": sds((1, bucket), "int32"),
                     "glm_prefill_len": sds((1, 1), "int32")})
    text = lowered.as_text()
    # jax lowers a function once and calls it: the calls are counted
    assert len(re.findall(r"call @gmm\w*\(", text)) == 4 * 4 * 3
    assert text.count('kernel_name = "kept_keys_attn_fwd"') == 5
    hlo = lowered.as_text(dialect="hlo")       # the same program, HLO's names
    # (T, heads x 256) is 16,384 square at these sizes, so q is: no array
    # has heads or index heads before such a square, and the selection is
    # held as the tiers' blocks of int8
    assert re.search(r"bf16\[1,16384,16384\]", hlo)
    assert not re.search(r"\[(?:\d+,)*(?:64|32),16384,16384\]", hlo)
    assert re.search(r"s8\[32,1,128,16384\]", hlo)

    wide = cfg.heads * (cfg.nope_dim + cfg.rope_dim)
    q = sds((1, bucket, wide), "bfloat16")
    v = sds((1, bucket, cfg.heads * cfg.v_dim), "bfloat16")
    keep = sds((1, bucket, bucket), "int8")
    assert "tensor<1x%dx%dxi8>" % keep.shape[1:] in text
    compiled = _no_cache_compile(jax.jit(
        lambda q, k, v, keep: kept_keys_attention(
            q, k, v, keep, cfg.heads, (cfg.nope_dim + cfg.rope_dim) ** -0.5,
            block=hybrid_ops.KEPT_BLOCK)).lower(q, q, v, keep))
    assert "kept_keys_attn_fwd" in compiled.as_text()
    _grouped_products_alone(sds, text, 4096 * cfg.top_k, cfg)


def test_solar_open2s_programs_lower_at_published_widths(one_chip):
    """Solar-Open2's cut (3,308 M parameters, 64 slots x 16,896) lowered
    for the described chip, both programs: the step with all fourteen state
    buffers donated (5.26 GB), the 16,384 prefill with the delta-rule
    layers' mixers in four runs of 4,096 positions from a carried state.
    Lowered, not compiled: the chip's compiler takes 40 s over the step and
    100 s over the prefill here (read once, by hand: 0.33 GB and 3.1 GB of
    scratch beside 11.9 GB of arrays; PERF.md). What is compiled is the one
    call the default limits refuse: the flash kernel over 16,384 positions
    of 128 holds K and V of a head whole in fast memory, 16 MiB
    double-buffered, and asks for the room."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from benchmark import costs_solar
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import solar_open2 as solar
    from paddle_tpu.ops.pallas_attention import flash_attention

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "solar_open2_250b.json")))
    doc["model"] = {k: v for k, v in doc.items()
                    if not isinstance(v, (dict, list))}
    m = costs_solar.sizes(doc)
    cfg = solar.SolarOpen2Config.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])
    slots, cache_len = doc["serving"]["slots"], doc["serving"]["cache_len"]

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {k: sds(s, d) for k, (s, d) in solar.param_shapes(cfg).items()}
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in params.values())
    assert round(weights / 1e9, 2) == 6.62
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = solar.build_step(cfg, cache_len)
        prog = fluid.default_main_program()
    step = build_step_fn(prog, v["feed_names"],
                         [x.name for x in v["fetch_vars"]], is_test=True,
                         platform="tpu")
    names = v["cache_feed_names"]
    decl = cfg.decode_model(cache_len).state
    assert round(slots * sum(e.nbytes for e in decl) / 1e9, 2) == 5.26

    def fwd(state, feeds, donated):
        feeds = dict(feeds)
        feeds.update(zip(names, donated))
        return step(state, feeds, jax.random.PRNGKey(0))[0]

    feeds = {"so_step_tok": sds((slots, 1), "int32"),
             "so_step_pos": sds((slots, 1), "int32")}
    donated = tuple(sds((slots,) + tuple(e.shape), e.dtype) for e in decl)
    text = jax.jit(fwd, donate_argnums=(2,)).lower(
        params, feeds, donated).as_text()
    assert "tpu_custom_call" in text                    # the experts' gmm
    assert text.count("tf.aliasing_output") == len(decl) == 14

    bucket = 16384
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = solar.build_prefill(cfg, bucket, cache_len)
        prog = fluid.default_main_program()
    prefill = build_step_fn(prog, v["feed_names"],
                            [x.name for x in v["fetch_vars"]], is_test=True,
                            platform="tpu")
    text = jax.jit(
        lambda state, feeds: prefill(state, feeds, jax.random.PRNGKey(0))[0]
    ).lower(params, {"so_prefill_ids": sds((1, bucket), "int32"),
                     "so_prefill_len": sds((1, 1), "int32")}).as_text()
    # the softmax layer through the flash kernel; no float32 array of the
    # delta-rule layers is the prompt's length, only a run's
    assert "tpu_custom_call" in text and "flash_fwd" in text
    assert "tensor<1x16384x8192xf32>" not in text
    assert "tensor<1x4096x8192xf32>" in text

    qkv = sds((64, bucket, 128), "bfloat16")
    compiled = _no_cache_compile(jax.jit(
        lambda q, k, v: flash_attention(q[None], k[None], v[None],
                                        causal=True, block_q=512,
                                        block_k=512)).lower(qkv, qkv, qkv))
    assert "flash_fwd" in compiled.as_text()


def test_solar_open2s_chunk_program_at_published_widths(one_chip):
    """The program a long prompt fills its slot by, a chunk a turn
    (`solar.build_chunk`: 4,096 positions from the carried state), lowered
    for the described chip: all fourteen carried arrays donated and aliased
    (82 MB in, the same buffers out), the softmax layer through the flash
    forward kernel with a query offset, no float32 array of a delta-rule
    layer longer than the chunk. Compiled by hand once (30 s here: 1.67 GB
    of scratch where the 16,384 program holds 3.08; PERF.md); what is
    compiled here is the kernel's call alone at the chunk's shapes: 4,096
    queries of 64 heads against the 16,896 rows of the cache, K and V of a
    head whole in fast memory, the offset a scalar beside the seed."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from benchmark import costs_solar
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import solar_open2 as solar
    from paddle_tpu.ops.pallas_attention import flash_attention

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "solar_open2_250b.json")))
    doc["model"] = {k: v for k, v in doc.items()
                    if not isinstance(v, (dict, list))}
    m = costs_solar.sizes(doc)
    cfg = solar.SolarOpen2Config.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])
    cache_len = doc["serving"]["cache_len"]
    model = cfg.decode_model(cache_len)
    rows = model.chunk_rows
    assert rows == 4096 and model.build_chunk is solar.build_chunk

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {k: sds(s, d) for k, (s, d) in solar.param_shapes(cfg).items()}
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = solar.build_chunk(cfg, rows, cache_len)
        prog = fluid.default_main_program()
    chunk = build_step_fn(prog, v["feed_names"],
                          [x.name for x in v["fetch_vars"]], is_test=True,
                          platform="tpu")
    names = v["cache_feed_names"]
    assert v["feed_names"][3:] == names and len(names) == len(model.state)

    def fwd(state, feeds, donated):
        feeds = dict(feeds)
        feeds.update(zip(names, donated))
        return chunk(state, feeds, jax.random.PRNGKey(0))[0]

    feeds = {"so_chunk_ids": sds((1, rows), "int32"),
             "so_chunk_len": sds((1, 1), "int32"),
             "so_chunk_start": sds((1, 1), "int32")}
    donated = tuple(sds((1,) + tuple(e.shape), e.dtype) for e in model.state)
    text = jax.jit(fwd, donate_argnums=(2,)).lower(
        params, feeds, donated).as_text()
    assert text.count("tf.aliasing_output") == len(model.state) == 14
    assert "flash_fwd_offset" in text
    assert "tensor<1x4096x8192xf32>" in text
    assert "tensor<1x16896x8192xf32>" not in text

    q = sds((64, rows, 128), "bfloat16")
    kv = sds((64, cache_len, 128), "bfloat16")
    compiled = _no_cache_compile(jax.jit(
        lambda q, k, v, at: flash_attention(
            q[None], k[None], v[None], causal=True, block_q=512,
            block_k=512, q_offset=at)).lower(q, kv, kv, sds((), "int32")))
    assert "flash_fwd_offset" in compiled.as_text()


def _kimi_vl_at_published_widths():
    from benchmark import costs_kimi_vl
    from paddle_tpu.models import kimi_vl as kimi

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "kimi_vl_a3b_instruct.json")))
    doc["model"] = {k: v for k, v in doc.items()
                    if not isinstance(v, (dict, list))}
    return (kimi.KimiVlConfig.from_hf(costs_kimi_vl.sizes(doc)),
            doc["serving"]["slots"], doc["serving"]["cache_len"])


def test_kimi_vls_step_chunk_and_tower_at_published_widths(one_chip):
    """Kimi-VL-A3B's cut (3,541 M parameters: five decoder layers whole
    with all 64 experts, the whole vocabulary, the tower and the projector;
    48 slots x 17,408) for the described chip. The step, compiled whole: the
    latent rows of every layer (640 wide) aliased to their fetches (5.35 GB
    updated in place), no cache-sized scratch (the absorbed path reads the
    rows where they lie: no gather, no copy), the three grouped kernels of
    each of the four sparse layers in it, the decoder's weights 6.19 GB of
    the 7.08. The chunk and the largest tower, lowered: five carried arrays
    donated and aliased, the flash forward kernel with a query offset once a
    layer (queries and keys padded 192 -> 256, the values at 128), the
    tower's flash kernel once a block, no (.., T, T) array in either. The
    chip's compiler is handed the two attention calls alone at their
    operand shapes. Compiled whole by hand (PR 47): the step 11 s, 0.02 GB
    of scratch; the chunk 26 s, 0.53 GB; the 16,384 prefill 34 s, 0.89 GB;
    the 4,096 tower 26 s, 0.69 GB."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.lowering import build_step_fn
    from paddle_tpu.models import kimi_vl as kimi
    from paddle_tpu.ops import LOWERINGS
    from paddle_tpu.ops.pallas_attention import flash_attention

    cfg, slots, cache_len = _kimi_vl_at_published_widths()
    model = cfg.decode_model(cache_len)
    decl, rows = model.state, model.chunk_rows
    assert rows == 4096 and model.encoder.buckets == (1024, 2048, 4096)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    params = {k: sds(s, d) for k, (s, d) in kimi.param_shapes(cfg).items()}
    weights = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                  for s in params.values())
    assert round(weights / 1e9, 2) == 7.08

    def lowered(build, args, feeds, donated):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            v = build(cfg, *args)
            prog = fluid.default_main_program()
        step = build_step_fn(prog, v["feed_names"],
                             [x.name for x in v["fetch_vars"]], is_test=True,
                             platform="tpu")
        names = v.get("cache_feed_names", [])
        used = {n: params[n] for n in params
                if prog.global_block().has_var(n)}

        def fwd(state, feeds, donated):
            feeds = dict(feeds)
            feeds.update(zip(names, donated))
            return step(state, feeds, jax.random.PRNGKey(0))[0]

        return used, jax.jit(fwd, donate_argnums=(2,)).lower(
            used, feeds, donated)

    used, step = lowered(
        kimi.build_step, (cache_len,),
        {"kimi_step_tok": sds((slots, 1), "int32"),
         "kimi_step_pos": sds((slots, 1), "int32")},
        tuple(sds((slots,) + tuple(e.shape), e.dtype) for e in decl))
    compiled = _no_cache_compile(step)
    mem = compiled.memory_analysis()
    state_bytes = slots * sum(e.nbytes for e in decl)
    assert round(state_bytes / 1e9, 2) == 5.35
    assert mem.alias_size_in_bytes >= state_bytes          # all five, in place
    assert mem.temp_size_in_bytes < 256e6
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 4 * 3
    for scope in ("kimi.mla", "kimi.mlp", "kimi.experts.route",
                  "kimi.experts.experts", "kimi.experts.shared", "kimi.head"):
        assert scope + "/" in hlo, scope          # in the HLO's op_names
    held = sum(int(np.prod(s.shape)) * s.dtype.itemsize for s in used.values())
    assert round(held / 1e9, 2) == 6.19

    _, chunk = lowered(
        kimi.build_chunk, (rows, cache_len),
        {"kimi_chunk_ids": sds((1, rows), "int32"),
         "kimi_chunk_len": sds((1, 1), "int32"),
         "kimi_chunk_start": sds((1, 1), "int32"),
         "kimi_chunk_media": sds((cfg.media_rows, cfg.hidden), "bfloat16"),
         "kimi_chunk_media_index": sds((1, rows), "int32")},
        tuple(sds((1,) + tuple(e.shape), e.dtype) for e in decl))
    assert "kimi.splice/" in chunk.as_text(debug_info=True)
    text = chunk.as_text()
    assert text.count("tf.aliasing_output") == len(decl) == 5
    assert text.count("flash_fwd_offset") >= 5
    assert not re.search(r"tensor<(\d+x)*4096x(4096|%d)xf32>" % cache_len,
                         text)
    heads = cfg.heads
    q = sds((heads, rows, 256), "bfloat16")
    k = sds((heads, cache_len, 256), "bfloat16")
    v = sds((heads, cache_len, 128), "bfloat16")
    compiled = _no_cache_compile(jax.jit(
        lambda q, k, v, at: flash_attention(
            q[None], k[None], v[None], causal=True, sm_scale=192 ** -0.5,
            block_q=512, block_k=512, q_offset=at)).lower(
        q, k, v, sds((), "int32")))
    assert "flash_fwd_offset" in compiled.as_text()

    patches = model.encoder.buckets[-1]
    _, tower = lowered(
        kimi.build_tower, (patches,),
        {"kimi_tower_patches": sds((1, patches, 588), "uint8"),
         "kimi_tower_grid": sds((1, 2), "int32")}, ())
    named = tower.as_text(debug_info=True)
    for scope in ("kimi.tower.patch", "kimi.tower.attn", "kimi.tower.mlp",
                  "kimi.tower.merge", "kimi.project"):
        assert scope + "/" in named, scope
    text = tower.as_text()
    assert text.count("flash_fwd") >= cfg.vision.layers
    assert not re.search(r"tensor<(\d+x)*4096x4096x(f32|bf16)>", text)

    class Ctx:
        platform, mesh_axes = "tpu", None

    wide = sds((1, patches, cfg.vision.hidden), "bfloat16")
    compiled = _no_cache_compile(jax.jit(
        lambda ins: LOWERINGS["tower_attention"](
            Ctx(), ins, dict(heads=cfg.vision.heads))).lower(
        {"Q": [wide], "K": [wide], "V": [wide],
         "Grid": [sds((1, 2), "int32")]}))
    assert "flash_fwd" in compiled.as_text()


def _lowered_digest(build, cfg, args, batch, one_chip):
    """sha256 (16 hex digits) of a program's lowered text for the described
    chip, outside file paths: the checkout's root replaced, a Pallas call's
    payload (the serialised kernel, source locations and all) blanked."""
    import hashlib

    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.lowering import build_step_fn

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        v = build(cfg, *args)
        prog = fluid.default_main_program()
    fn = build_step_fn(prog, v["feed_names"],
                       [x.name for x in v["fetch_vars"]], is_test=True,
                       platform="tpu")
    block = prog.global_block()

    def sds(var):
        shape = [batch if (d is None or d < 0) else d for d in var.shape]
        dtype = {"int64": "int32"}.get(str(var.dtype), str(var.dtype))
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    state = {n: sds(x) for n, x in block.vars.items()
             if getattr(x, "persistable", False)}
    feeds = {n: sds(block.vars[n]) for n in v["feed_names"]}
    text = jax.jit(lambda s, f, k: fn(s, f, k)[0]).lower(
        state, feeds, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                           sharding=one_chip)).as_text()
    text = re.sub(r'backend_config = "[^"]*"', 'backend_config = "<kernel>"',
                  text.replace(ROOT, "<root>"))
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def test_text_only_models_programs_lower_as_before_images(one_chip):
    """What ISSUE 47 (requests that carry images; `mla_attention` without a
    selection; the flash forward kernel's second width; the latent row's
    make moved into `decoder_blocks.py`) must NOT move: the step and fill
    programs of GPT, GLM-5 and Solar-Open2 at their cells' sizes, lowered for
    the described chip, are line for line what the parent commit lowers
    (digests taken from `git archive 90eb6e1` by the same code, PR 47). A
    later PR that changes one of these programs on purpose takes its new
    digest the same way and says so."""
    from benchmark import costs_glm5, costs_solar
    from paddle_tpu.models import glm_moe_dsa as glm
    from paddle_tpu.models import gpt
    from paddle_tpu.models import solar_open2 as solar

    def doc_of(name):
        doc = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                          name + ".json")))
        doc["model"] = {k: v for k, v in doc.items()
                        if not isinstance(v, (dict, list))}
        return doc

    got = {}
    doc = doc_of("glm_5")
    m = costs_glm5.sizes(doc)
    cfg = glm.GlmMoeDsaConfig.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])
    sv = doc["serving"]
    got["glm5.step"] = _lowered_digest(glm.build_step, cfg,
                                       (sv["cache_len"],), sv["slots"],
                                       one_chip)
    got["glm5.prefill_8192"] = _lowered_digest(
        glm.build_prefill, cfg, (8192, sv["cache_len"]), 1, one_chip)
    doc = doc_of("solar_open2_250b")
    m = costs_solar.sizes(doc)
    cfg = solar.SolarOpen2Config.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])
    sv = doc["serving"]
    got["solar.step"] = _lowered_digest(solar.build_step, cfg,
                                        (sv["cache_len"],), sv["slots"],
                                        one_chip)
    got["solar.chunk_4096"] = _lowered_digest(
        solar.build_chunk, cfg, (4096, sv["cache_len"]), 1, one_chip)
    doc = doc_of("openai_gpt")
    m, sv = doc["model"], doc["serving"]
    cfg = gpt.GPTConfig(vocab=m["vocab_size"], hidden=m["n_embd"],
                        num_layers=m["n_layer"], heads=m["n_head"],
                        ffn=m["n_inner"], max_len=m["n_positions"],
                        dropout=0.0)
    model = cfg.decode_model(sv["cache_len"], sv.get("kv_dtype", "fp32"))
    got["gpt.step"] = _lowered_digest(model.build_step, cfg,
                                      (sv["cache_len"],), sv["slots"],
                                      one_chip)
    got["gpt.prefill_128"] = _lowered_digest(
        model.build_prefill, cfg, (128, sv["cache_len"]), 1, one_chip)
    assert got == PINNED_AT_PR_47, got


PINNED_AT_PR_47 = {
    "glm5.step": "9414e6d20bf22bc1", "glm5.prefill_8192": "27c7f756360afdd1",
    "solar.step": "2efa83de882fceb5", "solar.chunk_4096": "375f95600c0187ed",
    "gpt.step": "65d582511be71238", "gpt.prefill_128": "7f3da6e0d33c6c86"}


def test_the_delta_rules_scan_kernel_at_solar_open2s_widths(one_chip):
    """The Pallas kernel of the delta rule's chunked scan
    (`ops/pallas_kda.py` `kda_scan_fwd`) compiled for the described chip at
    the shapes of one run of a Solar-Open2 layer: 4,096 positions of 64
    heads of 128 channels from a carried float32 state. The chip's compiler
    takes the lanes' rolls, the transposes and the float32 products at
    `highest` (interpret mode shows none of that), no float32 copy of the
    operands goes through HBM beside the call, and the op takes the kernel
    from what it sees."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import LOWERINGS

    heads, dim, rows = 64, 128, 4096

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    class Ctx:
        platform, mesh_axes = "tpu", None

    wide = sds((1, rows, heads * dim), "bfloat16")
    ins = {"Q": [wide], "K": [wide], "V": [wide],
           "G": [sds((1, rows, heads * dim), "float32")],
           "Beta": [sds((1, rows, heads), "float32")],
           "ALog": [sds((heads,), "float32")],
           "DtBias": [sds((heads * dim,), "float32")],
           "State": [sds((1, heads, dim, dim), "float32")],
           "Len": [sds((1, 1), "int32")]}
    compiled = _no_cache_compile(jax.jit(
        lambda ins: LOWERINGS["kda_scan"](
            Ctx(), ins, dict(heads=heads, head_dim=dim, beta_scale=2.0))
    ).lower(ins))
    assert "kda_scan_fwd" in compiled.as_text()
    mem = compiled.memory_analysis()
    # the raw beta transposed (1 MB) and the decays' sign, nothing a run long
    assert mem.temp_size_in_bytes < 8e6


def test_fused_vocabulary_head_at_berts_widths_holds_one_chunk(one_chip):
    """BERT-base's head and its gradient (256 x 128 rows, hidden 768,
    vocabulary 30,522, bfloat16 operands as under AMP), compiled for the
    described chip: the loop's scratch is chunks of 2,048 rows, and no
    array of rows x vocabulary exists (in bfloat16 alone it would be 2 GB).
    It lives in this file because only one test file may load the TPU's
    library (see the fixture)."""
    import re

    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import loss_ops

    rows, hidden, vocab = (256, 128), 768, 30522
    assert loss_ops.head_chunk_rows(rows[0] * rows[1], vocab) == 2048

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss_and_grads(x, w, lab):
        def mean_loss(x_, w_):
            loss, n_rows, trips = loss_ops.linear_softmax_ce(
                x_, w_, lab, -1, None)
            return jnp.mean(loss), (n_rows, trips)
        return jax.value_and_grad(mean_loss, (0, 1), has_aux=True)(x, w)

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        compiled = jax.jit(loss_and_grads).lower(
            sds(rows + (hidden,), "bfloat16"), sds((vocab, hidden), "bfloat16"),
            sds(rows, "int32")).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
    sizes = [int(np.prod([int(d) for d in m.group(1).split(",")]))
             for m in re.finditer(r"\b(?:bf16|f32|s32|pred)\[([0-9,]+)\]",
                                  compiled.as_text())]
    assert max(sizes) == 2048 * vocab         # one chunk of float32 logits
    assert compiled.memory_analysis().temp_size_in_bytes < 1.2e9


def _no_cache_compile(lowered):
    import jax

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        return lowered.compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


def _grouped_products_alone(sds, text, rows, cfg):
    """The routed layer's two grouped products (hidden -> expert width and
    back) over `rows` sorted rows, as the lowered `text` names them,
    compiled alone for the described chip."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import hybrid_ops

    held = cfg.held[1]
    for k, n in ((cfg.hidden, cfg.moe_ffn), (cfg.moe_ffn, cfg.hidden)):
        assert "tensor<%dx%dxbf16>" % (rows, k) in text
        compiled = _no_cache_compile(jax.jit(
            lambda xs, w, sizes: hybrid_ops.grouped_dot(
                xs, w, sizes, "tpu", jnp.bfloat16)).lower(
            sds((rows, k), "bfloat16"), sds((held, k, n), "bfloat16"),
            sds((held,), "int32")))
        assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("k,n", [(2048, 1792), (1792, 2048)])
def test_grouped_dot_takes_the_lfm2_expert_matrix_both_ways(one_chip, k, n):
    """LFM2-8B-A1B's expert matrices (2048 x 1792 and back; 3.67 M
    elements, more than one whole-matrix tile holds) at a training step's
    rows, forward and backward (`gmm`, `gmm` transposed, `tgmm`), compiled
    for the described chip: the chip's compiler takes the tiles
    `gmm_tiling` chooses, and a decode step's few rows too."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import hybrid_ops

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=one_chip)

    def loss_and_grads(xs, w, sizes):
        return jax.value_and_grad(lambda a, b: jnp.sum(hybrid_ops.grouped_dot(
            a, b, sizes, "tpu", jnp.bfloat16).astype(jnp.float32)),
            (0, 1))(xs, w)

    for rows in (4 * 4096 * 4, 64):
        compiled = _no_cache_compile(jax.jit(loss_and_grads).lower(
            sds((rows, k), "bfloat16"), sds((8, k, n), "bfloat16"),
            sds((8,), "int32")))
        assert compiled.as_text().count("tpu_custom_call") >= 3


def _static_bound_passes(text):
    """The `copy`, `gather` and `select` instructions of an optimised HLO
    text whose result is a `[65536, 2048]` or `[65536, 1792]` array:
    {"outside_loops": [lines], "in_loops": [opcodes]}, a loop being every
    computation reachable from a `while`'s body or condition."""
    comps, name = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.-]+) \(.*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif name is not None:
            comps[name].append(line)
    calls = {n: set(re.findall(
        r"(?:body|condition|calls|to_apply)=%?([\w.-]+)", "\n".join(ls)))
        for n, ls in comps.items()}
    todo = set(re.findall(r"(?:body|condition)=%?([\w.-]+)", text))
    inside = set()
    while todo:
        n = todo.pop()
        if n not in inside:
            inside.add(n)
            todo |= calls.get(n, set())
    big = re.compile(r"= \w+\[65536,(?:2048|1792)\]\S* (copy|gather|select)\(")
    out = {"outside_loops": [], "in_loops": []}
    for n, ls in comps.items():
        for line in ls:
            m = big.search(line)
            if m and n in inside:
                out["in_loops"].append(m.group(1))
            elif m:
                out["outside_loops"].append(line.strip()[:160])
    return out


def _row_scatters(text, shape):
    """The `scatter` instructions of an optimised HLO text whose result is
    a float32 array of `shape` ("rows,width")."""
    return [line.strip() for line in text.splitlines()
            if re.search(r"= f32\[%s\]\S* scatter\(" % shape, line)]


def test_the_lfm2_training_step_at_published_widths_fits_one_chip(one_chip):
    """The cell's step (LFM2-8B-A1B's cut: 507.8 M parameters, 4 x 4,096
    tokens, bf16 AMP, Adam) as `Executor.run` would build it, compiled for
    the described chip: the Pallas kernels are in it (grouped products
    forward and backward, the three flash kernels), no (T, T) score array
    is, the state is donated and updated in place, and arguments + scratch
    fit 16 GiB less what the runtime keeps. Between the sort and the
    un-sort of an expert layer nothing outside a loop over the held rows
    passes over the static bound of 65,536 sorted rows: the optimised
    program holds no `copy`, `gather` or `select` that makes a
    `[65536, 2048]` or `[65536, 1792]` array outside a `while` body (before
    the loops there were sixteen such gathers and sixteen selects), and a
    `copy` of one anywhere would be a loop that lost its buffer. The way
    back into the tokens, forward and backward, is a read a token: the one
    scatter left that adds rows into a `[16384, 2048]` array is the tied
    embedding's gradient (16,384 held rows of the vocabulary)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.contrib.mixed_precision import decorate
    from paddle_tpu.fluid.lowering import build_step_fn, persistable_names
    from paddle_tpu.models import lfm2

    doc = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "lfm2_8b_a1b.json")))
    mix = json.load(open(os.path.join(
        ROOT, "benchmark", "traffic", "pretrain_lm_s4096.json")))
    rows, seq = mix["rows_per_chip"], mix["seq_len"]
    opt = doc["optimizer"]
    cfg = lfm2.Lfm2Config.from_hf(
        doc, router_experts=doc["reduced_from"]["num_experts"],
        first_expert=doc["share"]["first_expert"],
        bias_update_rate=opt["expert_bias_update_rate"])
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        vs = lfm2.build_lfm2_pretrain(cfg, seq)
        adam = fluid.optimizer.Adam(
            learning_rate=opt["learning_rate"], beta1=opt["beta1"],
            beta2=opt["beta2"], epsilon=opt["epsilon"])
        decorate(adam, use_bf16=True).minimize(vs["loss"])
        prog = fluid.default_main_program()
    step = build_step_fn(prog, ["input_ids", "labels"],
                         [vs["loss"].name, vs["moe_counts"].name],
                         platform="tpu")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype),
                                    sharding=one_chip)

    block = prog.global_block()
    state = {n: sds(block.vars[n].shape, block.vars[n].dtype)
             for n in persistable_names(prog)}
    feeds = {"input_ids": sds((rows, seq), "int32"),
             "labels": sds((rows, seq), "int32")}
    compiled = _no_cache_compile(jax.jit(step, donate_argnums=(0,)).lower(
        state, feeds, jax.ShapeDtypeStruct((2,), jnp.uint32,
                                           sharding=one_chip)))
    text = compiled.as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkdv"):
        assert kernel in text, kernel
    # per expert layer 3 + 3 grouped products and 3 transposed ones
    assert text.count("tpu_custom_call") >= 4 * 9 + 3
    # under the names the trace's readers sum (`lfm2_gmm_roofline_pct`): a
    # kernel reached through `jax.vjp` would be called `jvp_jit_gmm__`
    named = re.findall(r"^\s*%(t?gmm)(?:\.\d+)? = .*tpu_custom_call", text,
                       re.M)
    assert (named.count("gmm"), named.count("tgmm")) == (4 * 6, 4 * 3)
    assert not re.search(r"\[4,32,4096,4096\]|\[128,4096,4096\]", text)
    passes = _static_bound_passes(text)
    assert not passes["outside_loops"], passes["outside_loops"][:4]
    assert not [op for op in passes["in_loops"] if op == "copy"]
    scatters = _row_scatters(text, "16384,2048")
    assert len(scatters) == 1 and "lookup_table" in scatters[0], scatters
    # four expert layers x (three loops forward + six backward: the two
    # products' rows are added over the held rows before the read back) +
    # the fused head's two (+ the compiler's own)
    assert len(re.findall(r" while\(", text)) >= 4 * 9 + 2
    mem = compiled.memory_analysis()
    state_bytes = sum(int(np.prod(s.shape)) * s.dtype.itemsize
                      for s in state.values())
    assert round(state_bytes / 1e9, 1) == 6.1      # weights + two moments
    assert mem.alias_size_in_bytes >= 0.99 * state_bytes
    # the balancing rule's score corrections are state the step writes
    assert sum(n.endswith(".moe.gate.bias") for n in state) == 4
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    # 15.80 GB; before the loops 16.06, read on the chip as 15.98
    assert 14e9 < held < 16.1e9, held
