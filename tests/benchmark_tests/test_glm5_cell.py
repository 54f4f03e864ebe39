"""The GLM-5 decoder's cell (`glm5_agent_context_decode`) at rehearsal size
on the CPU: its cost functions against the issue's arithmetic, the state the
configuration file promises, a plain and a traced line that carry whole
`attempted` >= 1, `correct` coming out false when the timed path is broken
six ways or computed in float8, and its readers on a run that has what they
read.

What the rehearsal reads (seeds 41, 51, 3000000043, 3000000053; 8 kept keys
of 12-47 predecessors, 16 index heads, prompts 12-32, 8-16 new tokens, 12
sampled requests). A query that keeps 8 keys and swaps one where the eighth
and ninth scores tie within bfloat16's rounding reads `select_overlap_miss`
0.125 and moves its own `latent_gap` row by a third, so at this size the
sound system reads `latent_gap` 0.10-0.22 and `select_overlap_miss` 0.125
(0.25 when one query of four hundred swaps two), with `select_count_off`
0: the rehearsal's limits are 0.4, 0.3 and 0.05. The faults: 7 kept keys
for 8 and every key kept both read `select_count_off` 1.0; a selection by
scores without the rotary term `select_overlap_miss` 0.5-0.75; the indexer's
row written one position on 0.5-0.9; the held range shifted by one expert
`routed_gap` 1.1-2.6 (sound 0.012-0.03), the routed scaling
factor dropped 0.6; float8 `routed_gap` 0.6-0.8, `latent_gap` 0.39-0.52 and
`select_overlap_miss` 0.375. The rehearsal's `routed_gap` limit is 0.25 (the
median over the sample's routed positions reads 0.013-0.021 sound at this
size; the median of ONE request's dozen positions read up to 0.46, which is
why the positions are pooled) and its `logit_gap_sigma` limit 6 (the widest
of a few dozen tokens over 64-wide products reads 0.3-2.7 sound). The
timed size's limits and the readings they were set from, sound and every
fault at 16,000 positions on the chip, are in the traffic file's `check`
group and PERF.md section 6.
"""
import numpy as np
import pytest

from conftest import ROOT

from benchmark import costs_glm5, harness

CELL = "glm5_agent_context_decode"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def measure(make_run, seed, trace=False, seconds=1.5, sample=8):
    run = make_run(CELL, seed=seed, seconds=seconds)
    run.trace = trace
    run.traffic["check"]["sample_requests"] = sample
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["failed"] == 0
    return run, line


def published():
    manifest = harness.load_manifest(ROOT)
    _, config, _ = harness.resolve_cell(manifest, CELL, root=ROOT)
    return costs_glm5.sizes(config), config


def test_the_manifest_names_the_configuration_the_cell_and_the_readers():
    """Membership, never equality or position: a later PR appends."""
    manifest = harness.load_manifest(ROOT)
    config = {c["name"]: c for c in manifest["configs"]}["glm_5"]
    assert config["file"] == "benchmark/configs/glm_5.json"
    assert set(config["reduced"]) == {
        "first_k_dense_replace", "n_routed_experts", "vocab_size",
        "num_nextn_predict_layers"}
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "glm_5", "agent_context_closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    reported = {m["name"] for m in harness.metrics_for(
        manifest, "end_to_end", CELL)}
    assert reported == {"itl_ms_p90", "setup_s"}
    per_layer = {m["name"]: m for m in harness.metrics_for(
        manifest, "per_layer", CELL)}
    for name in ("glm5_step_roofline_pct", "glm5_prefill_device_ms",
                 "glm5_prefill_roofline_pct", "glm5_gmm_roofline_pct",
                 "glm5_latent_rows_read_over_live", "glm5_select_keep_pct",
                 "glm5_tokens_per_held_expert", "glm5_load_max_over_mean",
                 "glm5_kept_attn_roofline_pct"):
        assert per_layer[name]["moves"] == "itl_ms_p90"
        assert CELL in per_layer[name]["workloads"]
        assert harness.load_part("metrics", name).read
    for name in ("completed_tokens_per_s", "slot_occupancy_pct",
                 "serve_step_device_ms", "serve_device_idle_pct",
                 "serve_peak_hbm_gb", "compile_s"):
        assert name in per_layer
    assert all(m["moves"] in ("itl_ms_p90", "setup_s")
               for m in per_layer.values())


def test_the_configuration_file_is_the_catalog_row_with_the_cut_written_down():
    m, config = published()
    assert config["source"].endswith("zai-org/GLM-5/blob/main/config.json")
    for key, value in (("hidden_size", 6144), ("num_attention_heads", 64),
                       ("q_lora_rank", 2048), ("kv_lora_rank", 512),
                       ("qk_nope_head_dim", 192), ("qk_rope_head_dim", 64),
                       ("v_head_dim", 256), ("index_n_heads", 32),
                       ("index_head_dim", 128), ("index_topk", 2048),
                       ("intermediate_size", 12288),
                       ("moe_intermediate_size", 2048),
                       ("num_experts_per_tok", 8),
                       ("routed_scaling_factor", 2.5), ("n_group", 1),
                       ("max_position_embeddings", 202752)):
        assert config[key] == value, key
    assert config["reduced_from"] == {
        "first_k_dense_replace": 3, "n_routed_experts": 256,
        "vocab_size": 154880, "num_nextn_predict_layers": 1}
    # the depth is the length of the file's own list; the published key
    # stays as published (a `reduced` key may not contain `hidden`)
    assert config["num_hidden_layers"] == 78
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert (m["num_hidden_layers"], m["first_k_dense_replace"],
            m["n_routed_experts"], m["vocab_size"],
            m["num_nextn_predict_layers"]) == (5, 1, 16, 19360, 0)
    assert m["router_experts"] == 256 and m["first_expert"] == 0
    assert config["rope_parameters"] == {"rope_theta": 1000000,
                                         "rope_type": "default"}
    for group in ("deployment", "share", "assumed", "precision", "serving",
                  "rehearsal"):
        assert config[group], group
    assert "16 chips" in config["deployment"]


def test_costs_follow_the_cut_written_in_the_issue():
    m, config = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    f = costs_glm5.ffn_params(m)
    assert round(costs_glm5.attention_params(m) / 1e6, 1) == 165.0
    assert round(costs_glm5.indexer_params(m) / 1e6, 1) == 9.4
    assert round(f["dense"] / 1e6, 1) == 226.5
    assert round(f["expert"] / 1e6, 2) == 37.75
    assert round((costs_glm5.attention_params(m)
                  + costs_glm5.indexer_params(m) + f["sparse_outside"]
                  + 16 * f["expert"]) / 1e6, 1) == 817.7
    assert round(costs_glm5.held_params(m) / 1e6) == 3910      # 7.82 GB
    # the shapes give the family's published count for the whole model
    whole = dict(m, num_hidden_layers=78, first_k_dense_replace=3,
                 n_routed_experts=256, vocab_size=154880)
    assert round(costs_glm5.held_params(whole) / 1e9, 1) == 743.9
    # 1,152 + 256 B a position a layer: what the selection reads and scores
    assert costs_glm5.row_bytes(m) == {"latent": 1152, "indexer": 256}
    # 16 slots at 12,000 live positions: 4.7 GB of weights (6.4 of 16 held
    # experts a layer), 49 MB of indexer rows scored and 38 MB of latent
    # rows kept a layer: 5.1 GB, 6.2 ms at the peak bandwidth
    slots, rows = 16, 16 * 12000
    assert 6.3 < costs_glm5.experts_touched(m, 16) < 6.5
    assert 4.6e9 < costs_glm5.weight_bytes(m, 16) < 4.8e9
    cache = costs_glm5.step_cache_bytes(m, slots, rows)
    assert cache == 5 * (rows * 256 + slots * 2048 * 1152)
    nbytes = costs_glm5.step_bytes(m, slots, rows)
    assert costs_glm5.step_min_seconds(m, slots, rows, peaks) == \
        pytest.approx(nbytes / 819e9)
    assert 6.0e-3 < nbytes / 819e9 < 6.5e-3
    # a sequence shorter than the selection keeps all it has
    assert costs_glm5.step_cache_bytes(m, 1, 1000) == 5 * 1000 * (256 + 1152)
    # a batch-1 prefill of 16,384: 16,384 x 2.67 GFLOP of products, 5 x 2.1
    # TFLOP of attention over the kept keys (not the causal square's 8.8),
    # 5 x 1.1 of indexer scores: 59 TFLOP, 0.30 s at the peak
    flops = costs_glm5.prefill_flops(m, 16384)
    per_token = costs_glm5.flops_per_token(m, 0, kept=0) \
        - 2 * 6144 * 19360
    assert 2.6e9 < per_token < 2.75e9
    kept = 2048 * 2049 / 2 + (16384 - 2048) * 2048
    assert flops == pytest.approx(
        16384 * per_token + 2 * 6144 * 19360
        + 2 * 5 * (kept * 64 * 512 + 16384 * 16385 / 2 * 32 * 128))
    assert 58e12 < flops < 61e12
    assert costs_glm5.prefill_min_seconds(m, 16384, peaks) == \
        pytest.approx(flops / 197e12)
    # one layer's attention over the kept pairs of 16,384: 31.5 M pairs x 64
    # heads x (256 + 256) x 2 = 2.06 TFLOP, 10.5 ms at the peak; a prompt of
    # 1,000 keeps its causal half and is bound by its bytes
    assert costs_glm5.kept_pairs(m, 16384) == kept
    assert costs_glm5.kept_pairs(m, 1000) == 1000 * 1001 / 2
    assert costs_glm5.kept_attention_min_seconds(m, 16384, peaks) == \
        pytest.approx(2 * kept * 64 * 512 / 197e12)
    assert 10e-3 < costs_glm5.kept_attention_min_seconds(m, 16384, peaks) \
        < 11e-3
    assert costs_glm5.kept_attention_min_seconds(m, 100, peaks) == \
        pytest.approx(100 * (64 * 512 + 576) * 2 / 819e9)
    # one sparse layer's three products with every slot live: 6.4 experts'
    # matrices, 0.48 GB, 0.59 ms
    one = costs_glm5.grouped_products_min_seconds(m, 16, peaks)
    assert 0.45e9 / 819e9 < one < 0.5e9 / 819e9
    assert costs_glm5.grouped_products_min_seconds(
        m, 16, peaks, touched=3) < 0.5 * one


def test_the_held_state_is_what_the_configuration_file_says():
    """The declaration the engine allocates from, summed at the published
    sizes: 16 slots x 17,408 positions x 5 layers of a latent row (its 576
    values in the 640 the chip's tiles give them) and an indexer's row."""
    from benchmark.systems import glm5_decode_server as server
    from paddle_tpu.models import glm_moe_dsa as glm

    m, config = published()
    cfg = server.model_config(server.reference_sizes(config))
    sv = config["serving"]
    model = cfg.decode_model(sv["cache_len"])
    assert [(e.kind, e.shape) for e in model.state] == [
        ("rows", (17408, 640)), ("rows", (17408, 128))] * 5
    held = sv["slots"] * model.slot_bytes("rows")
    assert held == 16 * 5 * 17408 * (640 + 128) * 2            # 2.14 GB
    least = costs_glm5.state_bytes(m, sv["slots"], sv["cache_len"])
    assert least == {"latent": 16 * 5 * 17408 * 1152,
                     "indexer": 16 * 5 * 17408 * 256}           # 1.96 GB
    params = sum(int(np.prod(s)) for s, _ in glm.param_shapes(cfg).values())
    assert params == costs_glm5.held_params(m)
    # every request of the mix fits its slot, and most of a prompt lies
    # beyond what a query keeps
    mix = harness.load_json(
        ROOT + "/benchmark/traffic/agent_context_closed.json")
    assert mix["prompt_tokens"]["min"] >= 2 * m["index_topk"]
    assert (mix["prompt_tokens"]["max"] + mix["max_new_tokens"]["max"] - 1
            <= sv["cache_len"])
    assert mix["clients"] == 1.5 * sv["slots"]
    assert max(mix["prompt_buckets"]) == mix["prompt_tokens"]["max"]
    assert mix["client_timeout_s"] == sv["request_timeout_s"]


@pytest.mark.parametrize("seed,trace", [(41, False), (3000000043, True)])
def test_the_sound_system_is_correct_and_its_readers_read(make_run, seed,
                                                          trace):
    """A plain and a traced window (0.6 of the seconds): both lines carry
    whole `attempted` >= 1, as the driver's check wants them."""
    run, line = measure(make_run, seed, trace=trace)
    assert line["correct"] is True, line["compared"]
    assert isinstance(line["attempted"], int) and line["attempted"] >= 1
    assert isinstance(line["failed"], int)
    assert set(line["compared"]) == {
        "logit_gap_sigma", "routed_gap", "latent_gap", "select_overlap_miss",
        "select_count_off", "tokens_short_of_sample"}
    assert line["compared"]["select_count_off"]["value"] == 0
    c = run.obs["counters"]
    assert c["moe_assignments_total"] > c["moe_assignments_held"] > 0
    assert c["dsa_rows_scored"] > c["latent_rows_live"] > \
        c["dsa_rows_selected"] > 0
    assert c["cache_copy_steps"] == 0

    def read(name):
        return harness.load_part("metrics", name).read(run)

    # 4 of 16 experts held, top-3: a live slot lands 0.75 assignments on the
    # 4 held experts under even routing, 4 live slots 0.75 on each
    assert 0.1 < read("glm5_tokens_per_held_expert") < 2.0
    assert 1.0 <= read("glm5_load_max_over_mean") <= 4.0
    # 8 rows a slot are gathered, live or not; a live sequence holds 13-47
    assert 0.1 < read("glm5_latent_rows_read_over_live") < 1.0
    assert 15.0 < read("glm5_select_keep_pct") < 70.0
    # a traced run keeps the trace of one fill of the longest bucket, made
    # before the window; a program's line is the device's: none on the CPU
    assert ("glm5_fill" in run.obs) == trace
    if trace:
        assert run.obs["glm5_fill"]["plen"] == 32
        assert run.obs["glm5_fill"]["trace"]["modules"] == {}
    assert read("glm5_prefill_device_ms") is None
    assert read("glm5_prefill_roofline_pct") is None
    assert read("glm5_kept_attn_roofline_pct") is None
    gauges = run.obs["gauges"][-1]
    assert gauges["state_bytes_rows_latent"] == 4 * 5 * 64 * 128 * 2
    assert gauges["state_bytes_rows_indexer"] == 4 * 5 * 64 * 8 * 2
    assert gauges["state_bytes_rows"] == (gauges["state_bytes_rows_latent"]
                                          + gauges["state_bytes_rows_indexer"])
    if trace:
        assert set(line["metrics"]) >= {
            "glm5_latent_rows_read_over_live", "glm5_select_keep_pct",
            "glm5_tokens_per_held_expert", "glm5_load_max_over_mean",
            "slot_occupancy_pct", "completed_tokens_per_s"}
    else:
        assert set(line["metrics"]) == {"itl_ms_p90", "setup_s"}


# Faults planted in the timed path. Each takes `patch(owner, name, value)`
# (pytest's `monkeypatch.setattr`, or plain `setattr` for a run on the chip)
# so the same faults are read here at rehearsal size and there at the timed
# size.

def _config_from(change):
    from paddle_tpu.models import glm_moe_dsa as glm

    real = glm.GlmMoeDsaConfig.from_hf.__func__
    return classmethod(
        lambda cls, m, router_experts=None, first_expert=0:
        real(cls, *change(m, router_experts, first_expert)))


def selection_of_one_key_fewer(patch):
    """The indexer keeps index_topk - 1 positions."""
    from paddle_tpu.models import glm_moe_dsa as glm

    patch(glm.GlmMoeDsaConfig, "from_hf", _config_from(
        lambda m, r, f: (dict(m, index_topk=m["index_topk"] - 1), r, f)))


def selection_dropped(patch):
    """Attention over all keys: the indexer keeps every position."""
    from paddle_tpu.models import glm_moe_dsa as glm

    patch(glm.GlmMoeDsaConfig, "from_hf", _config_from(
        lambda m, r, f: (dict(m, index_topk=1 << 20), r, f)))


def selection_before_the_rotary_term(patch):
    """The indexer scores its queries and keys unturned."""
    from paddle_tpu.models import glm_moe_dsa as glm

    real = glm._turned

    def turned(x, lead, count, width, cfg, first, pos=None):
        if width == cfg.index_dim:
            return x
        return real(x, lead, count, width, cfg, first, pos)

    patch(glm, "_turned", turned)


def indexer_row_written_one_position_on(patch):
    """The step writes the indexer's cache row at pos + 1."""
    from paddle_tpu.models import glm_moe_dsa as glm

    real = glm.update_cache

    def update(cache, new, pos=None, per_row=False):
        if "idx_" in cache.name:
            pos = glm.layers.scale(pos, scale=1.0, bias=1.0)
        return real(cache, new, pos=pos, per_row=per_row)

    patch(glm, "update_cache", update)


def held_range_shifted_by_one(patch):
    """The layer believes it holds experts 1..held where it holds 0..held-1."""
    from paddle_tpu.models import glm_moe_dsa as glm

    patch(glm.GlmMoeDsaConfig, "from_hf", _config_from(
        lambda m, r, f: (m, r, f + 1)))


def routed_scaling_factor_dropped(patch):
    """The chosen experts' weights are left unscaled."""
    from paddle_tpu.models import glm_moe_dsa as glm

    patch(glm.GlmMoeDsaConfig, "from_hf", _config_from(
        lambda m, r, f: (dict(m, routed_scaling_factor=1.0), r, f)))


FAULTS = {f.__name__: f for f in (
    selection_of_one_key_fewer, selection_dropped,
    selection_before_the_rotary_term, indexer_row_written_one_position_on,
    held_range_shifted_by_one, routed_scaling_factor_dropped)}


@pytest.mark.parametrize("fault,by", [
    ("selection_of_one_key_fewer", "select_count_off"),
    ("selection_dropped", "select_count_off"),
    ("selection_before_the_rotary_term", "select_overlap_miss"),
    ("indexer_row_written_one_position_on", "select_overlap_miss"),
    ("held_range_shifted_by_one", "routed_gap"),
    ("routed_scaling_factor_dropped", "routed_gap")])
def test_a_planted_fault_is_not_correct(make_run, monkeypatch, fault, by):
    """A run with `fault` planted reads `correct` false, by the number
    that holds the mechanism directly (others may join it)."""
    FAULTS[fault](monkeypatch.setattr)
    _, line = measure(make_run, 41)
    got = line["compared"][by]
    assert line["correct"] is False and got["value"] > got["limit"], \
        line["compared"]


@pytest.mark.parametrize("seed", [51, 3000000053])
def test_the_control_in_float8_fails_the_limits(make_run, seed):
    """The same window judged in float8: at each position of the same
    prompts and tokens its own first choice, its own held experts' parts,
    attention blocks and selections in place of the system's. The held
    experts' parts lie over their limit, and so does what the attention
    adds or the selection it is taken over (at this size the first follows
    the second: 8 kept keys)."""
    from benchmark.systems import glm5_decode_server as server

    run = make_run(CELL, seed=seed, seconds=1.5)
    run.traffic["check"]["sample_requests"] = 8
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["correct"] is True, line["compared"]
    sut = harness.load_part("systems", run.config["system"])
    built = type("Sut", (), {})()
    built.model = server.reference_sizes(run.config)
    built.serving = run.config["serving"]
    built.cfg = server.model_config(built.model)
    run.compared = {}
    sut.check(run, built, control="float8")
    over = {name: c["value"] / c["limit"] for name, c in run.compared.items()
            if c["limit"]}
    assert over["routed_gap"] > 1, run.compared
    assert max(over["latent_gap"], over["select_overlap_miss"]) > 1, \
        run.compared


def test_the_readers_read_their_own_events_and_nothing_else(make_run):
    """`glm5_gmm_roofline_pct` and the step's share from a recorded table:
    the kernel's events by name and by the step's row count, its calls
    counted from the traced executions of the step; the prefill's time, its
    share and the kept-keys kernel's share from the adapter's own traced
    fill of the longest program; nothing on another
    configuration's run."""
    m, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    run = make_run(CELL)
    run.config = harness.resolve_cell(run.manifest, CELL, root=ROOT)[1]
    run.traffic = harness.resolve_cell(run.manifest, CELL, root=ROOT)[2]
    gmm = harness.load_part("metrics", "glm5_gmm_roofline_pct")
    step = harness.load_part("metrics", "glm5_step_roofline_pct")
    t0 = 1000.0
    run.obs.update(
        gauges=[{"slot_utilization": 1.0}], window_t0=t0, window_s=24.0,
        live_row_seconds=24.0 * 16 * 12000,
        counters={"steps": 10, "moe_experts_touched_sum": 10 * 4 * 6.4,
                  "latent_rows_live": 10 * 5 * 16 * 12000,
                  "latent_rows_read": 10 * 5 * 16 * 2048,
                  "dsa_rows_selected": 10 * 5 * 16 * 2048},
        trace={"ops": {"%fusion.1 = bf16[128,6144] fusion(": 9.0,
                       "%gmm.9 = bf16[32768,2048] custom-call(": 5.0},
               "modules": {"jit_fwd_decode_step": {
                   "count": 4, "seconds": 0.048, "by_plane": {}}}})
    assert gmm.read(run) is None      # the prefill's calls are not the step's
    one = costs_glm5.grouped_products_min_seconds(m, 16, peaks, 6.4)
    run.obs["trace"]["ops"]["%gmm.3 = bf16[128,2048] custom-call("] = 0.006
    run.obs["trace"]["ops"]["%gmm = bf16[128,6144] custom-call("] = 0.006
    assert gmm.read(run) == pytest.approx(100 * 4 * 4 * one / 0.012)
    least = costs_glm5.step_min_seconds(m, 16, 16 * 12000, peaks, 6.4)
    assert step.read(run) == pytest.approx(100 * least / 0.012)
    assert 45 < step.read(run) < 60
    assert harness.load_part(
        "metrics", "glm5_latent_rows_read_over_live").read(run) == \
        pytest.approx(2048 / 12000.0)
    assert harness.load_part("metrics", "glm5_select_keep_pct").read(run) \
        == pytest.approx(100 * 2048 / 12000.0)
    # the prefill's time, its share and the kept-keys kernel's read the
    # adapter's own traced fill, never the window's three traced seconds
    ms = harness.load_part("metrics", "glm5_prefill_device_ms")
    share = harness.load_part("metrics", "glm5_prefill_roofline_pct")
    kept = harness.load_part("metrics", "glm5_kept_attn_roofline_pct")

    def site(i, bucket):
        return ("%%kept_keys_attn_fwd.%d = bf16[1,%d,16384] custom-call("
                % (i, bucket))

    def none_reads():
        return [r.read(run) for r in (ms, share, kept)] == [None] * 3

    run.obs["trace"]["modules"]["jit_fwd_prefill_16384"] = {
        "count": 3, "seconds": 2.1, "by_plane": {}}
    run.obs["trace"]["ops"].update({site(i, 16384): 0.2 for i in range(5)})
    assert none_reads()               # a plain run, a CPU: no traced fill
    # a trace that holds only the other bucket's program, or only the step
    run.obs["glm5_fill"] = {"plen": 16384, "trace": {
        "modules": {"jit_fwd_prefill_8192": {
            "count": 1, "seconds": 0.34, "by_plane": {}},
            "jit_fwd_decode_step": {
                "count": 1, "seconds": 0.012, "by_plane": {}}},
        "ops": {site(5 + i, 8192): 0.02 for i in range(5)}}}
    assert none_reads()
    fill = run.obs["glm5_fill"]["trace"]
    fill["modules"]["jit_fwd_prefill_16384"] = {
        "count": 1, "seconds": 0.9, "by_plane": {}}
    assert ms.read(run) == pytest.approx(900.0)
    least = costs_glm5.prefill_min_seconds(m, 16384, peaks)
    assert share.read(run) == pytest.approx(100 * least / 0.9)
    assert 20 < share.read(run) < 40
    assert kept.read(run) is None     # no such kernel in the program
    fill["ops"].update({site(i, 16384): 0.08 for i in range(5)})
    call = costs_glm5.kept_attention_min_seconds(m, 16384, peaks)
    assert kept.read(run) == pytest.approx(100 * call / 0.08)
    assert 5 < kept.read(run) < 20
    # two executions under the profiler: a call a site and execution
    fill["modules"]["jit_fwd_prefill_16384"] = {
        "count": 2, "seconds": 1.8, "by_plane": {}}
    fill["ops"].update({site(i, 16384): 0.16 for i in range(5)})
    assert ms.read(run) == pytest.approx(900.0)
    assert share.read(run) == pytest.approx(100 * least / 0.9)
    assert kept.read(run) == pytest.approx(100 * call / 0.08)
    other = make_run("laguna_code_context_decode")
    other.obs.update(run.obs)
    for name in ("glm5_gmm_roofline_pct", "glm5_step_roofline_pct",
                 "glm5_prefill_device_ms", "glm5_prefill_roofline_pct",
                 "glm5_latent_rows_read_over_live", "glm5_select_keep_pct",
                 "glm5_tokens_per_held_expert", "glm5_load_max_over_mean",
                 "glm5_kept_attn_roofline_pct"):
        assert harness.load_part("metrics", name).read(other) is None, name
    # and Laguna's readers find nothing in this cell's run
    assert harness.load_part("metrics",
                             "laguna_gmm_roofline_pct").read(run) is None


def test_the_check_samples_one_bucket_the_longest():
    """One prefill program for the check, not one a bucket: the sample is
    of the longest bucket that was used, the longest request in it."""
    from benchmark.systems import glm5_decode_server as system

    finished = [{"index": i, "bucket": b, "prompt": [1] * n, "tokens": [2] * t}
                for i, (b, n, t) in enumerate([
                    (8192, 8000, 700), (16384, 9000, 400), (8192, 5000, 768),
                    (16384, 16000, 500), (16384, 12000, 768)])]
    for seed in (1, 2390004071):
        sample = system.pick_sample(finished, 2, seed)
        assert len(sample) == 2
        assert {r["bucket"] for r in sample} == {16384}
        assert sample[0]["index"] == 3
    assert [r["index"] for r in system.pick_sample(finished[:1], 2, 1)] == [0]
    assert system.pick_sample([], 2, 1) == []


def test_the_cell_rehearses_from_the_command_line():
    """`benchmark/run.py --rehearse-cpu`: the command the driver runs, at
    the rehearsal sizes; its last line is a rehearsal, never a result."""
    import json
    import os
    import subprocess
    import sys

    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3000000077", "--seconds", "2",
         "--trace", "0", "--rehearse-cpu"],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    doc = json.loads(p.stdout.strip().splitlines()[-1])
    assert doc["rehearsal"] is True
    line = doc["would_print"]
    assert line["workload"] == CELL and line["seed"] == 3000000077
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    assert set(line["metrics"]) == {"itl_ms_p90", "setup_s"}
