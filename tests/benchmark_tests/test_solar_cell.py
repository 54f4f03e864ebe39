"""The Solar-Open2 decoder's cell (`solar_doc_context_decode`) at rehearsal
size on the CPU: the manifest's entries by membership, the configuration
file against the catalog's row and the cut, the cost functions against hand
counts, the state the configuration file promises, one rehearsed window
whose line carries whole `attempted` >= 1 and `correct` true, and the
readers on a hand-made run. That every control of `check()` fails is
tests/benchmark_tests/test_solar_controls.py.

The rehearsal reads (seeds 41, 51, 3000000043; one softmax layer and ONE
delta-rule layer, heads of 16, 4 of 16 experts held, prompts 12-32, 8-16 new
tokens): `kda_gap` 0.008-0.012, `state_gap` 0.006, `gqa_gap` 0.005-0.006,
`routed_gap` 0.02-0.05, `logit_gap_sigma` 0-1.4 (the widest of a few dozen
tokens over 64-wide products). The timed size's limits and the readings they
were set from are in the traffic file's `check` group and PERF.md."""
import json

import pytest

from conftest import ROOT

from benchmark import costs_solar, harness

CELL = "solar_doc_context_decode"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
READERS = ("solar_step_roofline_pct", "solar_prefill_device_ms",
           "solar_prefill_roofline_pct", "solar_gmm_roofline_pct",
           "solar_states_updated_over_live", "solar_kv_rows_read_over_live",
           "solar_tokens_per_held_expert", "solar_load_max_over_mean")


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def published():
    manifest = harness.load_manifest(ROOT)
    _, config, _ = harness.resolve_cell(manifest, CELL, root=ROOT)
    return costs_solar.sizes(config), config


def test_the_manifest_names_the_configuration_the_cell_and_the_readers():
    """Membership, never equality or position: a later PR appends."""
    manifest = harness.load_manifest(ROOT)
    config = {c["name"]: c for c in manifest["configs"]}["solar_open2_250b"]
    assert config["file"] == "benchmark/configs/solar_open2_250b.json"
    assert set(config["reduced"]) == {"gqa_layers", "n_routed_experts",
                                      "vocab_size"}
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "solar_open2_250b", "doc_context_closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    reported = {m["name"] for m in harness.metrics_for(
        manifest, "end_to_end", CELL)}
    assert {"itl_ms_p90", "setup_s"} <= reported
    assert reported <= {"itl_ms_p90", "setup_s", "serve_tokens_per_s"}
    per_layer = {m["name"]: m for m in harness.metrics_for(
        manifest, "per_layer", CELL)}
    for name in READERS:
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
    assert config["source"].endswith(
        "upstage/Solar-Open2-250B/blob/main/config.json")
    for key, value in (("hidden_size", 4096), ("num_attention_heads", 64),
                       ("num_key_value_heads", 8), ("head_dim", 128),
                       ("intermediate_size", 10240),
                       ("moe_intermediate_size", 1280),
                       ("num_experts_per_tok", 8), ("n_shared_experts", 1),
                       ("routed_scaling_factor", 1), ("gqa_interval", 3),
                       ("num_hidden_layers", 48), ("rms_norm_eps", 1e-5),
                       ("max_position_embeddings", 1048576),
                       ("use_rope", False), ("use_gqa_gate", True),
                       ("kda_use_full_proj", False),
                       ("kda_allow_neg_eigval", True),
                       ("first_k_dense_replace", 0), ("rope_theta", 10000),
                       ("partial_rotary_factor", 1)):
        assert config[key] == value, key
    assert config["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert config["reduced_from"] == {
        "gqa_layers": list(range(0, 48, 4)), "n_routed_experts": 320,
        "vocab_size": 196608}
    assert (config["gqa_layers"], m["n_routed_experts"], m["vocab_size"]) \
        == ([0], 40, 24576)
    assert m["router_experts"] == 320 and m["first_expert"] == 0
    assert costs_solar.layers(m) == (1, 3)
    for group in ("deployment", "share", "assumed", "precision", "serving",
                  "rehearsal"):
        assert config[group], group
    assert "8 chips" in config["deployment"]
    assert config["serving"]["slots"] == 64
    assert config["serving"]["cache_len"] == 16896
    # every number of the catalog's row, where the catalog is at hand
    try:
        rows = [json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        return
    row = next(r for r in rows if r["name"] == "Solar-Open2-250B")
    assert config["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key not in config["reduced_from"]:
            assert config[key] == value, key
        else:
            assert config["reduced_from"][key] == value, key


def test_costs_follow_the_cut_written_in_the_issue():
    m, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    f = costs_solar.ffn_params(m)
    # the issue's table, in millions (norms, A_log, dt_bias beside them)
    assert round(costs_solar.gqa_params(m) / 1e6, 1) == 109.1
    assert round(costs_solar.kda_params(m) / 1e6, 1) == 137.7
    assert round(f["outside"] / 1e6, 2) == 17.04
    assert round(f["expert"] / 1e6, 2) == 15.73
    assert round((costs_solar.gqa_params(m) + f["outside"]
                  + 40 * f["expert"]) / 1e6, 1) == 755.2
    assert round((costs_solar.kda_params(m) + f["outside"]
                  + 40 * f["expert"]) / 1e6, 1) == 783.9
    assert round(costs_solar.held_params(m) / 1e6) == 3308      # 6.62 GB
    # the shapes give the family's published count for the whole model
    whole = dict(m, gqa_layers=list(range(0, 48, 4)), n_routed_experts=320,
                 vocab_size=196608)
    assert round(costs_solar.held_params(whole) / 1e9, 1) == 250.3
    # a slot's state in one delta-rule layer: 64 x 128 x 128 float32 and
    # three windows of 3 x 8,192 bfloat16; a position's K and V: 4,096 B
    assert costs_solar.slot_bytes(m) == {"state": 4194304, "windows": 147456}
    assert costs_solar.kv_row_bytes(m) == 4096
    state = costs_solar.state_bytes(m, 64, 16896)
    assert state["rows"] == 64 * 16896 * 4096                   # 4.43 GB
    assert state["fixed"] == 64 * 3 * (4194304 + 147456)        # 0.83 GB
    # 64 slots at 10,300 live positions: 32.0 of 40 held experts touched a
    # layer, 5.5 GB of weights, 1.67 GB of state in and out, 2.7 GB of rows:
    # 9.9 GB, 12 ms at the peak bandwidth
    slots, rows = 64, 64 * 10300
    assert 31.5 < costs_solar.experts_touched(m, 64) < 32.5
    assert 5.3e9 < costs_solar.weight_bytes(m, 64) < 5.6e9
    moved = costs_solar.step_state_bytes(m, slots, rows)
    assert moved == slots * 3 * 2 * (4194304 + 147456) + rows * 4096
    nbytes = costs_solar.step_bytes(m, slots, rows)
    assert costs_solar.step_min_seconds(m, slots, rows, peaks) == \
        pytest.approx(nbytes / 819e9)
    assert 11.5e-3 < nbytes / 819e9 < 12.5e-3
    # a token's matrices: 1.3 GFLOP outside the mixers' cores; the
    # recurrence's three products: 6.3 MFLOP a delta-rule layer
    head = 2 * 4096 * 24576
    per_token = costs_solar.flops_per_token(m, 0) - head
    assert 1.29e9 < per_token < 1.36e9
    assert costs_solar.flops_per_token(m, 1000) - head - per_token == \
        2 * 1000 * 64 * 2 * 128
    # a batch-1 prefill of 16,384: 21.6 TFLOP of products and recurrence,
    # 4.4 TFLOP of causal softmax attention: 26 TFLOP, 0.13 s at the peak
    flops = costs_solar.prefill_flops(m, 16384)
    assert flops == pytest.approx(
        16384 * per_token + head + 2 * 16384 * 16385 / 2 * 64 * 2 * 128)
    assert 25.5e12 < flops < 26.5e12
    assert costs_solar.prefill_min_seconds(m, 16384, peaks) == \
        pytest.approx(flops / 197e12)
    # one layer's three grouped products with every slot live: 32 experts'
    # matrices of 31.5 MB, 1.0 GB, 1.2 ms
    one = costs_solar.grouped_products_min_seconds(m, 64, peaks)
    assert 1.0e9 / 819e9 < one < 1.03e9 / 819e9
    assert costs_solar.grouped_products_min_seconds(
        m, 64, peaks, touched=8) < 0.5 * one


def test_the_held_state_is_what_the_configuration_file_says():
    """The declaration the engine allocates from, summed at the published
    sizes: 64 slots x (16,896 positions of K and V in one layer; three
    windows and a float32 state in each of three)."""
    from benchmark.systems import solar_decode_server as server

    m, config = published()
    cfg = server.model_config(m)
    decl = cfg.decode_model(config["serving"]["cache_len"])
    slots = config["serving"]["slots"]
    want = costs_solar.state_bytes(m, slots, config["serving"]["cache_len"])
    assert slots * decl.slot_bytes("rows") == want["rows"]
    assert slots * decl.slot_bytes("fixed") == want["fixed"]
    assert round((want["rows"] + want["fixed"]) / 1e9, 2) == 5.26
    from paddle_tpu.models import solar_open2

    shapes = solar_open2.param_shapes(cfg)
    count = sum(int(__import__("numpy").prod(s)) for s, _ in shapes.values())
    assert count == costs_solar.held_params(m)


@pytest.fixture(scope="module")
def rehearsed(manifest, tmp_path_factory):
    """One plain window of the cell at rehearsal size."""
    import os
    import time

    cell, config, traffic = harness.resolve_cell(manifest, CELL, root=ROOT,
                                                 rehearse=True)
    run = harness.Run(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=3000000043, seconds=1.5, trace=False, chips=1,
        peaks=harness.load_json(os.path.join(
            ROOT, "benchmark", "peaks.json"))["TPU v5 lite"],
        rehearse=True, out_dir=str(tmp_path_factory.mktemp("solar")),
        t0=time.monotonic(), compiles=Counting())
    return run, harness.measure(run, dict(DEVICE))


def test_the_cell_rehearses_on_the_cpu(rehearsed):
    run, line = rehearsed
    assert line["workload"] == CELL and line["seed"] == 3000000043
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert {"itl_ms_p90", "setup_s"} <= set(line["metrics"])
    assert set(line["compared"]) == {
        "logit_gap_sigma", "routed_gap", "kda_gap", "state_gap", "gqa_gap",
        "tokens_short_of_sample"}
    assert all(c["value"] is not None for c in line["compared"].values())
    # what the step program counted on the device reached the readers
    c = run.obs["counters"]
    assert c["kda_states_updated"] >= c["kda_states_live"] > 0
    assert c["kv_rows_read"] > c["kv_rows_live"] > 0
    assert c["moe_assignments_total"] > c["moe_assignments_held"] > 0
    for name in READERS[4:]:
        assert harness.load_part("metrics", name).read(run) > 0, name
    for name in READERS[:4]:          # a CPU run kept no device trace
        assert harness.load_part("metrics", name).read(run) is None, name
    g = run.obs["gauges"][-1]
    assert g["state_bytes_rows"] > 0 and g["state_bytes_fixed"] > 0


def test_the_readers_read_their_own_events_and_nothing_else(make_run):
    """The step's share and the grouped kernel's from a recorded table, the
    prefill's time and share from the adapter's own traced fill; the counts'
    ratios; nothing on another configuration's run."""
    m, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    run = make_run(CELL)
    run.config = harness.resolve_cell(run.manifest, CELL, root=ROOT)[1]
    run.traffic = harness.resolve_cell(run.manifest, CELL, root=ROOT)[2]

    def reader(name):
        return harness.load_part("metrics", name)

    run.obs.update(
        gauges=[{"slot_utilization": 1.0}], window_t0=1000.0, window_s=24.0,
        live_row_seconds=24.0 * 64 * 10300,
        counters={"steps": 10, "moe_experts_touched_sum": 10 * 4 * 32.0,
                  "moe_assignments_held": 10 * 4 * 64,
                  "moe_expert_load_max_sum": 10 * 4 * 5,
                  "kda_states_live": 10 * 3 * 60,
                  "kda_states_updated": 10 * 3 * 64,
                  "kv_rows_live": 10 * 64 * 10300,
                  "kv_rows_read": 10 * 64 * 16896},
        trace={"ops": {"%fusion.1 = bf16[512,4096] fusion(": 9.0,
                       "%gmm.9 = bf16[32768,1280] custom-call(": 5.0},
               "modules": {"jit_fwd_decode_step": {
                   "count": 4, "seconds": 0.1, "by_plane": {}}}})
    gmm, step = reader("solar_gmm_roofline_pct"), \
        reader("solar_step_roofline_pct")
    assert gmm.read(run) is None      # the prefill's calls are not the step's
    one = costs_solar.grouped_products_min_seconds(m, 64, peaks, 32.0)
    run.obs["trace"]["ops"]["%gmm.3 = bf16[512,1280] custom-call("] = 0.012
    run.obs["trace"]["ops"]["%gmm = bf16[512,4096] custom-call("] = 0.008
    assert gmm.read(run) == pytest.approx(100 * 4 * 4 * one / 0.020)
    least = costs_solar.step_min_seconds(m, 64, 64 * 10300, peaks, 32.0)
    assert step.read(run) == pytest.approx(100 * least / 0.025)
    assert 40 < step.read(run) < 60
    assert reader("solar_states_updated_over_live").read(run) == \
        pytest.approx(64 / 60.0)
    assert reader("solar_kv_rows_read_over_live").read(run) == \
        pytest.approx(16896 / 10300.0)
    assert reader("solar_tokens_per_held_expert").read(run) == \
        pytest.approx(64 / 40.0)
    assert reader("solar_load_max_over_mean").read(run) == \
        pytest.approx(5 * 40 / 64.0)
    # the prefill's time and share read the adapter's own traced fill,
    # never the window's three traced seconds
    ms, share = reader("solar_prefill_device_ms"), \
        reader("solar_prefill_roofline_pct")
    run.obs["trace"]["modules"]["jit_fwd_prefill_16384"] = {
        "count": 3, "seconds": 1.1, "by_plane": {}}
    assert ms.read(run) is None and share.read(run) is None
    run.obs["solar_fill"] = {"plen": 16384, "trace": {"modules": {
        "jit_fwd_prefill_8192": {"count": 1, "seconds": 0.2, "by_plane": {}},
        "jit_fwd_decode_step": {"count": 1, "seconds": 0.02,
                                "by_plane": {}}}, "ops": {}}}
    assert ms.read(run) is None and share.read(run) is None
    run.obs["solar_fill"]["trace"]["modules"]["jit_fwd_prefill_16384"] = {
        "count": 2, "seconds": 0.9, "by_plane": {}}
    assert ms.read(run) == pytest.approx(450.0)
    least = costs_solar.prefill_min_seconds(m, 16384, peaks)
    assert share.read(run) == pytest.approx(100 * least / 0.45)
    assert 20 < share.read(run) < 40
    other = make_run("laguna_code_context_decode")
    other.obs.update(run.obs)
    for name in READERS:
        assert reader(name).read(other) is None, name
    assert reader("glm5_gmm_roofline_pct").read(run) is None
    assert reader("laguna_kv_rows_read_over_live").read(run) is None
