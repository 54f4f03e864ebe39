"""The Kimi-VL cell (`kimi_vl_doc_pages_decode`) at rehearsal size on the
CPU: the manifest's entries by membership, the configuration file against
the catalog's row key by key and the cut, the cost functions against the
issue's hand counts, the media traffic total and steady over seeds, one
rehearsed window through the new driver whose line carries whole `attempted`
>= 1 and `correct` true, `correct` false under three of the controls, and the
readers on a made-up run.

The rehearsal reads (seed 2147483999; one dense and two sparse layers, 8
experts top-3 all held, a two-block tower over grids of 2-6 patches a side,
prompts 24-60 rows, 6-12 new tokens, chunks of 8): `logit_gap_sigma` 0.005,
`routed_gap` 0.012, `mla_gap` 0.005, `ffn_gap` 0.007, `tower_gap` 0.007,
`tower_attn_gap` 0.0027-0.0036, `table_gap` 3e-7, `splice_gap` 0.006; float8
reads `mla_gap` 0.09, `tower_gap` 0.07, `ffn_gap` 0.07; the media rows one
position on `splice_gap` 0.96; no 2-D rotary term `tower_attn_gap`
0.024-0.045 (and `tower_gap` 0.019-0.035, which at the timed size does not
tell it from rounding). The timed size's limits and the readings they were set
from are in the traffic file's `check` group and PERF.md."""
import json

import numpy as np
import pytest

from conftest import ROOT

from benchmark import costs_kimi_vl, harness, traffic_media

CELL = "kimi_vl_doc_pages_decode"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
TRACED = ("kimi_vl_step_roofline_pct", "kimi_vl_chunk_device_ms",
          "kimi_vl_chunk_roofline_pct", "kimi_vl_tower_device_ms",
          "kimi_vl_tower_roofline_pct", "kimi_vl_gmm_roofline_pct")
COUNTED = ("kimi_vl_latent_rows_read_over_live", "kimi_vl_media_rows_pct",
           "kimi_vl_media_prepare_ms_per_image",
           "kimi_vl_tokens_per_held_expert", "kimi_vl_load_max_over_mean")
READERS = TRACED + COUNTED


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def reader(name):
    return harness.load_part("metrics", name)


def published():
    manifest = harness.load_manifest(ROOT)
    _, config, traffic = harness.resolve_cell(manifest, CELL, root=ROOT)
    return costs_kimi_vl.sizes(config), config, traffic


def test_the_manifest_names_the_configuration_the_cell_and_the_readers():
    """Membership, never equality or position: a later PR appends."""
    manifest = harness.load_manifest(ROOT)
    config = {c["name"]: c for c in manifest["configs"]}[
        "kimi_vl_a3b_instruct"]
    assert config["file"] == "benchmark/configs/kimi_vl_a3b_instruct.json"
    assert config["reduced"] == ["mlp_layer_types"]
    cell = {c["name"]: c for c in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "kimi_vl_a3b_instruct", "doc_pages_closed", 1)
    assert len(cell["why"]) <= 200 and len(config["why"]) <= 200
    assert {m["name"] for m in harness.metrics_for(
        manifest, "end_to_end", CELL)} == {"itl_ms_p90", "setup_s"}
    per_layer = {m["name"]: m for m in harness.metrics_for(
        manifest, "per_layer", CELL)}
    for name in READERS:
        assert per_layer[name]["moves"] == "itl_ms_p90"
        assert per_layer[name]["workloads"] == [CELL]
        assert reader(name).read
    for name in ("completed_tokens_per_s", "slot_occupancy_pct",
                 "dispatches_per_token", "serve_step_device_ms",
                 "serve_device_idle_pct", "serve_peak_hbm_gb",
                 "engine_sync_wait_pct"):
        assert name in per_layer
    assert all(m["moves"] in ("itl_ms_p90", "setup_s")
               for m in per_layer.values())


def test_the_configuration_file_is_the_catalog_row_key_by_key():
    m, config, _ = published()
    assert config["source"].endswith(
        "moonshotai/Kimi-VL-A3B-Instruct/blob/main/config.json")
    try:
        rows = [json.loads(line) for line in open(
            "/opt/skills/guides/model-configs/architectures.jsonl")]
    except OSError:
        rows = []
    for row in rows:
        if row["name"] == "Kimi-VL-A3B-Instruct":
            assert row["source_url"] == config["source"]
            for key, value in row["config"].items():
                assert config[key] == value, key      # the depth too: 27
    assert config["num_hidden_layers"] == 27 and config["q_lora_rank"] is None
    assert config["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert config["reduced_from"] == {
        "mlp_layer_types": ["dense"] + ["sparse"] * 26}
    assert m["num_hidden_layers"] == 5
    v = config["vision_config"]
    assert (v["num_hidden_layers"], v["hidden_size"], v["intermediate_size"],
            v["num_attention_heads"], v["patch_size"],
            v["init_pos_emb_height"], v["in_token_limit"]) == (
                27, 1152, 4304, 16, 14, 64, 4096)
    for group in ("deployment", "share", "assumed", "precision", "serving",
                  "rehearsal"):
        assert config[group], group
    for key in ("vision_config", "media_placeholder_token_id",
                "in_token_limit", "initialisation", "pixels"):
        assert key in config["assumed"], key
    assert "FIRST" in config["deployment"]
    assert "3.3 GFLOP a media row" in config["deployment"]
    assert config["serving"] == {"slots": 48, "cache_len": 17408,
                                 "queue_capacity": 128,
                                 "request_timeout_s": 240.0}
    assert config["precision"]["control"] == "float8"


def test_costs_follow_the_cut_written_in_the_issue():
    """Held parameters 3,541 M (the issue's table), bytes and operations
    from shapes."""
    m, config, _ = published()
    assert costs_kimi_vl.attention_params(m) == round(13.763072e6)
    f = costs_kimi_vl.ffn_params(m)
    assert (f["dense"], f["expert"]) == (3 * 2048 * 11264, 3 * 2048 * 1408)
    t = costs_kimi_vl.tower_params(m)
    assert round(t["tower"] / 1e6, 1) == 416.9
    assert round(t["projector"] / 1e6, 1) == 30.7
    assert round(costs_kimi_vl.held_params(m) / 1e6) == 3541
    assert round(costs_kimi_vl.held_params(m) * 2 / 1e9, 2) == 7.08
    sv = config["serving"]
    assert round(costs_kimi_vl.state_bytes(
        m, sv["slots"], sv["cache_len"], 640) / 1e9, 2) == 5.35
    # a token's matrices without the head: 0.83 GFLOP for the five layers
    assert round(costs_kimi_vl.token_flops(m, head=False) / 1e9, 2) == 0.83
    assert 5.4e12 < costs_kimi_vl.tower_flops(m, 4096) < 5.6e12
    assert 3.8e12 < costs_kimi_vl.chunk_flops(m, 4096, 0) < 4.0e12
    assert (costs_kimi_vl.chunk_flops(m, 4096, 12288)
            > costs_kimi_vl.chunk_flops(m, 4096, 0) + 2.5e12)
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    # all 64 experts touched, 3.2 GB of live rows: the bytes bound a step
    least = costs_kimi_vl.step_min_seconds(m, 48, 48 * 10400, peaks, 64.0)
    assert 0.009 < least < 0.012
    from paddle_tpu.models import kimi_vl

    shapes = kimi_vl.param_shapes(kimi_vl.KimiVlConfig.from_hf(m))
    assert sum(int(np.prod(s)) for s, _ in shapes.values()) == \
        costs_kimi_vl.held_params(m)


@pytest.mark.parametrize("seed", [0, 41, 2 ** 31 + 77])
def test_the_media_traffic_is_total_and_steady_over_seeds(seed):
    m, config, traffic = published()
    media_id, cache_len = m["media_placeholder_token_id"], 17408
    src = traffic_media.MediaSource(traffic, seed, m["vocab_size"], cache_len,
                                    media_id)
    blocks = []
    for _ in range(2):
        grids, counts, rows, total = [], [], 0, 0
        for _ in range(traffic["block"]):
            r = src.next()
            marked = r["prompt"] == media_id
            want = sum(h * w // 4 for h, w, _ in r["images"])
            assert int(marked.sum()) == want
            runs = np.flatnonzero(np.diff(np.concatenate(
                [[0], marked.astype(np.int8)])) == 1)
            assert len(runs) == len(r["images"]) and runs[0] >= 16
            assert not marked[-1]
            assert len(r["prompt"]) <= 16384
            assert len(r["prompt"]) + r["max_new"] - 1 <= cache_len
            assert r["prompt"].min() >= 0 and r["prompt"].max() < 163840
            for h, w, _ in r["images"]:
                assert h % 2 == w % 2 == 0 and 32 <= h <= 64 and 32 <= w <= 64
                grids.append((h, w))
            counts.append(len(r["images"]))
            rows += want
            total += len(r["prompt"])
        blocks.append((sorted(h for h, _ in grids),
                       sorted(w for _, w in grids), sorted(counts)))
        assert 13.0 < 100.0 * rows / total < 15.0
    assert blocks[0] == blocks[1]
    assert blocks[0][2] == sorted([1, 2, 3, 4] * 64)
    px = traffic_media.pixels(2, 4, 7)
    assert px.shape == (28, 56, 3) and px.dtype == np.uint8
    assert (px == traffic_media.pixels(2, 4, 7)).all()
    field = json.loads(traffic_media.images_field([(2, 4, 7)]))
    assert field[0]["grid"] == [2, 4]


@pytest.fixture(scope="module")
def rehearsed(manifest, tmp_path_factory):
    """One plain window of the cell at rehearsal size, the fill's chunks 8
    rows long (at the model's own 4,096 no rehearsal prompt is cut)."""
    import os
    import time

    from paddle_tpu.models import kimi_vl

    was, kimi_vl.CHUNK_ROWS = kimi_vl.CHUNK_ROWS, 8
    cell, config, traffic = harness.resolve_cell(manifest, CELL, root=ROOT,
                                                 rehearse=True)
    run = harness.Run(
        manifest=manifest, cell=cell, config=config, traffic=traffic,
        seed=2147483999, seconds=1.5, trace=False, chips=1,
        peaks=harness.load_json(os.path.join(
            ROOT, "benchmark", "peaks.json"))["TPU v5 lite"],
        rehearse=True, out_dir=str(tmp_path_factory.mktemp("kimi")),
        t0=time.monotonic(), compiles=Counting())
    try:
        yield run, harness.measure(run, dict(DEVICE))
    finally:
        kimi_vl.CHUNK_ROWS = was


def test_the_cell_rehearses_on_the_cpu_through_the_media_driver(rehearsed):
    run, line = rehearsed
    assert line["workload"] == CELL and line["seed"] == 2147483999
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    assert set(line["metrics"]) == {"itl_ms_p90", "setup_s"}
    assert set(line["compared"]) == {
        "logit_gap_sigma", "routed_gap", "mla_gap", "ffn_gap", "tower_gap",
        "tower_attn_gap", "table_gap", "splice_gap",
        "tokens_short_of_sample"}
    assert all(c["value"] is not None for c in line["compared"].values())
    c = run.obs["counters"]
    assert c["latent_rows_read"] > c["latent_rows_live"] > 0
    assert c["moe_assignments_total"] == c["moe_assignments_held"] > 0
    assert c["media_images"] == c["tower_runs"] > 0
    assert c["fill_rows"] > c["media_rows"] > 0 and c["fill_chunks"] > 0
    assert all(r["images"] for r in run.obs["finished"])
    for name in COUNTED:
        assert reader(name).read(run) > 0, name
    for name in TRACED:               # a CPU run kept no device trace
        assert reader(name).read(run) is None, name


@pytest.mark.parametrize("control,by", [("float8", "mla_gap"),
                                        ("media_shifted", "splice_gap"),
                                        ("no_rope_2d", "tower_attn_gap")])
def test_a_control_is_not_correct(rehearsed, control, by):
    """The reference computed in float8, or with the media rows one position
    on, judged in the system's place over what the window served."""
    from benchmark import controls_kimi_vl
    from benchmark.systems import kimi_vl_decode_server as server

    assert set(controls_kimi_vl.FAILS) == set(server.CONTROLS)
    assert by in controls_kimi_vl.FAILS[control]
    run, _ = rehearsed
    sut = type("Sut", (), {})()
    sut.model = server.reference_sizes(run.config)
    sut.serving = run.config["serving"]
    sut.cfg = server.model_config(sut.model)
    sut.patch = sut.cfg.vision.patch
    kept, run.compared = run.compared, {}
    try:
        server.check(run, sut, control=control)
        got = run.compared[by]
        assert got["value"] > got["limit"], run.compared
    finally:
        run.compared = kept


def test_the_readers_read_their_own_events_and_nothing_else(make_run,
                                                            monkeypatch):
    m, _, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    run = make_run(CELL)
    run.config = harness.resolve_cell(run.manifest, CELL, root=ROOT)[1]
    run.traffic = harness.resolve_cell(run.manifest, CELL, root=ROOT)[2]
    run.obs.update(
        gauges=[{"slot_utilization": 1.0}], window_t0=1000.0, window_s=24.0,
        live_row_seconds=24.0 * 48 * 10400,
        counters={"steps": 10, "moe_experts_touched_sum": 10 * 4 * 63.0,
                  "moe_assignments_held": 10 * 4 * 288,
                  "moe_expert_load_max_sum": 10 * 4 * 11,
                  "latent_rows_live": 10 * 5 * 48 * 10400,
                  "latent_rows_read": 10 * 5 * 48 * 17408,
                  "fill_rows": 100000, "media_rows": 14100},
        trace={"ops": {"%gmm.9 = bf16[24576,1408] custom-call(": 5.0,
                       "%gmm.3 = bf16[384,1408] custom-call(": 0.012,
                       "%gmm = bf16[288,2048] custom-call(": 0.008},
               "modules": {
                   "jit_fwd_decode_step": {"count": 4, "seconds": 0.08,
                                           "by_plane": {}},
                   "jit_fwd_chunk_4096": {"count": 5, "seconds": 0.3,
                                          "by_plane": {}},
                   "jit_fwd_tower_1024": {"count": 2, "seconds": 0.03,
                                          "by_plane": {}},
                   "jit_fwd_tower_4096": {"count": 2, "seconds": 0.13,
                                          "by_plane": {}}}})
    spans = {
        "decode.prefill.chunk": [
            {"t0": 1001.0, "t1": 1001.001,
             "fields": {"rows": 4096, "start": 0}},
            {"t0": 1002.0, "t1": 1002.001,
             "fields": {"rows": 2000, "start": 8192}}],
        "serving.decode.tower": [
            {"t0": 1001.5, "t1": 1001.501, "fields": {"patches": 1024}},
            {"t0": 1002.5, "t1": 1002.501, "fields": {"patches": 3600}}],
        "serving.decode.media_prepare": [
            {"t0": 1003.0, "t1": 1003.012, "fields": {"images": 3}},
            {"t0": 1004.0, "t1": 1004.004, "fields": {"images": 1}}]}
    from benchmark.metrics import _kimi_vl, _program

    def window_spans(run, name):
        return spans.get(name)

    for mod in (_kimi_vl, _program,
                reader("kimi_vl_media_prepare_ms_per_image")):
        monkeypatch.setattr(mod, "window_spans", window_spans)
    least = costs_kimi_vl.step_min_seconds(m, 48, 48 * 10400, peaks, 63.0)
    assert reader("kimi_vl_step_roofline_pct").read(run) == pytest.approx(
        100 * least / 0.02)
    assert 40 < reader("kimi_vl_step_roofline_pct").read(run) < 60
    one = costs_kimi_vl.grouped_products_min_seconds(m, 48, peaks, 63.0)
    assert reader("kimi_vl_gmm_roofline_pct").read(run) == pytest.approx(
        100 * 4 * 4 * one / 0.020)
    assert reader("kimi_vl_chunk_device_ms").read(run) == pytest.approx(60.0)
    chunk = (costs_kimi_vl.chunk_min_seconds(m, 4096, 0, peaks)
             + costs_kimi_vl.chunk_min_seconds(m, 2000, 8192, peaks)) / 2
    assert reader("kimi_vl_chunk_roofline_pct").read(run) == pytest.approx(
        100 * chunk / 0.06)
    assert reader("kimi_vl_tower_device_ms").read(run) == pytest.approx(40.0)
    tower = (costs_kimi_vl.tower_min_seconds(m, 1024, peaks)
             + costs_kimi_vl.tower_min_seconds(m, 3600, peaks)) / 2
    assert reader("kimi_vl_tower_roofline_pct").read(run) == pytest.approx(
        100 * tower / 0.04)
    for name in ("kimi_vl_chunk_roofline_pct", "kimi_vl_tower_roofline_pct"):
        assert 0 < reader(name).read(run) < 100
    assert reader("kimi_vl_latent_rows_read_over_live").read(run) == \
        pytest.approx(17408 / 10400.0)
    assert reader("kimi_vl_media_rows_pct").read(run) == pytest.approx(14.1)
    assert reader("kimi_vl_media_prepare_ms_per_image").read(run) == \
        pytest.approx(4.0)
    assert reader("kimi_vl_tokens_per_held_expert").read(run) == \
        pytest.approx(4.5)
    assert reader("kimi_vl_load_max_over_mean").read(run) == \
        pytest.approx(11 * 64 / 288.0)
    # a program without the spans or the counters (the parent's): nothing
    spans.clear()
    for name in ("kimi_vl_chunk_roofline_pct", "kimi_vl_tower_roofline_pct",
                 "kimi_vl_media_prepare_ms_per_image"):
        assert reader(name).read(run) is None, name
    other = make_run("glm5_agent_context_decode")
    other.obs.update(run.obs)
    for name in READERS:
        assert reader(name).read(other) is None, name
    assert reader("glm5_gmm_roofline_pct").read(run) is None
    assert reader("solar_kv_rows_read_over_live").read(run) is None
