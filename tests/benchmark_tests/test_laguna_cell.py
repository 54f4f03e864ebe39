"""The Laguna decoder's cell (`laguna_code_context_decode`) at rehearsal size
on the CPU: its cost functions against the issue's arithmetic, the state the
configuration file promises, a plain and a traced line that carry whole
`attempted` >= 1, `correct` coming out false when the timed path is broken
five ways or computed in float8, and its readers on a run that has what they
read.

What the rehearsal reads (seeds 41, 51, 3000000043, 3000000053; window 8,
prompts 12-32, 8-16 new tokens, 12 sampled requests). `window_gap` (each
layer's attention block over the system's own stream, prefill rows and step
rows, root-mean-square against the reference's): sound 0.0055-0.0068
(bfloat16 K/V, probabilities and outputs against float32), window 9 for 8
0.34, the YaRN factor dropped 0.19, the gate dropped 1.70, the ring written
one row on 0.44, float8 0.084-0.090; held to the rehearsal's limit of 0.03.
`routed_gap` (median over a prompt's 12-32 positions, so a tie in the
routing moves it at this size): sound 0.016-0.045, the held range shifted
2.47, float8 0.30-0.37; held to 0.08. An attention fault moves `routed_gap`
too (0.25-1.7: the system's own pass routes another stream), a shifted range
leaves `window_gap` at 0.0068 and a ring one row on leaves `routed_gap` at
0.016 (the parts over the prompt do not see the ring).
"""
import numpy as np
import pytest

from conftest import ROOT

from benchmark import costs_laguna, harness

CELL = "laguna_code_context_decode"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def measure(make_run, seed, trace=False, seconds=2, sample=12):
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
    return costs_laguna.sizes(config), config


def test_costs_follow_the_cut_written_in_the_issue():
    m, config = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    f = costs_laguna.ffn_params(m)
    assert round(costs_laguna.attention_params(m, 48) / 1e6, 1) == 44.2
    assert round(costs_laguna.attention_params(m, 72) / 1e6, 1) == 63.1
    assert round(f["dense"] / 1e6, 1) == 113.2
    assert round((costs_laguna.attention_params(m, 72) + f["sparse_outside"]
                  + 64 * f["expert"]) / 1e6, 1) == 677.3
    assert round(costs_laguna.held_params(m) / 1e6) == 3002    # 6.00 GB
    sv = config["serving"]
    state = costs_laguna.state_bytes(m, sv["slots"], sv["cache_len"])
    assert round(state["rows"] / 1e9, 2) == 4.56
    assert round(state["ring"] / 1e9, 2) == 0.40
    # 64 slots at a mean context of 4,700: 2.4 GB of live rows + 0.4 of
    # rings, 4.1 GB of touched experts, 1.2 of the rest: about 8.2 GB, 10 ms
    slots, rows = 64, 64 * 4700
    nbytes = costs_laguna.step_bytes(m, slots, rows)
    assert 7.9e9 < nbytes < 8.6e9
    assert costs_laguna.step_min_seconds(m, slots, rows, peaks) == \
        pytest.approx(nbytes / 819e9)
    kv = costs_laguna.live_kv_rows(m, slots, rows) * costs_laguna.kv_row_bytes(m)
    assert 0.3 < kv / nbytes < 0.4                 # a third of the step
    assert 55 < costs_laguna.experts_touched(m, 64) < 60
    # a ring stops growing: beyond the window only the full layers' rows do
    assert costs_laguna.live_kv_rows(m, 1, 8192) - \
        costs_laguna.live_kv_rows(m, 1, 4096) == 2 * 4096
    # a batch-1 prefill of 8,192: 10-11 TFLOP, its window layers 0.46 of it
    # where a full causal call would spend 3.7
    flops = costs_laguna.prefill_flops(m, 8192)
    assert 10.0e12 < flops < 11.2e12
    assert costs_laguna.prefill_min_seconds(m, 8192, peaks) == \
        pytest.approx(flops / 197e12)
    window_part = 3 * 8192 * 4 * 72 * 128 * (512 - 512 * 511 / (2 * 8192.0))
    assert 0.42e12 < window_part < 0.48e12
    # one sparse layer's three products with every slot live: the touched
    # experts' matrices, 1.1 GB, 1.4 ms
    one = costs_laguna.grouped_products_min_seconds(m, 64, peaks)
    assert 1.0e9 / 819e9 < one < 1.2e9 / 819e9
    assert costs_laguna.grouped_products_min_seconds(
        m, 64, peaks, touched=32) < 0.6 * one


def test_the_held_state_is_what_the_configuration_file_says():
    """The declaration the engine allocates from, summed at the published
    sizes: 4.56 GB of rows and 0.40 GB of rings for 64 slots, 3,002 M
    parameters."""
    from benchmark.systems import laguna_decode_server as server
    from paddle_tpu.models import laguna

    m, config = published()
    cfg = server.model_config(server.reference_sizes(config))
    sv = config["serving"]
    model = cfg.decode_model(sv["cache_len"])
    state = costs_laguna.state_bytes(m, sv["slots"], sv["cache_len"])
    assert sv["slots"] * model.slot_bytes("rows") == state["rows"]
    assert sv["slots"] * model.slot_bytes("ring") == state["ring"]
    assert [e.kind for e in model.state] == (
        ["rows"] * 2 + ["ring"] * 6 + ["rows"] * 2)
    params = sum(int(np.prod(s)) for s, _ in
                 laguna.param_shapes(cfg).values())
    assert params == costs_laguna.held_params(m)
    assert cfg.rope["full_attention"][1] == 64
    assert cfg.rope["sliding_attention"] == (10000.0, 128, None)
    # every request of the mix fits its slot and crosses the ring's wrap
    mix = harness.load_json(ROOT + "/benchmark/traffic/code_context_closed.json")
    assert mix["prompt_tokens"]["min"] >= 2 * m["sliding_window"]
    assert (mix["prompt_tokens"]["max"] + mix["max_new_tokens"]["max"] - 1
            <= sv["cache_len"])
    assert mix["clients"] == 1.5 * sv["slots"]


@pytest.mark.parametrize("seed,trace", [(41, False), (3000000043, True)])
def test_the_sound_system_is_correct_and_its_readers_read(make_run, seed,
                                                          trace):
    """A plain and a traced window (0.6 of the seconds): both lines carry
    whole `attempted` >= 1, as the driver's check wants them."""
    run, line = measure(make_run, seed, trace=trace)
    assert line["correct"] is True, line["compared"]
    assert isinstance(line["attempted"], int) and line["attempted"] >= 1
    assert isinstance(line["failed"], int)
    assert line["compared"]["window_gap"]["value"] <= 0.012
    c = run.obs["counters"]
    assert c["moe_assignments_total"] > c["moe_assignments_held"] > 0
    assert c["cache_copy_steps"] == 0

    def read(name):
        return harness.load_part("metrics", name).read(run)

    # 4 of 16 experts held, top-3: a live slot lands 0.75 assignments on the
    # 4 held experts under even routing, 4 live slots 0.75 on each
    assert 0.1 < read("laguna_tokens_per_held_expert") < 2.0
    assert 1.0 <= read("laguna_load_max_over_mean") <= 4.0
    # every column of 64 rows and of the rings of 8 is gone over; the live
    # ones are the 20-48 positions of a sequence and its whole rings
    assert 1.2 < read("laguna_kv_rows_read_over_live") < 4.0
    gauges = run.obs["gauges"][-1]
    assert gauges["state_bytes_ring"] == 4 * 6 * 8 * 32 * 2
    assert gauges["state_bytes_rows"] == 4 * 4 * 64 * 32 * 2
    if trace:
        assert set(line["metrics"]) >= {
            "laguna_kv_rows_read_over_live", "laguna_tokens_per_held_expert",
            "laguna_load_max_over_mean", "slot_occupancy_pct",
            "completed_tokens_per_s"}
    else:
        assert set(line["metrics"]) == {"itl_ms_p90", "serve_tokens_per_s",
                                        "setup_s"}


# Faults planted in the timed path. Each takes `patch(owner, name, value)`
# (pytest's `monkeypatch.setattr`, or plain `setattr` for a run on the chip)
# so the same faults are read here at rehearsal size and there at the timed
# size.

def _config_from(change):
    from paddle_tpu.models import laguna

    real = laguna.LagunaConfig.from_hf.__func__
    return classmethod(
        lambda cls, m, router_experts=None, first_expert=0:
        real(cls, *change(m, router_experts, first_expert)))


def window_one_too_long(patch):
    """Window layers see 513 positions (and keep rings of 513) for 512."""
    from paddle_tpu.models import laguna

    patch(laguna.LagunaConfig, "from_hf", _config_from(
        lambda m, r, f: (dict(m, sliding_window=m["sliding_window"] + 1),
                         r, f)))


def attention_factor_dropped(patch):
    """YaRN's factor on cos and sin is left out."""
    from paddle_tpu.models import laguna

    def change(m, r, f):
        rope = {k: dict(v) for k, v in m["rope_parameters"].items()}
        rope["full_attention"]["attention_factor"] = 1.0
        return dict(m, rope_parameters=rope), r, f

    patch(laguna.LagunaConfig, "from_hf", _config_from(change))


def gate_dropped(patch):
    """The attention output goes into Wo ungated."""
    from paddle_tpu.models import laguna

    patch(laguna.layers, "sigmoid",
          lambda g: laguna.layers.scale(g, scale=0.0, bias=1.0))


def held_range_shifted_by_one(patch):
    """The layer believes it holds experts 1..held where it holds 0..held-1."""
    from paddle_tpu.models import laguna

    patch(laguna.LagunaConfig, "from_hf", _config_from(
        lambda m, r, f: (m, r, f + 1)))


def ring_written_one_row_on(patch):
    """The step writes a window layer's row at (pos + 1) mod window."""
    from paddle_tpu.models import laguna

    real = laguna.layers.elementwise_mod
    patch(laguna.layers, "elementwise_mod",
          lambda x, y: real(laguna.layers.scale(x, scale=1.0, bias=1.0), y))


FAULTS = {f.__name__: f for f in (
    window_one_too_long, attention_factor_dropped, gate_dropped,
    held_range_shifted_by_one, ring_written_one_row_on)}


@pytest.mark.parametrize("fault,by", [
    ("window_one_too_long", "window_gap"),
    ("attention_factor_dropped", "window_gap"),
    ("gate_dropped", "window_gap"),
    ("held_range_shifted_by_one", "routed_gap"),
    ("ring_written_one_row_on", "window_gap")])
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
    prompts and tokens its own first choice, its own held experts' parts
    and its own attention blocks in place of the system's. Each of the two
    per-layer numbers lies over its limit."""
    from benchmark.systems import laguna_decode_server as server

    run = make_run(CELL, seed=seed, seconds=2)
    run.traffic["check"]["sample_requests"] = 12
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["correct"] is True
    sut = harness.load_part("systems", run.config["system"])
    built = type("Sut", (), {})()
    built.model = server.reference_sizes(run.config)
    built.serving = run.config["serving"]
    built.cfg = server.model_config(built.model)
    run.compared = {}
    sut.check(run, built, control="float8")
    for name in ("routed_gap", "window_gap"):
        got = run.compared[name]
        assert got["value"] > got["limit"], run.compared


def test_the_readers_read_their_own_events_and_nothing_else(make_run):
    """`laguna_gmm_roofline_pct` and the step's share from a recorded
    table: the kernel's events by name, its calls counted from the traced
    executions of the step and the prefills; nothing on another
    configuration's run."""
    m, _ = published()
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    run = make_run(CELL)
    run.config = harness.resolve_cell(run.manifest, CELL, root=ROOT)[1]
    gmm = harness.load_part("metrics", "laguna_gmm_roofline_pct")
    step = harness.load_part("metrics", "laguna_step_roofline_pct")
    run.obs.update(
        gauges=[{"slot_utilization": 1.0}], prompt_lens=[4096],
        window_s=24.0, live_row_seconds=24.0 * 64 * 4700,
        counters={"steps": 10, "moe_experts_touched_sum": 10 * 4 * 58},
        trace={"ops": {"%fusion.1 = bf16[640,3072] fusion(": 9.0},
               "modules": {"jit_fwd_decode_step": {
                   "count": 4, "seconds": 0.08, "by_plane": {}}}})
    assert gmm.read(run) is None            # no such kernel in this program
    one = costs_laguna.grouped_products_min_seconds(m, 64, peaks, 58.0)
    run.obs["trace"]["ops"]["%gmm.3 = bf16[640,1024] custom-call("] = 0.02
    run.obs["trace"]["ops"]["%gmm = bf16[640,3072] custom-call("] = 0.02
    assert gmm.read(run) == pytest.approx(100 * 4 * 4 * one / 0.04)
    least = costs_laguna.step_min_seconds(m, 64, 64 * 4700, peaks, 58.0)
    assert step.read(run) == pytest.approx(100 * least / 0.02)
    assert 40 < step.read(run) < 60
    other = make_run("nemotron_h_chat_decode")
    other.obs.update(run.obs)
    assert gmm.read(other) is None and step.read(other) is None
    assert harness.load_part(
        "metrics", "laguna_kv_rows_read_over_live").read(other) is None
    # and the hybrid's readers find nothing in this cell's run
    assert harness.load_part("metrics", "gmm_roofline_pct").read(run) is None
