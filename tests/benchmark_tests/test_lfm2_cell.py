"""The LFM2-MoE training cell (`lfm2_moe_pretrain_s4096`) at rehearsal size
on the CPU: its cost functions against the issue's arithmetic, the printed
line of a plain and of a traced rehearsal, its readers on a run that has
what they read (and None where it has not), and `correct` coming out false
for the float8 control and for a held range shifted by one expert.

At this size (hidden 64, 3 blocks, 2 x 32 tokens) over five seeds the sound
program reads loss / gradient / change gaps of 0.0001-0.0003 / 0.0016-0.0077
/ 0.0011-0.0050, the float8 control 0.0017-0.0031 / 0.018-0.046 /
0.0068-0.014; the rehearsal's limits are 0.0009 / 0.015 / 0.02."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT

from benchmark import costs_lfm2, harness

CELL = "lfm2_moe_pretrain_s4096"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
NEW = ["lfm2_train_step_mfu_pct", "lfm2_gmm_roofline_pct",
       "lfm2_gmm_step_share_pct", "lfm2_flash_roofline_pct",
       "lfm2_tokens_per_held_expert", "lfm2_load_max_over_mean"]


class Counting:
    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


def published():
    from benchmark.systems import lfm2_fluid_trainer

    manifest = harness.load_manifest(ROOT)
    _, config, traffic = harness.resolve_cell(manifest, CELL, root=ROOT)
    return lfm2_fluid_trainer.sizes(config), config, traffic


def test_costs_follow_the_cut_written_in_the_issue():
    m, config, traffic = published()
    p = costs_lfm2.block_params(m)
    assert round(p["conv"] / 1e6, 2) == 16.78
    assert round(p["full_attention"] / 1e6, 2) == 10.49
    assert round(p["mlp"] / 1e6, 2) == 44.04
    assert round(8 * p["expert"] / 1e6, 2) == 88.08
    assert round(costs_lfm2.held_params(m) / 1e6, 1) == 507.8
    # the program's own parameters are the same count
    from paddle_tpu.models import lfm2
    import numpy as np

    cfg = lfm2.Lfm2Config.from_hf(m, router_experts=m["router_experts"])
    assert sum(int(np.prod(s)) for s in lfm2.param_shapes(
        cfg).values()) == costs_lfm2.held_params(m)
    # 16,384 tokens a step; under even routing a quarter of the 65,536
    # assignments of each of the four expert layers land here
    tokens = traffic["rows_per_chip"] * traffic["seq_len"]
    flops = costs_lfm2.step_flops(m, traffic["seq_len"], tokens,
                                  4 * tokens)
    assert round(flops / tokens / 1e9, 2) == 1.25       # GFLOP a token
    assert round(flops / 1e12, 1) == 20.4               # TFLOP a step
    experts = costs_lfm2.expert_flops(m, 4 * tokens)
    assert 0.19 < experts / flops < 0.23                # "about a fifth"
    peaks = harness.load_json(ROOT + "/benchmark/peaks.json")["TPU v5 lite"]
    # the grouped products are bound by arithmetic here, not by bytes
    least = costs_lfm2.grouped_products_min_seconds(m, tokens, peaks)
    assert least == pytest.approx(
        costs_lfm2.expert_flops(m, tokens) / 197e12)
    assert 0.005 < least < 0.006
    assert 0.0045 < costs_lfm2.flash_min_seconds(
        m, traffic["seq_len"], tokens, peaks) < 0.0052   # 7 x 137 GFLOP


def test_the_configuration_keeps_every_published_width():
    m, config, _ = published()
    assert (m["hidden_size"], m["intermediate_size"],
            m["moe_intermediate_size"], m["num_experts_per_tok"],
            m["num_attention_heads"], m["num_key_value_heads"],
            m["conv_L_cache"], m["router_experts"]) == (
                2048, 7168, 1792, 4, 32, 8, 3, 32)
    assert config["layer_types"] == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    kept = config["reduced_from"]["layer_types"]
    assert len(kept) == config["num_hidden_layers"] == 24
    # the dense layer counted once, then one whole period of the pattern
    assert config["layer_types"] == [kept[0]] + kept[2:6]
    assert config["vocab_size"] * 4 == config["reduced_from"]["vocab_size"]


def measure(make_run, seed, trace):
    run = make_run(CELL, seed=seed, seconds=2)
    run.trace = trace
    run.compiles = Counting()
    return run, harness.measure(run, dict(DEVICE))


@pytest.mark.parametrize("trace", [False, True], ids=["plain", "traced"])
def test_a_rehearsal_prints_a_whole_line_and_its_readers_read(make_run,
                                                              trace):
    run, line = measure(make_run, 3000000043, trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["workload"] == CELL
    assert set(line["compared"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                     "change_norm_rel_gap"}
    c = run.obs["counters"]
    assert c["steps"] == line["attempted"]
    # 64 tokens x top-4 over 8 experts, 4 held: half the 256 assignments
    # of each of the two expert layers under even routing
    assert 0.2 < c["moe_assignments_held"] / (c["steps"] * 2 * 256.0) < 0.8
    assert c["head_rows"] == c["steps"] * 2 * 31
    if not trace:
        assert set(line["metrics"]) == {"train_tokens_per_s", "setup_s"}
        return
    assert "breakdown" in line
    read = {n: harness.load_part("metrics", n).read(run) for n in NEW}
    # no device trace on the CPU: the four trace readers have nothing
    assert [read[n] for n in NEW[:4]] == [None] * 4
    assert 8.0 < read["lfm2_tokens_per_held_expert"] < 56.0   # even: 32
    assert 1.0 <= read["lfm2_load_max_over_mean"] <= 4.0
    assert {"lfm2_tokens_per_held_expert", "lfm2_load_max_over_mean",
            "train_dispatch_ms", "compile_s"} <= set(line["metrics"])
    assert not set(NEW[:4]) & set(line["metrics"])
    # and with a trace that holds the kernels, they read shares under 100
    run.obs["trace"] = {
        # 6.25 steps of 0.4 s, cut into 8 events at the trace's two ends
        "modules": {"jit_step": {"count": 8, "seconds": 2.5,
                                 "by_plane": {}}},
        "ops": {"%gmm.3": 0.12, "%tgmm.1": 0.06, "%flash_fwd.2": 0.05,
                "%flash_dq": 0.06, "%flash_dkdv.1": 0.07, "%fusion.9": 1.0}}
    full, _, traffic = published()
    run.config = dict(run.config, model=full,
                      layer_types=full["layer_types"])
    run.traffic = dict(run.traffic, seq_len=traffic["seq_len"])
    run.obs["tokens_per_step"] = 16384
    # 100 steps of 0.4 s by the host's clock, a quarter of the assignments
    # of four layers on the held experts in each
    run.obs.update(window_s=40.0, steps=100)
    run.obs["counters"] = dict(c, steps=100,
                               moe_assignments_held=100 * 4 * 16384)
    got = {n: harness.load_part("metrics", n).read(run) for n in NEW[:4]}
    assert got["lfm2_gmm_step_share_pct"] == pytest.approx(7.2)
    # the whole step's share of the peak is the window's, by the host's
    # clock: it needs no trace, and a stall in the window lowers it
    assert got["lfm2_train_step_mfu_pct"] == pytest.approx(
        100 * 20.43e12 / 0.4 / 197e12, rel=1e-2)
    run.obs["window_s"] = 50.0
    assert harness.load_part("metrics", NEW[0]).read(run) == pytest.approx(
        0.8 * got["lfm2_train_step_mfu_pct"])
    run.obs["window_s"] = 40.0
    # 6.25 steps x 4 layers x 5.49 ms over 180 ms of the two kernels
    assert got["lfm2_gmm_roofline_pct"] == pytest.approx(76.3, abs=0.5)
    assert 10 < got["lfm2_flash_roofline_pct"] < 100


def test_correct_is_false_for_the_control_and_for_a_shifted_held_range(
        make_run):
    from benchmark.systems import lfm2_fluid_trainer as system

    run, line = measure(make_run, 41, False)
    assert line["correct"] is True
    sound = {k: v["value"] for k, v in run.compared.items()}
    limits = run.config["check"]["limits"]

    def judged(got, want):
        run.compared = {}
        system.compare(run, got, want, limits)
        return {k: v["value"] for k, v in run.compared.items()}

    sut = type("S", (), {"model": system.sizes(run.config),
                         "optimizer": run.config["optimizer"]})
    run.compared = {}
    want = system.check(run, sut)
    # the reference in float8 in the program's place
    run.compared = {}
    control = judged(system.check(run, sut, precision="float8"), want)
    # by one of the limits, not by each: the loss hardly feels a precision
    assert control["grad_norm_rel_gap"] > limits["grad_norm_rel_gap"], control
    assert all(control[k] > 2 * sound[k] for k in limits), (control, sound)
    # the reference told another held range than the program computed
    sut.model = dict(sut.model, first_expert=sut.model["first_expert"] + 1)
    shifted = judged(run.obs["first_steps"], system.check(run, sut))
    assert any(shifted[k] > limits[k] for k in limits), shifted


def test_the_command_rehearses_the_cell_end_to_end():
    """`benchmark/run.py --rehearse-cpu --trace 1` as the driver would call
    it: exit 0, the rehearsal's line last on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", CELL, "--seed",
         "2147483659", "--seconds", "1.5", "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    line = out["would_print"]
    assert out["rehearsal"] is True and line["correct"] is True
    assert line["attempted"] >= 1 and line["failed"] == 0
    assert "lfm2_tokens_per_held_expert" in line["metrics"]
