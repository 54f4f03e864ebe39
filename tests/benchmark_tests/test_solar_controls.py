"""Every control of the Solar-Open2 cell's `check()` fails at the rehearsal
size: the reference computed in float8, or with ONE departure planted in it
(`solar_decode_server.FAULTS`), judged in the system's place over the same
prompts and tokens, reads `correct` false by the number that holds the
mechanism (others may join it). A control needs no system: its own first
choices, held experts' parts, blocks and states stand where the system's
would, so the finished requests are made here and nothing is served.

What the rehearsal reads over its limits (seed 41; x the limit): float8
`routed_gap` 1.4; `beta` not doubled `kda_gap` 3.5, `state_gap` 5.2; the
decay a mean over a head's channels 5.8 and 5.3; no decay 7.2 and 9.2; the
handed-over window one position on `kda_gap` 8.4 and nothing else (the
state a fill hands over is sound, the first steps after it are not); k not
normed 5.2 and 5.7; the held range shifted by one expert `routed_gap` 4.7;
the softmax layer's gate dropped `gqa_gap` 10.5."""
import numpy as np
import pytest

from conftest import ROOT  # noqa: F401

from benchmark.systems import solar_decode_server as server

CELL = "solar_doc_context_decode"
BY = {"float8": "kda_gap", "beta_not_doubled": "kda_gap",
      "decay_head_mean": "kda_gap", "alpha_one": "state_gap",
      "conv_window_shifted": "kda_gap", "k_norm_dropped": "kda_gap",
      "held_shifted": "routed_gap", "gqa_gate_dropped": "gqa_gap"}


def test_every_control_has_a_number_that_holds_it():
    assert set(BY) == set(server.CONTROLS)


@pytest.fixture
def finished_run(make_run):
    run = make_run(CELL, seed=41)
    rng = np.random.default_rng(41)
    bucket = max(run.traffic["prompt_buckets"])
    run.obs["finished"] = [
        {"index": i, "bucket": bucket,
         "prompt": rng.integers(1, 211, plen).tolist(),
         "tokens": rng.integers(1, 211, 14).tolist()}
        for i, plen in enumerate((29, 21))]
    sut = type("Sut", (), {})()
    sut.model = server.reference_sizes(run.config)
    sut.serving = run.config["serving"]
    return run, sut


@pytest.mark.parametrize("control", server.CONTROLS)
def test_a_control_is_not_correct(finished_run, control):
    run, sut = finished_run
    server.check(run, sut, control=control)
    got = run.compared[BY[control]]
    assert got["value"] > got["limit"], run.compared
    if control == "conv_window_shifted":
        # the window is handed over at the prompt's end: the state the fill
        # hands over and the prompt's own positions are sound
        assert run.compared["state_gap"]["value"] < 1e-6
