"""`engine_steps_ahead_pct` (ISSUE 28): the share of decode steps the
engine's pipelined loop dispatched ahead of the last step's delivery, read
from `steps_ahead` / `steps` of the window's counters; against a fabricated
`run.obs`, against a program without the counter (the parent's), and on the
line of a traced rehearsal of each serving cell."""
import numpy as np
import pytest

from conftest import ROOT

from benchmark import harness
from test_benchmark_harness import last_line, run_command

NAME = "engine_steps_ahead_pct"
M = harness.load_manifest(ROOT)
ENTRY = {m["name"]: m for m in M["per_layer"]}[NAME]
SERVING = ["gpt_doc_prefill", "gpt_batch_decode", "nemotron_h_chat_decode"]


def read(counters):
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    if counters is not None:
        run.obs.update(window_s=40.0, counters=counters)
    return harness.load_part("metrics", NAME).read(run)


def test_the_entry_is_the_loops_counter_metric_of_the_serving_cells():
    assert ENTRY == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "DecodeEngine loop",
        "moves": "itl_ms_p90", "workloads": SERVING}
    assert M["per_layer"][-1] is ENTRY      # appended, nothing moved


@pytest.mark.parametrize("counters, want", [
    ({"steps": 800, "steps_ahead": 792}, 99.0),
    ({"steps": 800, "steps_ahead": 0}, 0.0),
    ({"steps": 3, "steps_ahead": 3, "prefills": 9}, 100.0),
    # the parent's engine counts steps and no steps_ahead
    ({"steps": 800, "prefills": 100, "sync_seconds": 16.0}, None),
    # a window in which no step ran
    ({"steps": 0, "steps_ahead": 0}, None),
    ({"steps_ahead": 0}, None),
    ({}, None),
    (None, None),
])
def test_reader_is_the_ratio_or_nothing(counters, want):
    got = read(counters)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("cell", SERVING)
def test_a_traced_rehearsal_reports_it(cell):
    p = run_command(ROOT, ["--workload", cell, "--seed", "3000000071",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"][NAME]
    assert m["unit"] == "%"
    assert np.isfinite(m["value"]) and 0 < m["value"] <= 100
    if cell != "gpt_doc_prefill":       # closed loops: every slot live
        assert m["value"] > 80
