"""`lfm2_expert_rows_covered_pct` (ISSUE 34): the sorted rows the gated
experts' loops covered (`moe_rows_covered`, counted on the device) over the
static bound of steps x expert layers x tokens a step x experts per token;
against recorded counters with and without the count (the parent's program
has none), and on the line of a traced rehearsal of the training cell."""
import pytest

from conftest import ROOT

from benchmark import harness
from test_lfm2_cell import CELL, measure, published

NAME = "lfm2_expert_rows_covered_pct"
M = harness.load_manifest(ROOT)
ENTRY = {m["name"]: m for m in M["per_layer"]}[NAME]
# a window of the cell as the ledger has it: 108 steps, 4 expert layers,
# about 16,400 of 65,536 assignments a layer on the 8 held experts
RECORDED = {"steps": 108, "moe_assignments_held": 108 * 4 * 16424,
            "moe_expert_load_max_sum": 108 * 4 * 2150,
            "moe_experts_touched_sum": 108 * 4 * 8,
            "head_rows": 108 * 16380, "head_chunks": 108 * 8}


def read(counters, tokens=16384):
    full, config, traffic = published()
    run = harness.Run(config=config, traffic=traffic, chips=1, peaks={})
    if counters is not None:
        run.obs.update(window_s=40.0, steps=counters.get("steps"),
                       tokens_per_step=tokens, counters=counters)
    return harness.load_part("metrics", NAME).read(run)


def test_the_entry_is_the_held_experts_counter_metric_of_the_training_cell():
    assert ENTRY == {
        "name": NAME, "unit": "%", "better": "lower",
        "source": "program_counter", "layer": "expert layer (held experts)",
        "moves": "train_tokens_per_s", "workloads": [CELL]}
    assert M["per_layer"][-1] is ENTRY      # appended, nothing moved
    assert ENTRY["layer"] in {m["layer"] for m in M["per_layer"][:-1]}


@pytest.mark.parametrize("counters, want", [
    # nine chunks of 2,048 over 16,424 held rows of 65,536
    (dict(RECORDED, moe_rows_covered=108 * 4 * 18432), 28.125),
    # five chunks of 4,096
    (dict(RECORDED, moe_rows_covered=108 * 4 * 20480), 31.25),
    # every chosen expert held: the loops cover the bound
    (dict(RECORDED, moe_rows_covered=108 * 4 * 65536), 100.0),
    (dict(RECORDED, moe_rows_covered=0), 0.0),
    # the parent's program counts three columns: nothing to read
    (RECORDED, None),
    (dict(RECORDED, steps=0, moe_rows_covered=0), None),
    ({}, None),
    (None, None),
])
def test_reader_is_the_share_of_the_static_bound_or_nothing(counters, want):
    got = read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_reader_reads_nothing_for_another_family():
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    run.obs.update(counters={"steps": 3, "moe_rows_covered": 9},
                   tokens_per_step=8)
    assert harness.load_part("metrics", NAME).read(run) is None


def test_a_traced_rehearsal_reports_it(make_run):
    """At rehearsal size one chunk holds an expert layer's whole bound (64
    tokens x 4 experts): every layer that got a row covers it all."""
    run, line = measure(make_run, 3000000071, True)
    assert line["correct"] is True and line["failed"] == 0
    c = run.obs["counters"]
    assert c["moe_rows_covered"] == c["steps"] * 2 * 256
    m = line["metrics"][NAME]
    assert m["unit"] == "%" and m["value"] == pytest.approx(100.0)
