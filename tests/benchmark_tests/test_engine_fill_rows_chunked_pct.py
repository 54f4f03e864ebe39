"""`engine_fill_rows_chunked_pct` (ISSUE 44): the share of the window's
cold-fill rows that went into their slot in chunks with a decode step
between chunks (`prefill_rows_chunked`) and not through a bucket's one-shot
program (`prefill_rows_computed`); against fabricated counters, against a
program whose engine has no such counter (the parent's), and on the line of
a traced rehearsal of its cell, where no bucket is longer than a chunk and
the share is a 0.0 that is there."""
import numpy as np
import pytest

from conftest import ROOT

from benchmark import harness
from test_benchmark_harness import last_line, run_command

NAME = "engine_fill_rows_chunked_pct"
CELL = "solar_doc_context_decode"
M = harness.load_manifest(ROOT)
ENTRY = {m["name"]: m for m in M["per_layer"]}[NAME]


def read(counters):
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    if counters is not None:
        run.obs.update(window_s=40.0, counters=counters)
    return harness.load_part("metrics", NAME).read(run)


def test_the_entry_is_the_schedulers_counter_metric_of_solars_cell():
    assert ENTRY == {
        "name": NAME, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "DecodeEngine scheduling",
        "moves": "itl_ms_p90", "workloads": [CELL]}
    # appended behind everything PR 43 left, whose entries stand as they did
    names = [m["name"] for m in M["per_layer"]]
    assert names.index(NAME) > names.index("solar_load_max_over_mean")
    assert CELL in next(m for m in M["end_to_end"]
                        if m["name"] == "itl_ms_p90")["workloads"]


@pytest.mark.parametrize("counters, want", [
    # a window of the cell: 88 fills in 262 chunks of 4,096, one fill that
    # found no slot live and took the 16,384 program
    ({"prefill_rows_chunked": 262 * 4096, "prefill_rows_computed": 16384,
      "chunked_fills": 88, "prefills": 1}, 100.0 * 262 / 266),
    ({"prefill_rows_chunked": 4096, "prefill_rows_computed": 0}, 100.0),
    # an engine that counts them and chunked nothing: 0.0, not nothing
    ({"prefill_rows_chunked": 0, "prefill_rows_computed": 9 * 16384}, 0.0),
    ({"prefill_rows_chunked": 0, "prefill_rows_computed": 0}, 0.0),
    ({"prefill_rows_chunked": 0}, 0.0),
    # the parent's engine counts the bucket programs' rows alone
    ({"prefill_rows_computed": 9 * 16384, "prefills": 9}, None),
    ({}, None),
    (None, None),
])
def test_reader_is_the_share_of_the_rows_or_nothing(counters, want):
    got = read(counters)
    assert got is None if want is None else got == pytest.approx(want)


def test_a_traced_rehearsal_of_the_cell_reports_it():
    p = run_command(ROOT, ["--workload", CELL, "--seed", "3000000097",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["failed"] == 0
    m = line["metrics"][NAME]
    assert m["unit"] == "%" and np.isfinite(m["value"])
    # the rehearsal's one bucket (32) is shorter than the model's chunk
    # (4,096): every fill takes the bucket program
    assert m["value"] == 0.0
