"""BENCHMARK.json against the contract's rules of form, and every name in
it against the files the harness finds by that name."""
import json
import os
import re

import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|head_dim|"
                   r"_dim$|_rank$|expansion|experts_per_tok|n_embd|n_inner)")

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    M = json.load(f)
with open(os.path.join(ROOT, "benchmark", "kept_for_later.json")) as f:
    KEPT = json.load(f)     # entries a later PR moves back into the manifest
CELLS = [c["name"] for c in M["workloads"]]
LATER = CELLS + [c["name"] for c in KEPT["workloads"]]
E2E = [m["name"] for m in M["end_to_end"]]
PER_LAYER = [m["name"] for m in M["per_layer"]]


def one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_sizes():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(M["run_seconds"], int) and 1 <= M["run_seconds"] <= 51
    assert 1 <= len(M["command"]) <= 32 and all(map(one_line, M["command"]))
    assert 1 <= len(M["paths"]) <= 16
    for p in M["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= len(M["configs"]) <= 24 and 1 <= len(M["workloads"]) <= 24
    assert 1 <= len(M["end_to_end"]) <= 16 and 1 <= len(M["per_layer"]) <= 128


def test_command_names_only_files_under_paths():
    for word in M["command"][1:]:
        if "/" in word or word.endswith(".py"):
            assert any(word.startswith(p + "/") for p in M["paths"]), word
            assert os.path.exists(os.path.join(ROOT, word))


def test_names_are_unique():
    for group in ("configs", "workloads"):
        names = [e["name"] for e in M[group]]
        assert len(names) == len(set(names))
    metrics = E2E + PER_LAYER
    assert len(metrics) == len(set(metrics))
    pairs = [(c["config"], c["traffic"]) for c in M["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in M["configs"]]
    assert len(files) == len(set(files))


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"])
    assert one_line(config["source"]) and one_line(config["why"])
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    assert len(config["reduced"]) <= 16
    for key in config["reduced"]:
        assert NAME.match(key) and not WIDTH.search(key), key
    assert any(c["config"] == config["name"] for c in M["workloads"])
    doc = json.load(open(os.path.join(ROOT, config["file"])))
    assert doc["source"] == config["source"]
    # every key the file says it changed from the source is in `reduced`
    assert sorted(doc.get("reduced_from", {})) == sorted(config["reduced"])


@pytest.mark.parametrize("cell", M["workloads"] + KEPT["workloads"],
                         ids=lambda c: c["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["config"] in {c["name"] for c in M["configs"]}
    assert cell["chips"] in (1, 4) and one_line(cell["why"])


def test_four_chip_cells_within_quota():
    four = [c for c in M["workloads"] if c["chips"] == 4]
    assert len(four) <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better", "bound",
                                           "source"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1
    assert set(metric.get("workloads", CELLS)) <= set(CELLS)


def test_kept_entries_are_not_in_the_manifest_and_name_what_is():
    assert not set(LATER[len(CELLS):]) & set(CELLS)
    assert not {m["name"] for m in KEPT["per_layer"]} & set(PER_LAYER)
    assert set(KEPT["also_in"]) <= set(E2E + PER_LAYER)
    for cell in KEPT["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "benchmark", "traffic", cell["traffic"] + ".json"))


@pytest.mark.parametrize("metric", M["per_layer"] + KEPT["per_layer"],
                         ids=lambda m: m["name"])
def test_per_layer_entry(metric):
    assert set(metric) - {"workloads"} == {"name", "unit", "better",
                                           "source", "layer", "moves"}
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES and one_line(metric["layer"])
    moved = next(m for m in M["end_to_end"] if m["name"] == metric["moves"])
    cells = metric.get("workloads", CELLS)
    assert cells and set(cells) <= set(LATER)
    # every cell the metric is read in reports the metric it should move
    assert set(cells) <= set(moved.get("workloads", CELLS)
                             + KEPT["also_in"].get(moved["name"], []))
    if metric["name"].endswith("_roofline"):
        assert metric["unit"] == "%"


def test_setup_s_is_reported_everywhere():
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_reports_enough(cell):
    from benchmark import harness

    e2e = [m["name"] for m in harness.metrics_for(M, "end_to_end", cell)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert harness.metrics_for(M, "per_layer", cell)


@pytest.mark.parametrize("cell", CELLS)
def test_cell_files_resolve_by_name(cell):
    """Config, traffic, system, driver and reference are found by the names
    the manifest and the data files give, at full and rehearsal size."""
    from benchmark import harness

    for rehearse in (False, True):
        c, config, traffic = harness.resolve_cell(
            M, cell, root=ROOT, rehearse=rehearse)
        assert callable(harness.load_part("systems", config["system"]).build)
        driver = harness.load_part("drivers", traffic["driver"])
        assert callable(driver.warm) and callable(driver.window)
        assert harness.load_part("reference", config["reference"])
        assert config["model"] and "rehearsal" in config


@pytest.mark.parametrize("name",
                         PER_LAYER + [m["name"] for m in KEPT["per_layer"]])
def test_metric_has_a_reader_that_returns_nothing_for_nothing(name):
    """A reader that finds nothing to read returns None, never 0."""
    from benchmark import harness

    reader = harness.load_part("metrics", name)
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    if name != "compile_s":       # reads the program's hub, not the run
        assert reader.read(run) is None


def test_unknown_names_are_refused_not_guessed():
    from benchmark import harness

    with pytest.raises(SystemExit) as e:
        harness.resolve_cell(M, "no_such_cell", root=ROOT)
    assert e.value.code == harness.EXIT_MANIFEST
    with pytest.raises(SystemExit):
        harness.load_part("metrics", "no_such_metric")


def test_widest_cell_fits_the_time_limits():
    """A full check of 24 cells fits 43,200 s at this run length."""
    runs = 2 + 14 * 24
    assert runs * (M["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
