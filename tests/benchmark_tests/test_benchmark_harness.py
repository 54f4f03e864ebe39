"""The harness end to end at tiny sizes on the CPU: the command's line, its
refusals, a cell added as new files only, and `correct` coming out false
when the timed path is broken or computed in too low a precision."""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import ROOT

from benchmark import harness

CELLS = [c["name"] for c in harness.load_manifest(ROOT)["workloads"]]
CONTRACT_KEYS = ["correct", "attempted", "failed", "metrics", "device"]


def run_command(root, args, devices=1, pythonpath=None, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=%d" % devices)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("PYTHONPATH", None)
    if pythonpath:
        env["PYTHONPATH"] = pythonpath
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + args,
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)


def last_line(proc):
    return json.loads(proc.stdout.strip().splitlines()[-1])


# every cell untraced; one serving and one training cell traced as well
REHEARSALS = [(c, 0) for c in CELLS] + [(CELLS[0], 1), (CELLS[-1], 1)]


@pytest.mark.parametrize("cell,trace", REHEARSALS)
def test_rehearsal_prints_the_contract_line(cell, trace):
    """The command as the driver gives it, plus the rehearsal switch: exit
    0, the line's keys, the cell's metrics and no others."""
    manifest = harness.load_manifest(ROOT)
    chips = next(c["chips"] for c in manifest["workloads"]
                 if c["name"] == cell)
    p = run_command(ROOT, ["--workload", cell, "--seed", "3000000019",
                           "--seconds", "2", "--trace", str(trace),
                           "--rehearse-cpu"], devices=chips)
    assert p.returncode == 0, p.stderr[-2000:]
    doc = last_line(p)
    assert doc["rehearsal"] is True and "REHEARSAL" in p.stdout
    line = doc["would_print"]
    keys = list(line)
    assert keys[:5] == CONTRACT_KEYS and keys[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert set(line["device"]) >= {"platform", "kind", "count",
                                   "memory_peak_bytes"}
    group = "per_layer" if trace else "end_to_end"
    allowed = {m["name"]: m["unit"]
               for m in harness.metrics_for(manifest, group, cell)}
    assert line["metrics"], line
    for name, m in line["metrics"].items():
        assert allowed[name] == m["unit"] and np.isfinite(m["value"])
    if not trace:
        assert set(line["metrics"]) == set(allowed)
    else:
        assert "breakdown" in line
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]
    # each number compared stands beside its limit on the last stderr lines
    tail = p.stderr.strip().splitlines()[-len(line["compared"]):]
    assert all(t.startswith("compared ") and " limit " in t for t in tail)


def test_without_an_accelerator_there_is_no_result():
    p = run_command(ROOT, ["--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    assert p.returncode == harness.EXIT_NO_DEVICE and p.stdout == ""


def copy_benchmark(tmp_path):
    root = str(tmp_path / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    return root


@pytest.fixture(scope="module")
def checkout_with_kept_cells(tmp_path_factory):
    """A copy of the benchmark whose manifest has the entries of
    benchmark/kept_for_later.json moved back in, as a later PR would."""
    root = copy_benchmark(tmp_path_factory.mktemp("kept"))
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    kept = harness.load_json(os.path.join(root, "benchmark",
                                          "kept_for_later.json"))
    m["workloads"] += kept["workloads"]
    m["per_layer"] += kept["per_layer"]
    for e in m["end_to_end"] + m["per_layer"]:
        e.get("workloads", []).extend(
            c for c in kept["also_in"].get(e["name"], [])
            if c not in e["workloads"])
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))
    return root, kept


@pytest.mark.parametrize("trace", [0, 1])
def test_the_four_chip_cell_kept_for_later_still_rehearses(
        checkout_with_kept_cells, trace):
    root, kept = checkout_with_kept_cells
    cell = kept["workloads"][0]
    p = run_command(root, ["--workload", cell["name"], "--seed", "77",
                           "--seconds", "2", "--trace", str(trace),
                           "--rehearse-cpu"], devices=cell["chips"],
                    pythonpath=ROOT)
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["device"]["count"] == 4
    assert ("train_dispatch_ms" if trace else "train_tokens_per_s") in line[
        "metrics"]


def test_too_few_devices_for_the_cell_is_refused(checkout_with_kept_cells):
    root, kept = checkout_with_kept_cells
    p = run_command(root, ["--workload", kept["workloads"][0]["name"],
                           "--seed", "1", "--seconds", "1", "--trace", "0",
                           "--rehearse-cpu"], devices=1, pythonpath=ROOT)
    assert p.returncode == harness.EXIT_NO_DEVICE and "{" not in p.stdout


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    root = copy_benchmark(tmp_path)
    p = run_command(root, ["--workload", CELLS[0], "--seed", "1",
                           "--seconds", "1", "--trace", "0"])
    assert p.returncode != 0 and p.stdout == ""


def test_a_cell_added_as_new_files_only_runs_and_counts_its_failures(
        tmp_path):
    """A new configuration, traffic mix, per-layer metric and cell: four
    new files and new manifest entries, no edit to a file that was there.
    The mix offers far more than one slot and a queue of one can take, so
    requests are refused (429): they land in `failed`, the exit code stays
    0 and the tail says that the limit was missed."""
    root = copy_benchmark(tmp_path)
    before = {}
    for d, _, files in os.walk(os.path.join(root, "benchmark")):
        for f in files:
            path = os.path.join(d, f)
            before[path] = open(path, "rb").read()
    cfg = harness.load_json(os.path.join(
        root, "benchmark", "configs", "openai_gpt.json"))
    cfg["rehearsal"]["serving"] = {"slots": 1, "cache_len": 64,
                                   "queue_capacity": 1}
    json.dump(cfg, open(os.path.join(
        root, "benchmark", "configs", "tiny_gpt_one_slot.json"), "w"))
    tr = harness.load_json(os.path.join(
        root, "benchmark", "traffic", "doc_prefill_stratified.json"))
    tr["rehearsal"]["arrivals"] = {"rate_per_s": 150.0}
    tr["rehearsal"]["max_new_tokens"] = {"min": 8, "max": 16}
    json.dump(tr, open(os.path.join(
        root, "benchmark", "traffic", "overload.json"), "w"))
    with open(os.path.join(root, "benchmark", "metrics",
                           "shed_per_request.py"), "w") as f:
        f.write('"""Requests the engine refused at its queue, per request '
                'offered."""\n\n\ndef read(run):\n'
                '    c = run.obs.get("counters") or {}\n'
                '    n = run.obs.get("attempted")\n'
                '    return c.get("shed", 0) / n if n else None\n')
    m = harness.load_json(os.path.join(root, "BENCHMARK.json"))
    m["configs"].append({"name": "tiny_gpt_one_slot", "source": "test",
                         "file": "benchmark/configs/tiny_gpt_one_slot.json",
                         "reduced": [], "why": "test"})
    m["workloads"].append({"name": "gpt_overload", "chips": 1, "why": "test",
                           "config": "tiny_gpt_one_slot",
                           "traffic": "overload"})
    for e in m["end_to_end"]:
        if e["name"] in ("ttft_ms_p90", "itl_ms_p90"):
            e["workloads"].append("gpt_overload")
    m["per_layer"].append({
        "name": "shed_per_request", "unit": "1/request", "better": "lower",
        "source": "program_counter", "layer": "DecodeEngine admission",
        "moves": "ttft_ms_p90", "workloads": ["gpt_overload"]})
    json.dump(m, open(os.path.join(root, "BENCHMARK.json"), "w"))

    results = {}
    for trace in (0, 1):
        p = run_command(root, ["--workload", "gpt_overload", "--seed", "8",
                               "--seconds", "3", "--trace", str(trace),
                               "--rehearse-cpu"], pythonpath=ROOT)
        assert p.returncode == 0, p.stderr[-2000:]
        results[trace] = last_line(p)["would_print"]
    line = results[0]
    assert line["attempted"] > 50 and 0 < line["failed"] < line["attempted"]
    assert "status 429" in p.stderr
    assert set(line["metrics"]) == {"ttft_ms_p90", "itl_ms_p90", "setup_s"}
    # more than a tenth refused: the tail over all requests is lost, and
    # JSON has no number for that
    assert not np.isfinite(line["metrics"]["ttft_ms_p90"]["value"])
    assert results[1]["metrics"]["shed_per_request"]["value"] > 0
    for path, content in before.items():
        assert open(path, "rb").read() == content, path


def test_result_line_has_the_contract_keys_in_order():
    run = harness.Run(cell={"name": "c"}, seed=7)
    run.obs.update(attempted=4, failed=1)
    run.compared["gap"] = {"value": 0.1, "limit": 0.2}
    line = harness.result_line(
        run, {"setup_s": {"value": 1.0, "unit": "s"}},
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
         "memory_peak_bytes": 5}, True)
    assert list(line)[:5] == CONTRACT_KEYS and list(line)[-1] == "compared"
    assert "breakdown" not in line and line["attempted"] == 4
    traced = harness.result_line(run, {}, {}, False, {"device_ops": []})
    assert list(traced)[:6] == CONTRACT_KEYS + ["breakdown"]
    assert json.loads(json.dumps(line)) == line


class Counting:
    """Stands in for the harness's compile counter where a test drives
    harness.measure itself."""

    def snapshot(self):
        return {"requests": 0, "hits": 0, "misses": 0, "program": 0}


DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def test_a_training_step_that_drops_part_of_the_batch_is_not_correct(
        make_run, monkeypatch):
    """The look for a chip skipped, the rest of a run driven, with the timed
    path broken underneath: the step trains on the first three quarters of
    every batch."""
    from benchmark.systems import bert_fluid_trainer as sys_mod

    sound = make_run("bert_pretrain_s128", seed=11)
    sound.compiles = Counting()
    assert harness.measure(sound, dict(DEVICE))["correct"] is True

    real_step = sys_mod.Trainer.step

    def broken_step(self, feed):
        keep = feed["input_ids"].shape[0] * 3 // 4
        labels = feed["mlm_labels"].copy()
        labels[keep:] = -1            # the last quarter teaches nothing
        return real_step(self, dict(feed, mlm_labels=labels))

    monkeypatch.setattr(sys_mod.Trainer, "step", broken_step)
    run = make_run("bert_pretrain_s128", seed=11)
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["correct"] is False
    assert line["compared"]["loss_rel_gap"]["value"] > 0.05


def test_a_training_step_that_keeps_its_state_is_not_correct(
        make_run, monkeypatch):
    from benchmark.systems import bert_fluid_trainer as sys_mod

    def frozen(self):
        return {n: 0.0 for n in self.names}     # nothing moved

    monkeypatch.setattr(sys_mod.Trainer, "change_norms", frozen)
    run = make_run("bert_pretrain_s128", seed=12)
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["correct"] is False
    assert line["compared"]["change_norm_rel_gap"]["value"] == pytest.approx(
        1.0)


def test_a_served_token_altered_where_it_is_produced_is_not_correct(
        make_run, monkeypatch):
    from paddle_tpu.serving import decode

    real_emit = decode.DecodeStream._emit
    count = [0]

    def altered(self, tok):
        count[0] += 1
        return real_emit(self, (tok + 1) % 211 if count[0] % 7 == 0 else tok)

    monkeypatch.setattr(decode.DecodeStream, "_emit", altered)
    run = make_run("gpt_doc_prefill", seed=13, seconds=2)
    run.traffic["check"]["limits"]["logit_gap_sigma"] = 0.002
    run.compiles = Counting()
    line = harness.measure(run, dict(DEVICE))
    assert line["failed"] == 0 and line["correct"] is False
    assert line["compared"]["logit_gap_sigma"]["value"] > 0.002


@pytest.mark.parametrize("seed", [21, 22, 3000000023])
def test_training_control_in_float8_fails_a_limit(make_run, seed):
    """The control: the reference put in the program's place, computed in
    float8, the nearest precision below the bfloat16 the configuration
    states. It has to miss one of the cell's limits (not each)."""
    from benchmark import traffic as T
    from benchmark.reference import bert_mlm
    from benchmark.systems import bert_fluid_trainer as sys_mod

    run = make_run("bert_pretrain_s128", seed=seed)
    m, cfg = run.config["model"], run.config
    ring = T.train_ring(run.traffic, seed, m["vocab_size"], 1)[:3]
    w = bert_mlm.make_weights(m, seed)
    kw = dict(block_rows=cfg["check"]["block_rows"])
    want = bert_mlm.follow(w, ring, m, cfg["optimizer"], **kw)
    for precision, passes in (("bfloat16", True), ("float8", False)):
        got = bert_mlm.follow(w, ring, m, cfg["optimizer"],
                              precision=precision, **kw)
        run.compared = {}
        sys_mod.compare(run, got, want, cfg["check"]["limits"])
        ok = all(c["value"] <= c["limit"] for c in run.compared.values())
        assert ok is passes, (precision, run.compared)


@pytest.mark.parametrize("seed", [21, 22, 3000000023])
def test_training_with_dropout_on_is_told_from_its_control(make_run, seed):
    """Dropout on, as the cell runs it: the program's masks and the
    reference's are different draws, so the comparison carries their noise.
    At 128 rows of 32 tokens and 64 wide, the program read at most 0.13
    (gradient) and 0.07 (change) over these seeds and the float8 control at
    least 0.31 and 0.55, so this size's limits are 0.2; the cell's own
    stand in its configuration file, from readings on the chip."""
    from benchmark.drivers import train_loop
    from benchmark.systems import bert_fluid_trainer as sys_mod

    run = make_run("bert_pretrain_s128", seed=seed)
    run.config["model"].update(hidden_dropout_prob=0.1,
                               attention_probs_dropout_prob=0.1)
    run.traffic.update(rows_per_chip=128, seq_len=32)
    run.config["check"].update(block_rows=128, limits={
        "loss_rel_gap": 0.01, "grad_norm_rel_gap": 0.2,
        "change_norm_rel_gap": 0.2})
    sut = sys_mod.build(run)
    train_loop.warm(run, sut)
    sut.close()
    want = sys_mod.check(run, sut)
    assert all(c["value"] <= c["limit"] for c in run.compared.values()), (
        run.compared)
    # the masks did something: with dropout off the gaps are under 0.01
    assert run.compared["grad_norm_rel_gap"]["value"] > 0.01
    run.compared = {}
    sys_mod.compare(run, sys_mod.check(run, sut, precision="float8"), want,
                    run.config["check"]["limits"])
    assert run.compared["grad_norm_rel_gap"]["value"] > 0.2
    assert run.compared["change_norm_rel_gap"]["value"] > 0.2


def test_reference_dropout_is_inverted_and_drawn_from_its_key():
    import jax
    import jax.numpy as jnp

    from benchmark.reference import bert_mlm, blocks

    x = jnp.ones((200, 500))
    a = blocks.dropout(x, jax.random.PRNGKey(1), 0.1)
    b = blocks.dropout(x, jax.random.PRNGKey(2), 0.1)
    assert blocks.dropout(x, jax.random.PRNGKey(1), 0.0) is x
    assert np.allclose(np.unique(a), [0.0, 1 / 0.9])
    assert float((a == 0).mean()) == pytest.approx(0.1, abs=0.005)
    assert float(a.mean()) == pytest.approx(1.0, abs=0.01)
    assert not np.array_equal(a, b)
    assert np.array_equal(a, blocks.dropout(x, jax.random.PRNGKey(1), 0.1))
    # the reference trains under masks of its own seed: another seed,
    # another loss; the same seed, the same; with dropout off, no matter
    m = {"vocab_size": 64, "hidden_size": 16, "num_hidden_layers": 2,
         "num_attention_heads": 2, "intermediate_size": 32,
         "max_position_embeddings": 16, "hidden_dropout_prob": 0.1,
         "attention_probs_dropout_prob": 0.1}
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 64, (8, 16))
    batches = [(ids, np.where(rng.random((8, 16)) < 0.3, ids, -1))] * 2
    opt = {"learning_rate": 1e-3, "beta1": 0.9, "beta2": 0.999,
           "epsilon": 1e-8}
    w = bert_mlm.make_weights(m, 3)
    f = lambda m, seed: bert_mlm.follow(  # noqa: E731
        w, batches, m, opt, block_rows=4, mask_seed=seed)["loss"]
    assert f(m, 5) == f(m, 5) and f(m, 5) != f(m, 6)
    assert f(m, 5)[0] != f(m, 5)[1]          # each step draws anew
    off = dict(m, hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    assert f(off, 5) == f(off, 6) and f(off, 5)[0] != f(m, 5)[0]


def test_memory_peak_is_one_reading_not_a_sum_of_two_peaks(monkeypatch):
    """Live arrays peak in set-up, the programs' scratch later: the sampler
    reports the largest sum that was held at one instant."""
    import jax

    class Chip:
        def __init__(self, readings):
            self.readings = iter(readings)

        def memory_stats(self):
            in_use, reserved = next(self.readings)
            return {"bytes_in_use": in_use, "bytes_reserved": reserved,
                    "peak_bytes_in_use": 900, "peak_bytes_reserved": 800}

    chips = [Chip([(900, 0), (300, 800), (350, 800)]),
             Chip([(100, 0), (100, 100), (100, 100)])]
    monkeypatch.setattr(jax, "local_devices", lambda: chips)
    sampler = harness.MemorySampler(2)
    for _ in range(3):
        sampler.read()
    assert sampler.peak == {"bytes": 1150, "in_use": 350, "reserved": 800,
                            "readings": 3}

    class NoStats:
        def memory_stats(self):
            return None

    monkeypatch.setattr(jax, "local_devices", lambda: [NoStats()])
    with harness.MemorySampler(1, every_s=0.01) as s:
        time.sleep(0.05)
    assert s.peak["bytes"] == 0 and s.peak["readings"] >= 2


@pytest.mark.parametrize("seed", [31, 32, 3000000033])
def test_serving_control_in_float8_fails_the_limit(make_run, seed):
    """At each position of the same prompts and served tokens, the token
    float8 puts first lies further below the reference's best than any
    served token does. At this tiny size the sound reading is 0 (float32
    against float32), so the test's limit is a tenth of the smallest
    control reading seen at this size, 0.002."""
    from benchmark import traffic as T
    from benchmark.reference import gpt_lm

    run = make_run("gpt_batch_decode", seed=seed)
    m = run.config["model"]
    src = T.RequestSource(run.traffic, seed, m["vocab_size"],
                          run.config["serving"]["cache_len"])
    w = gpt_lm.make_weights(m, seed)
    prompts = [src.next() for _ in range(6)]
    served = []
    for r in prompts:          # greedy decoding by the reference itself
        toks = []
        for _ in range(r["max_new"]):
            seq = list(r["prompt"]) + toks
            x = gpt_lm.hidden_states(w, np.asarray(seq, np.int32), m,
                                     lambda a: a)
            toks.append(int(np.argmax(x[-1] @ w["gpt_out.w"]
                                      + w["gpt_out.b"])))
        served.append((list(r["prompt"]), toks))
    kw = dict(seq_len=run.config["serving"]["cache_len"], out_len=16)
    sound = max(g.max() for g in gpt_lm.served_gaps(w, served, m, **kw))
    control = max(g.max() for g in gpt_lm.served_gaps(
        w, served, m, control="float8", **kw))
    assert sound <= 0.002 < control
