"""The per-layer metrics that read the program's own tracing (ISSUE 25):
rehearsed in every cell that lists them, on the recorded rows of a trace
whose programs carry their names, and against a program that has none of
what they read."""
import json
import os

import numpy as np
import pytest

from conftest import ROOT

from benchmark import costs, harness, trace
from test_benchmark_harness import last_line, run_command

M = harness.load_manifest(ROOT)
CELLS = [c["name"] for c in M["workloads"]]
NEW = ["engine_sync_wait_pct", "engine_dispatch_ms_per_step",
       "engine_release_ms_per_step",
       "engine_emit_ms_per_step", "engine_prefill_host_ms_mean",
       "queue_wait_ms_p90", "engine_ttft_ms_p90", "http_overhead_ms_p50",
       "serve_step_device_ms", "serve_prefill_device_ms",
       "serve_step_roofline_pct", "executor_feed_convert_ms",
       "executor_enqueue_ms", "executor_fetch_ms"]
ENTRY = {m["name"]: m for m in M["per_layer"]}


def test_the_new_metrics_are_entries_with_readers():
    assert set(NEW) <= set(ENTRY)
    for name in NEW:
        assert callable(harness.load_part("metrics", name).read)
    by_source = {}
    for name in NEW:
        by_source.setdefault(ENTRY[name]["source"], []).append(name)
    assert sorted(by_source) == ["device_trace", "program_counter",
                                 "program_span"]
    assert len(by_source["device_trace"]) == 3


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_rehearsal_reports_every_span_and_counter_metric(cell):
    """`--rehearse-cpu --trace 1`: each metric that reads the ring or the
    engine's phase totals is on the line of every cell that lists it, and
    finite. (A CPU trace has no TPU plane: the three `device_trace`
    readers are tried on recorded rows below.)"""
    p = run_command(ROOT, ["--workload", cell, "--seed", "3000000029",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["failed"] == 0
    wanted = [n for n in NEW if cell in ENTRY[n]["workloads"]
              and ENTRY[n]["source"] != "device_trace"]
    assert wanted
    for name in wanted:
        m = line["metrics"][name]
        assert m["unit"] == ENTRY[name]["unit"]
        assert np.isfinite(m["value"]) and m["value"] >= 0, (name, m)
    if "engine_sync_wait_pct" in wanted:
        assert line["metrics"]["engine_sync_wait_pct"]["value"] <= 100


def named_trace():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "testdata", "named_modules_trace.json")))


def traced_run():
    doc = named_trace()
    red = trace.reduce([tuple(r) for r in doc["rows"]], doc["window_s"])
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "openai_gpt.json"))
    peaks = harness.load_json(os.path.join(
        ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    run = harness.Run(config={"model": cfg}, traffic={}, chips=1,
                      peaks=peaks)
    run.obs.update(trace=red, window_s=10.0,
                   live_row_seconds=64 * 100 * 10.0,
                   counters={"steps": 200, "prefills": 30, "tokens": 12800},
                   prompt_lens=[300])
    return run, red, doc, cfg, peaks


def test_trace_readers_tell_the_step_from_the_prefills():
    run, red, doc, cfg, peaks = traced_run()
    mods = red["modules"]
    assert {"jit_fwd_decode_step", "jit_fwd_prefill_256"} <= set(mods)
    read = lambda n: harness.load_part("metrics", n).read(run)  # noqa: E731
    step = mods["jit_fwd_decode_step"]
    assert read("serve_step_device_ms") == pytest.approx(
        1000 * step["seconds"] / step["count"])
    prefills = [m for n, m in mods.items() if "fwd_prefill" in n]
    assert read("serve_prefill_device_ms") == pytest.approx(
        1000 * sum(m["seconds"] for m in prefills)
        / sum(m["count"] for m in prefills))
    least = 200 * costs.gpt_step_min_seconds(cfg, 6400, peaks)
    roof = read("serve_step_roofline_pct")
    assert roof == pytest.approx(
        100 * (least / 10.0) / (step["seconds"] / red["window_s"]))
    assert 0 < roof < 100
    # the accepted pooled readers still read every program: all names
    # keep "fwd"
    pooled = sum(m["seconds"] for m in mods.values()) / sum(
        m["count"] for m in mods.values())
    assert read("serve_dispatch_device_ms") == pytest.approx(1000 * pooled)
    assert read("serve_step_device_ms") != read("serve_dispatch_device_ms")


def test_trace_readers_find_nothing_in_a_trace_of_unnamed_programs():
    """The parent's programs are all `jit_fwd`: nothing to read, and not
    the program that happened to take most time."""
    run = traced_run()[0]
    small = json.load(open(os.path.join(
        ROOT, "benchmark", "testdata", "small_trace.json")))
    run.obs["trace"] = trace.reduce([tuple(r) for r in small["rows"]],
                                    small["window_s"])
    for name in ("serve_step_device_ms", "serve_prefill_device_ms",
                 "serve_step_roofline_pct"):
        assert harness.load_part("metrics", name).read(run) is None


@pytest.mark.parametrize("name", NEW)
def test_reader_gives_none_on_an_empty_run(name):
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    assert harness.load_part("metrics", name).read(run) is None


SPAN_READERS = [n for n in NEW if ENTRY[n]["source"] == "program_span"]


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_on_a_program_without_the_ring(name, monkeypatch):
    """Run on the parent commit's program there is no `obs.spans`: the
    reader returns None and does not raise."""
    from paddle_tpu import observability as obs

    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    run.obs.update(window_t0=0.0, window_s=1e12, counters={"steps": 3})
    monkeypatch.delattr(obs, "spans")
    assert harness.load_part("metrics", name).read(run) is None


def test_span_readers_cut_the_window_and_join_requests_by_id():
    from paddle_tpu import observability as obs

    obs.reset()
    t0 = 1000.0
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    run.obs.update(window_t0=t0, window_s=10.0)
    # ten requests in the window: queue i ms, prefill 5 ms, HTTP adds 2 ms
    for i in range(1, 11):
        q0 = t0 + i * 0.5
        q1 = q0 + i * 1e-3
        obs.record_span("decode.queue", q0, q1, request=i)
        obs.record_span("decode.prefill", q1, q1 + 5e-3, request=i)
        obs.record_span("http.generate", q0 - 1e-3, q1 + 0.2, request=i,
                        first_byte_s=1e-3 + (q1 - q0) + 5e-3 + 1e-3)
    # outside the window, a refused request, and one that never prefilled
    obs.record_span("decode.queue", t0 - 1.0, t0 - 0.5, request=99)
    obs.record_span("decode.queue", t0 + 11.0, t0 + 12.0, request=98)
    obs.record_span("http.generate", t0 + 1.0, t0 + 1.1, status=400)
    obs.record_span("decode.queue", t0 + 2.0, t0 + 2.001, request=97)
    for i, d in enumerate((1e-3, 2e-3, 9e-3)):
        obs.record_span("executor.fetch", t0 + i, t0 + i + d)
    obs.record_span("executor.fetch", t0 - 5.0, t0 - 4.0)
    read = lambda n: harness.load_part("metrics", n).read(run)  # noqa: E731
    try:
        assert read("queue_wait_ms_p90") == pytest.approx(9.0, abs=1e-6)
        assert read("engine_ttft_ms_p90") == pytest.approx(14.1, abs=1e-6)
        assert read("http_overhead_ms_p50") == pytest.approx(2.0, abs=1e-6)
        assert read("executor_fetch_ms") == pytest.approx(2.0, abs=1e-6)
        assert read("executor_enqueue_ms") is None
    finally:
        obs.reset()


def test_counter_readers_are_ratios_of_the_windows_differences():
    run = harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})
    run.obs.update(window_s=40.0, counters={
        "steps": 800, "prefills": 100, "dispatch_seconds": 4.0,
        "emit_seconds": 1.6, "release_seconds": 8.0, "sync_seconds": 16.0,
        "prefill_sync_seconds": 2.0, "prefill_seconds_total": 3.0})
    read = lambda n: harness.load_part("metrics", n).read(run)  # noqa: E731
    assert read("engine_dispatch_ms_per_step") == pytest.approx(5.0)
    assert read("engine_emit_ms_per_step") == pytest.approx(2.0)
    assert read("engine_release_ms_per_step") == pytest.approx(10.0)
    assert read("engine_sync_wait_pct") == pytest.approx(45.0)
    assert read("engine_prefill_host_ms_mean") == pytest.approx(10.0)
    # the parent's engine counts steps and prefills but no phase totals
    run.obs["counters"] = {"steps": 800, "prefills": 100}
    for name in ("engine_dispatch_ms_per_step", "engine_emit_ms_per_step",
                 "engine_release_ms_per_step", "engine_sync_wait_pct",
                 "engine_prefill_host_ms_mean"):
        assert read(name) is None
