"""The eight readers of the stream-delivery instrumentation (ISSUE 37):
`decode.stream.read` (one span a stream, from the reader's thread),
`write_s` / `chunks` on `http.generate`, `loop_cpu_seconds` /
`process_cpu_seconds` in the engine's totals. Each against a program that
has none of what it reads (the parent's), on a hand-made ring and
counters, as an entry of the manifest, and on the line of a traced
rehearsal of each serving cell."""
import numpy as np
import pytest

from conftest import ROOT

from benchmark import harness
from test_benchmark_harness import last_line, run_command

M = harness.load_manifest(ROOT)
ENTRY = {m["name"]: m for m in M["per_layer"]}
SERVING = ["gpt_doc_prefill", "gpt_batch_decode", "nemotron_h_chat_decode",
           "laguna_code_context_decode"]
# name -> (source, layer, the reading of the hand-made run below)
READERS = {
    "stream_wake_ms_per_token": ("program_span", "stream delivery", 5.0),
    "stream_consume_ms_per_token": ("program_span", "stream delivery", 0.5),
    "http_write_ms_per_token": ("program_span", "HTTP + registry", 0.2),
    "stream_cpu_ms_per_step": ("program_span", "stream delivery", 3.2),
    "engine_loop_cpu_ms_per_step": ("program_counter", "DecodeEngine loop",
                                    2.5),
    "process_cpu_ms_per_step": ("program_counter", "service", 20.0),
    "stream_wake_ms_max": ("program_span", "stream delivery", 90.0),
    "engine_turn_ms_max": ("program_span", "DecodeEngine loop", 40.0),
}
T0 = 1000.0


def empty_run():
    return harness.Run(config={"model": {}}, traffic={}, chips=1, peaks={})


def read(name, run):
    return harness.load_part("metrics", name).read(run)


@pytest.fixture
def ring():
    from paddle_tpu import observability as obs

    obs.reset()
    yield obs
    obs.reset()


def hand_made(obs, new=True):
    """A window of 10 s: 400 steps of 64 tokens; three streams read in it,
    one before it; a loop that idles once. With `new` false, the spans and
    totals as the parent's program leaves them."""
    run = empty_run()
    run.obs.update(window_t0=T0, window_s=10.0, counters={
        "steps": 400, "tokens": 25600, "release_seconds": 4.4})
    if new:
        run.obs["counters"].update(loop_cpu_seconds=1.0,
                                   process_cpu_seconds=8.0)
    streams = [  # request, t0, tokens, wake, wake_max, consume, cpu, write
        (1, T0 + 1.0, 100, 0.50, 0.020, 0.050, 0.0050, 0.0202),
        (2, T0 + 2.0, 200, 1.00, 0.090, 0.100, 0.0100, 0.0402),
        (3, T0 + 9.9, 100, 0.50, 0.030, 0.050, 0.0050, 0.0202),
        (4, T0 - 0.5, 500, 9.00, 0.900, 9.000, 0.9000, 9.0000)]
    for rid, t0, n, wake, wmax, consume, cpu, write in streams:
        more, sock = {}, {}
        if new:
            more = dict(tokens=n, end="done", wake_s=wake, wake_max_s=wmax,
                        wake_max_index=7, consume_s=consume, cpu_s=cpu)
            sock = dict(write_s=write, chunks=n + 1)
            obs.record_span("decode.stream.read", t0, t0 + 3.0,
                            request=rid, **more)
        obs.record_span("http.generate", t0 - 1e-3, t0 + 3.0, request=rid,
                        tokens=n, status=200, **sock)
    obs.record_span("http.generate", T0 + 3.0, T0 + 3.1, status=400)
    # turns of 15 ms, one of 40 ms, an idle stretch of 2 s, and a turn of
    # 5 s before the window
    at = [T0 - 5.0, T0 + 0.0, T0 + 0.015, T0 + 0.030, T0 + 0.070,
          T0 + 0.085, T0 + 2.085, T0 + 2.100]
    for t in at:
        obs.record_span("decode.step.dispatch", t, t + 1e-3)
    obs.record_span("decode.loop.idle", T0 + 0.090, T0 + 2.080)
    return run


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_entry_names_the_layer_and_the_four_serving_cells(name):
    source, layer, _ = READERS[name]
    assert ENTRY[name] == {
        "name": name, "unit": "ms", "better": "lower", "source": source,
        "layer": layer, "moves": "itl_ms_p90", "workloads": SERVING}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_on_a_hand_made_ring_and_counters(name, ring):
    assert read(name, hand_made(ring)) == pytest.approx(
        READERS[name][2], rel=1e-9)


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader_gives_none_where_the_program_has_nothing_for_it(
        name, ring, monkeypatch):
    """An empty run; a window of the parent's program (its spans lack the
    fields, its totals the two CPU times); a program without the ring."""
    assert read(name, empty_run()) is None
    parent = hand_made(ring, new=False)
    if name == "engine_turn_ms_max":
        # the one reader whose spans the parent has had since PR 25
        assert read(name, parent) == pytest.approx(40.0)
        ring.tracing.clear_spans()
    assert read(name, parent) is None
    if READERS[name][0] == "program_span":
        monkeypatch.delattr(ring, "spans")
        assert read(name, hand_made(ring)) is None


def test_streams_without_tokens_and_windows_without_steps_read_nothing(ring):
    run = hand_made(ring)
    ring.tracing.clear_spans()
    ring.record_span("decode.stream.read", T0 + 1.0, T0 + 1.5, request=9,
                     tokens=0, end="timeout", wake_s=0.0, wake_max_s=0.0,
                     wake_max_index=None, consume_s=0.0, cpu_s=1e-4)
    ring.record_span("http.generate", T0 + 1.0, T0 + 1.5, request=9,
                     tokens=0, write_s=0.0, chunks=0)
    ring.record_span("decode.step.dispatch", T0 + 1.0, T0 + 1.001)
    for name in ("stream_wake_ms_per_token", "stream_consume_ms_per_token",
                 "stream_cpu_ms_per_step", "http_write_ms_per_token",
                 "engine_turn_ms_max"):
        assert read(name, run) is None
    assert read("stream_wake_ms_max", run) == 0.0
    run.obs["counters"]["steps"] = 0
    for name in ("engine_loop_cpu_ms_per_step", "process_cpu_ms_per_step",
                 "stream_cpu_ms_per_step"):
        assert read(name, run) is None


@pytest.mark.parametrize("cell", SERVING)
def test_a_traced_rehearsal_reports_all_eight(cell):
    p = run_command(ROOT, ["--workload", cell, "--seed", "3000000137",
                           "--seconds", "2", "--trace", "1",
                           "--rehearse-cpu"])
    assert p.returncode == 0, p.stderr[-2000:]
    line = last_line(p)["would_print"]
    assert line["correct"] is True and line["failed"] == 0
    got = {}
    for name in READERS:
        m = line["metrics"][name]
        assert m["unit"] == "ms"
        assert np.isfinite(m["value"]) and m["value"] >= 0, (name, m)
        got[name] = m["value"]
    # the loop's thread and the readers' threads are threads of the process
    assert got["engine_loop_cpu_ms_per_step"] > 0
    assert got["stream_cpu_ms_per_step"] > 0
    assert (got["engine_loop_cpu_ms_per_step"]
            + got["stream_cpu_ms_per_step"]) <= \
        got["process_cpu_ms_per_step"] * 1.05
    release = line["metrics"]["engine_release_ms_per_step"]["value"]
    assert got["engine_turn_ms_max"] >= release
