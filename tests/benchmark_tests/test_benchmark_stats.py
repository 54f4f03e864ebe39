"""The arithmetic of the metrics on hand-made samples, the reduction from a
recorded trace, and the functions that count operations and bytes."""
import json
import math
import os
import statistics
import threading

import pytest

from conftest import ROOT

from benchmark import costs, stats, trace
from benchmark.drivers import _http


def test_percentile_by_hand():
    assert stats.percentile([], 90) is None
    assert stats.percentile([7.0], 90) == 7.0
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile(list(range(1, 101)), 90) == pytest.approx(90.1)
    assert stats.percentile([4, 1, 3, 2], 100) == 4


def test_a_missed_request_misses_every_limit():
    assert stats.tail_ms([0.010] * 95, 90, missed=5) == 10.0
    assert math.isinf(stats.tail_ms([0.010] * 80, 90, missed=20))
    assert stats.tail_ms([0.010, 0.020], 50) == pytest.approx(15.0)


def test_rate_and_spread():
    assert stats.rate(1000, 4.0) == 250.0 and stats.rate(1, 0) is None
    vals = [100, 101, 99, 102, 98, 100.5]
    q = statistics.quantiles(vals, n=4)
    assert stats.spread(vals) == (q[2] - q[0]) / statistics.median(vals)


class FakeRun:
    def __init__(self):
        self.obs, self.traffic, self.trace, self.notes = {}, {}, False, []

    def note(self, text):
        self.notes.append(text)


def reduce_records(records, t0=100.0, seconds=10.0):
    load = _http.Load.__new__(_http.Load)
    load.run, load.records, load.gauges = FakeRun(), records, []
    load.t0, load.t_end = t0, t0 + seconds
    zero = {"tokens": 0, "steps": 0}
    return load._reduce(zero, zero), load.run.obs


def stream(index, due, first, n, gap, failed=False, stall_at=None,
           stall=0.0):
    times, t = [], first
    for j in range(n):
        if stall_at is not None and t >= stall_at:
            t, stall_at = t + stall, None
        times.append(t)
        t += gap
    return {"index": index, "due": due, "t_send": due + 0.001,
            "prompt": [1] * 10, "token_times": [] if failed else times,
            "tokens": [] if failed else [1] * n, "done": not failed,
            "failed": failed, "cut": False, "status": 200, "error": None}


def steady(stall=0.0):
    """100 requests, one every 90 ms, first token 40 ms after due, 20
    tokens 50 ms apart; optionally the server stops for `stall` seconds
    five seconds into the window."""
    return [stream(i, 100.0 + 0.09 * i, 100.04 + 0.09 * i, 20, 0.05,
                   stall_at=105.0 if stall else None, stall=stall)
            for i in range(100)]


def test_serving_metrics_by_hand():
    e2e, obs = reduce_records(steady())
    assert obs["attempted"] == 100 and obs["failed"] == 0
    assert e2e["ttft_ms_p90"] == pytest.approx(40.0)
    assert e2e["itl_ms_p90"] == pytest.approx(50.0)
    assert obs["tokens_received"] == 2000
    assert e2e["serve_tokens_per_s"] == pytest.approx(200.0)
    assert len(obs["gaps_s"]) == 100 * 19


def test_a_stall_in_the_window_moves_every_serving_metric():
    """No medians of chunks: one 3-second stall has to show in the rate and
    in both tails, each taken over all requests and gaps of the window."""
    calm, _ = reduce_records(steady())
    stalled, obs = reduce_records(steady(stall=3.0))
    assert stalled["serve_tokens_per_s"] < 0.95 * calm["serve_tokens_per_s"]
    assert stalled["ttft_ms_p90"] > 10 * calm["ttft_ms_p90"]
    # every gap of the window is in the sample, the stalled ones too; a 90th
    # percentile of all gaps moves once a tenth of them are caught
    assert stalled["itl_ms_p90"] >= calm["itl_ms_p90"]
    assert max(obs["gaps_s"]) == pytest.approx(3.05)
    longer, _ = reduce_records(
        [stream(i, 100.0, 100.04, 9, 0.05, stall_at=100.2, stall=3.0)
         for i in range(64)])
    assert longer["itl_ms_p90"] > 1000.0


def test_a_stall_moves_the_training_rate():
    steps, tokens = 160, 160 * 32768
    assert stats.rate(tokens, 40.0 + 3.0) < 0.94 * stats.rate(tokens, 40.0)
    assert stats.rate(tokens, 40.0) == steps * 32768 / 40.0


def test_failed_requests_are_numbers_not_exceptions():
    recs = steady()
    for i in range(0, 100, 5):          # every fifth request is refused
        recs[i] = stream(i, recs[i]["due"], 0, 0, 0, failed=True)
        recs[i]["status"] = 429
    e2e, obs = reduce_records(recs)
    assert obs["attempted"] == 100 and obs["failed"] == 20
    assert math.isinf(e2e["ttft_ms_p90"])      # 20% missed: the p90 is lost
    assert e2e["itl_ms_p90"] == pytest.approx(50.0)
    assert any("429" in n for n in obs and _notes(recs))


def _notes(recs):
    load = _http.Load.__new__(_http.Load)
    load.run, load.records, load.gauges = FakeRun(), recs, []
    load.t0, load.t_end = 100.0, 110.0
    load._reduce({"tokens": 0}, {"tokens": 0})
    return load.run.notes


def test_only_work_inside_the_window_counts():
    recs = [stream(0, 99.0, 99.5, 30, 0.05),        # started in the ramp
            stream(1, 109.9, 109.95, 30, 0.05)]     # runs past the end
    e2e, obs = reduce_records(recs)
    assert obs["attempted"] == 1                     # only one was due in it
    inside = sum(1 for r in recs for t in r["token_times"]
                 if 100.0 <= t <= 110.0)
    assert obs["tokens_received"] == inside and inside < 60


# -- the trace -------------------------------------------------------------

def small_trace():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "testdata", "small_trace.json")))


def test_union_and_uncovered():
    assert trace._union([(5, 9), (0, 3), (2, 4), (9, 10)]) == [[0, 4], [5, 10]]
    assert trace._uncovered(0, 10, [[2, 4], [8, 20]]) == 6
    assert trace.module_base("jit_fwd(142034)") == "jit_fwd"
    assert trace.is_collective("%all-reduce.7") and not trace.is_collective(
        "%fusion.3")


def test_reduction_of_the_recorded_trace():
    doc = small_trace()
    rows = [tuple(r) for r in doc["rows"]]
    red = trace.reduce(rows, doc["window_s"])
    # the real plane: three executions of one program; busy time is the
    # union of the kept operations, which never overlap on one core
    real = [r for r in rows if r[0] == "/device:TPU:0" and r[1] == "XLA Ops"]
    assert red["modules"]["jit_fwd"]["count"] == 3
    assert red["modules"]["jit_fwd"]["seconds"] == pytest.approx(
        sum(r[4] for r in rows if r[1] == "XLA Modules"
            and r[0] == "/device:TPU:0") * 1e-9)
    assert red["busy_s_by_plane"]["/device:TPU:0"] <= sum(
        r[4] for r in real) * 1e-9 + 1e-12
    assert 0 < red["busy_s_by_plane"]["/device:TPU:0"] < doc["window_s"]
    # the hand-made plane: 1000-5000 and 4000-7000 and 6000-10000 ns
    assert red["busy_s_by_plane"]["/device:TPU:1"] == pytest.approx(9e-6)
    assert red["modules"]["jit_step"] == {
        "count": 1, "seconds": pytest.approx(9e-6),
        "by_plane": {"/device:TPU:1": pytest.approx(9e-6)}}
    # the all-reduce runs 4000-7000, compute covers 4000-5000 and 6000-7000
    assert red["collective_s_by_plane"]["/device:TPU:1"] == pytest.approx(1e-6)
    assert red["collective_s_by_plane"]["/device:TPU:0"] == 0
    assert red["busy_s"] == pytest.approx(
        statistics.fmean(red["busy_s_by_plane"].values()))
    assert sum(red["ops"].values()) == pytest.approx(
        sum(r[4] for r in rows if r[1] == "XLA Ops") * 1e-9)
    # gaps of the busiest plane carry the host annotation that covers them
    assert set(red["idle_gaps"]) <= {"exe_run", "read_loss", "unannotated"}
    assert red["idle_gaps"]["read_loss"] > 0
    assert len(trace.top(red["ops"])) == 10
    assert trace.top({"a": 1.0, "b": 3.0}) == [["b", 3.0], ["a", 1.0]]


def test_the_window_is_never_shorter_than_the_devices_span():
    """Seen on four chips: 3.023 s of operations inside 3.001 s between the
    host's start and stop of the profiler; no idle share may come out
    negative."""
    rows = [("/device:TPU:0", "XLA Ops", "%fusion", 0, 2_000_000_000),
            ("/device:TPU:0", "XLA Ops", "%fusion", 2_000_000_000,
             1_023_000_000)]
    red = trace.reduce(rows, 3.001)
    assert red["window_s"] == pytest.approx(3.023)
    assert red["busy_s"] <= red["window_s"]
    assert trace.reduce(rows, 4.0)["window_s"] == 4.0


def test_a_trace_without_device_rows_gives_no_busy_time():
    red = trace.reduce([("/host:CPU", "python3", "bench.exe_run", 0, 5)], 1.0)
    assert "busy_s" not in red and red["modules"] == {}


def test_trace_readers_on_the_recorded_trace():
    from benchmark import harness

    doc = small_trace()
    red = trace.reduce([tuple(r) for r in doc["rows"]], doc["window_s"])
    cfg = harness.load_json(os.path.join(
        ROOT, "benchmark", "configs", "openai_gpt.json"))
    peaks = harness.load_json(os.path.join(
        ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    run = harness.Run(
        config={"model": cfg}, traffic={}, chips=1, peaks=peaks)
    run.obs.update(trace=red, window_s=10.0, live_row_seconds=64 * 100 * 10.0,
                   counters={"steps": 200, "prefills": 0, "tokens": 12800},
                   prompt_lens=[40])
    read = lambda n: harness.load_part("metrics", n).read(run)  # noqa: E731
    assert read("serve_dispatch_device_ms") == pytest.approx(
        1000 * red["modules"]["jit_fwd"]["seconds"] / 3)
    idle = read("serve_device_idle_pct")
    assert 0 < idle < 100
    roof = read("serve_roofline_pct")
    least = 200 * costs.gpt_step_min_seconds(cfg, 6400, peaks)
    assert roof == pytest.approx(
        100 * (least / 10.0) / (red["modules"]["jit_fwd"]["seconds"]
                                / doc["window_s"]))
    assert 0 < roof < 100
    assert read("dispatches_per_token") == pytest.approx(200 / 12800)


# -- operations and bytes ----------------------------------------------------

def test_bert_flops_per_token_by_hand():
    m = {"hidden_size": 768, "intermediate_size": 3072, "vocab_size": 30522,
         "num_hidden_layers": 12}
    per_layer = 2 * (4 * 768 * 768 + 2 * 768 * 3072) + 4 * 128 * 768
    assert costs.bert_train_flops_per_token(m, 128) == 3 * (
        12 * per_layer + 2 * 768 * 30522)
    # about 6 * parameters, the usual rule, within the attention term
    assert costs.bert_train_flops_per_token(m, 128) == pytest.approx(
        6 * (85e6 + 23.4e6), rel=0.05)


def test_gpt_bytes_and_least_times():
    m = {"n_embd": 768, "n_inner": 3072, "vocab_size": 40478, "n_layer": 12}
    peaks = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
    assert costs.gpt_weight_bytes(m) == pytest.approx(4 * 116.1e6, rel=0.01)
    empty = costs.gpt_step_min_seconds(m, 0, peaks)
    assert empty == pytest.approx(costs.gpt_weight_bytes(m) / 819e9)
    full = costs.gpt_step_min_seconds(m, 64 * 512, peaks)
    assert full - empty == pytest.approx(2.42e9 / 819e9, rel=0.01)
    # a batch-1 prefill of 320 tokens is bound by the weights' bytes
    assert costs.gpt_prefill_min_seconds(m, 320, peaks) == pytest.approx(
        empty)
    assert costs.gpt_prefill_min_seconds(m, 320, dict(
        peaks, hbm_bytes_per_s=1e15)) > 320 * 2 * 85e6 / 197e12
