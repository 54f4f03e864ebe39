"""The span primitive's three sinks (ISSUE 25): the in-memory ring and its
read side, the profiler annotation, the hub histogram; the spans the decode
engine and the `:generate` handler record for every request; the phase
totals in `DecodeEngine.stats()`; the names Predictor programs carry in a
device trace; and the `trace` command reading the ring."""
import glob
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.models import gpt
from paddle_tpu.observability import tracing
from paddle_tpu.serving import (DecodeEngine, DecodeStream, ModelRegistry,
                                ServingServer)

PHASES = ("admit_seconds", "prefill_seconds_total", "dispatch_seconds",
          "sync_seconds", "emit_seconds", "release_seconds", "idle_seconds")


@pytest.fixture(autouse=True)
def _clean_ring():
    obs.reset()
    yield
    obs.reset()


# -- the ring ---------------------------------------------------------------

def test_span_lands_in_ring_with_parent_thread_and_fields():
    t_before = time.monotonic()
    with obs.span("outer", program=3):
        with obs.span("inner") as sp:
            sp.note(rows=5)
    inner, outer = obs.spans()
    assert (inner["name"], inner["parent"]) == ("inner", "outer")
    assert (outer["name"], outer["parent"]) == ("outer", None)
    assert inner["fields"] == {"rows": 5}
    assert outer["fields"] == {"program": 3}
    assert outer["thread"] == threading.current_thread().name
    assert t_before <= outer["t0"] <= inner["t0"] <= inner["t1"] \
        <= outer["t1"] <= time.monotonic()
    assert sp.seconds == pytest.approx(inner["t1"] - inner["t0"])
    # the histogram sink stays
    assert obs.histogram("span.inner.seconds")["count"] == 1


def test_spans_returns_copies_and_filters_by_name_and_start():
    for name in ("a", "b", "a"):
        with obs.span(name, k=1):
            time.sleep(0.001)
    first, second = obs.spans("a")
    assert [s["name"] for s in obs.spans(("a", "b"))] == ["a", "b", "a"]
    assert obs.spans("a", since=second["t0"]) == [second]
    assert obs.spans("a", until=second["t0"]) == [first]
    assert obs.spans(since=first["t0"], until=first["t0"]) == []
    first["fields"]["k"] = 99          # a copy: the ring is untouched
    assert obs.spans("a")[0]["fields"] == {"k": 1}


def test_ring_is_bounded_and_reset_clears_it():
    assert tracing.RING_LEN == 65536
    for i in range(tracing.RING_LEN + 10):
        obs.record_span("tick", float(i), float(i) + 0.5, i=i)
    held = obs.spans()
    assert len(held) == tracing.RING_LEN
    assert held[0]["fields"]["i"] == 10 and held[-1]["fields"]["i"] == \
        tracing.RING_LEN + 9
    obs.reset()
    assert obs.spans() == []


def test_record_span_crosses_threads():
    """A span that starts on one thread and ends on another: the start is
    handed over as a monotonic time."""
    t0 = time.monotonic()
    done = []

    def end():
        obs.record_span("decode.queue", t0, time.monotonic(), request=41)
        done.append(1)

    t = threading.Thread(target=end, name="other")
    t.start()
    t.join(5)
    assert done
    (s,) = obs.spans("decode.queue")
    assert s["t0"] == t0 and s["thread"] == "other" and s["parent"] is None
    assert s["fields"] == {"request": 41}
    assert obs.histogram("span.decode.queue.seconds")["count"] == 1


def test_an_exception_is_noted_on_the_span_and_not_swallowed():
    with pytest.raises(KeyError):
        with obs.span("boom"):
            raise KeyError("x")
    assert obs.spans("boom")[0]["fields"] == {"error": "KeyError"}
    assert obs.current_span() is None


def test_off_mode_records_nothing(monkeypatch):
    monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
    with obs.span("quiet", a=1) as sp:
        sp.note(b=2)
    obs.record_span("quiet.queue", 1.0, 2.0)
    monkeypatch.delenv(obs.TELEMETRY_ENV)
    assert obs.spans() == [] and sp.seconds == 0.0
    assert obs.histogram("span.quiet.seconds") is None


def test_trace_mode_still_writes_the_flight_recorder_event(monkeypatch):
    monkeypatch.setenv(obs.TELEMETRY_ENV, "trace")
    with obs.span("step", program=1):
        pass
    (ev,) = obs.get_recorder().of("span")
    assert ev["name"] == "step" and ev["program"] == 1
    assert len(obs.spans("step")) == 1


def test_a_sampled_span_exports_its_jsonl_record_from_the_same_exit(
        tmp_path, monkeypatch):
    monkeypatch.setenv(obs.TRACE_DIR_ENV, str(tmp_path))
    root = obs.TraceContext.new()
    with obs.span("http.generate", proc="http") as sp:
        ctx = sp.adopt(root)           # the context arrived in the body
        q = obs.record_span("decode.queue", time.monotonic() - 0.01,
                            time.monotonic(), ctx=ctx, request=1)
        with obs.span("decode.prefill", ctx=q, request=1):
            pass
    with obs.span("unsampled"):
        pass
    recs = {r["name"]: r for r in obs.read_spans(str(tmp_path))}
    assert set(recs) == {"http.generate", "decode.queue", "decode.prefill"}
    assert recs["http.generate"]["parent"] == root.span_id
    assert recs["decode.queue"]["parent"] == recs["http.generate"]["span"]
    assert recs["decode.prefill"]["parent"] == recs["decode.queue"]["span"]
    assert recs["decode.queue"]["dur"] == pytest.approx(0.01, abs=0.005)
    assert {s["name"] for s in obs.spans()} == set(recs) | {"unsampled"}


def test_crash_dump_carries_the_rings_tail(tmp_path):
    with obs.span("last.thing", step=9):
        pass
    path = obs.get_recorder().crash_dump(str(tmp_path / "dump.json"))
    doc = json.load(open(path))
    assert doc["spans"][-1]["name"] == "last.thing"
    assert doc["spans"][-1]["fields"] == {"step": 9}


# -- the profiler's clock ----------------------------------------------------

def test_a_span_under_a_profiler_session_is_in_the_xplane(tmp_path):
    """While jax's profiler runs, each span is written by the profiler
    itself as `paddle_tpu.<name>` on the line of the thread that ran it,
    with the fields it had at entry."""
    import jax
    from jax.profiler import ProfileData

    def work(request):
        with obs.span("decode.step.dispatch", request=request):
            with obs.span("decode.step.sync"):
                time.sleep(0.002)

    jax.profiler.start_trace(str(tmp_path))
    try:
        work(1)
        t = threading.Thread(target=work, args=(2,))
        t.start()
        t.join(10)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith("paddle_tpu."):
                    lines.setdefault((plane.name, i), []).append(
                        (ev.name, dict(ev.stats), ev.start_ns,
                         ev.duration_ns))
    assert len(lines) == 2             # one line per thread
    for events in lines.values():
        names = sorted(e[0] for e in events)
        assert names == ["paddle_tpu.decode.step.dispatch",
                         "paddle_tpu.decode.step.sync"]
        outer, inner = sorted(events, key=lambda e: e[2])
        assert outer[1].get("request") in (1, 2)
        # nested on one time axis, and at least the sleep long
        assert outer[2] <= inner[2] and inner[3] >= 2e6
        assert inner[2] + inner[3] <= outer[2] + outer[3]
    # the same spans are in the ring
    assert len(obs.spans("decode.step.sync")) == 2


# -- the decode engine and the HTTP handler ----------------------------------

@pytest.fixture(scope="module")
def tiny():
    from paddle_tpu.fluid import executor as executor_mod
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    cfg = gpt.gpt_tiny(vocab=97, max_len=128)
    gpt.build_gpt_lm(cfg, 16)
    scope = executor_mod.Scope()
    fluid.Executor(fluid.CPUPlace()).run(
        fluid.default_startup_program(), scope=scope)
    return {"cfg": cfg, "scope": scope}


def make_engine(tiny, **kw):
    kw.setdefault("auto_start", False)
    eng = DecodeEngine(tiny["cfg"], tiny["scope"], slots=2, cache_len=64,
                       prompt_buckets=(8, 16), name="span-eng", **kw)
    eng.warmup(check_hbm=False)
    return eng


def prompt(n):
    return (np.arange(n) % 90 + 1).astype("int64")


def test_every_request_has_queue_prefill_and_stream_under_one_id(tiny):
    eng = make_engine(tiny)
    eng.start()
    try:
        handles = [eng.submit(prompt(3 + 2 * i), max_new=4 + i)
                   for i in range(5)]
        for h in handles:
            assert len(h.result(60)) == h.max_new
        time.sleep(0.02)
    finally:
        eng.stop()
    ids = [h.id for h in handles]
    assert len(set(ids)) == 5
    by_name = {name: {} for name in ("decode.queue", "decode.prefill",
                                     "decode.stream")}
    for s in obs.spans(tuple(by_name)):
        assert s["fields"]["request"] not in by_name[s["name"]]
        by_name[s["name"]][s["fields"]["request"]] = s
    for h in handles:
        q, p, st = (by_name[n][h.id] for n in by_name)
        assert q["t0"] == h.t_submit and q["t1"] <= p["t0"]
        assert p["parent"] == "decode.loop.admit"
        assert p["fields"]["path"] == "cold"
        assert p["fields"]["plen"] == h.prompt_len
        assert p["fields"]["bucket"] == (8 if h.prompt_len <= 8 else 16)
        assert p["t0"] <= st["t0"] <= p["t1"] <= st["t1"]
        assert st["fields"]["tokens"] == h.max_new
        assert st["fields"]["reason"] == "length"
    # no span per token: the ring holds per-phase and per-request spans
    assert not obs.spans("decode.token")
    sync = obs.spans("decode.prefill.sync")
    assert len(sync) == 5 and {s["parent"] for s in sync} == {
        "decode.prefill"}
    st = eng.stats()
    assert st["tokens"] == sum(h.max_new for h in handles)
    assert obs.counter("serving.decode.tokens") == st["tokens"]
    steps = obs.spans("decode.step.dispatch")
    assert len(steps) == st["steps"] == len(obs.spans("decode.step.sync")) \
        == len(obs.spans("decode.step.decide")) \
        == len(obs.spans("decode.step.emit")) \
        == len(obs.spans("decode.step.release"))


def test_phase_totals_account_for_the_loop_threads_time(tiny):
    """admit + prefill + dispatch + sync + emit + release + idle, from
    the span exits, is the dispatch thread's wall time."""
    eng = make_engine(tiny)
    eng.start()
    try:
        eng.submit(prompt(4), max_new=3).result(60)   # one-off costs
        before, t_a = eng.stats(), time.monotonic()
        handles = [eng.submit(prompt(3 + i), max_new=20) for i in range(6)]
        for h in handles:
            h.result(60)
        time.sleep(0.3)                               # an idle stretch
    finally:
        eng.stop()                                    # joins the thread
    wall = time.monotonic() - t_a
    after = eng.stats()
    delta = {k: after[k] - before[k] for k in PHASES}
    assert all(v > 0 for v in delta.values()), delta
    assert sum(delta.values()) == pytest.approx(wall, rel=0.05)
    assert 0 < after["prefill_sync_seconds"] < after["prefill_seconds_total"]
    # the totals are the span exits': same seconds as the ring holds
    ring = sum(s["t1"] - s["t0"] for s in obs.spans("decode.step.sync"))
    assert after["sync_seconds"] == pytest.approx(ring)
    assert delta["idle_seconds"] >= 0.3


def test_step_seconds_runs_from_dispatch_to_sync_and_feeds_the_ledger(tiny):
    """A step's latency on the host: its dispatch, the delivery and release
    of the step before it (the loop is pipelined by one step), its sync."""
    eng = make_engine(tiny)
    eng.start()
    try:
        eng.submit(prompt(4), max_new=12).result(60)
    finally:
        eng.stop()
    st, h = eng.stats(), obs.histogram("serving.decode.step_seconds")
    assert h["count"] == st["steps"]
    both = st["dispatch_seconds"] + st["sync_seconds"]
    between = st["emit_seconds"] + st["release_seconds"]
    assert both <= h["sum"] <= both + between + 0.002 * st["steps"]
    from paddle_tpu.fluid import compile_cache

    fp = compile_cache.program_fingerprint(eng._step_pred.program)
    measured = [e["measured_step_seconds"]
                for e in obs.get_ledger().snapshot()["entries"]
                if e["fingerprint"] == fp]
    assert measured and all(m >= h["min"] for m in measured)


def test_off_mode_engine_serves_and_records_nothing(tiny, monkeypatch):
    monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
    eng = make_engine(tiny)
    eng.start()
    try:
        assert len(eng.submit(prompt(5), max_new=4).result(60)) == 4
    finally:
        eng.stop()
    monkeypatch.delenv(obs.TELEMETRY_ENV)
    assert obs.spans() == []
    st = eng.stats()
    assert st["tokens"] == 4 and all(st[k] == 0.0 for k in PHASES)


def test_generate_over_http_has_one_span_with_the_engines_id(tiny):
    eng = make_engine(tiny, auto_start=True)
    reg = ModelRegistry()
    reg.publish("gpt", eng)
    srv = ServingServer(reg).start()
    try:
        body = json.dumps({"prompt": prompt(6).tolist(),
                           "max_new_tokens": 5}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                srv.url + "/v1/models/gpt:generate", data=body),
                timeout=60) as r:
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        assert lines[-1]["done"] and lines[-1]["n_tokens"] == 5
        bad = urllib.request.Request(
            srv.url + "/v1/models/gpt:generate", data=b"{}")
        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(bad, timeout=60)
        time.sleep(0.05)
    finally:
        srv.stop()
        eng.stop()
    ok, refused = obs.spans("http.generate")
    f = ok["fields"]
    assert f["status"] == 200 and f["tokens"] == 5 and f["model"] == "gpt"
    (queue,) = obs.spans("decode.queue")
    (prefill,) = obs.spans("decode.prefill")
    (stream,) = obs.spans("decode.stream")
    assert f["request"] == queue["fields"]["request"] == \
        prefill["fields"]["request"] == stream["fields"]["request"]
    # the handler's span starts before the submit and ends after the
    # stream; its first byte left after the engine's first token
    assert ok["t0"] <= queue["t0"] and stream["t1"] <= ok["t1"]
    engine_ttft = prefill["t1"] - queue["t0"]
    assert 0 < f["first_byte_s"] <= ok["t1"] - ok["t0"]
    assert f["first_byte_s"] >= engine_ttft - (prefill["t1"] - stream["t0"])
    assert refused["fields"]["status"] == 400
    assert "request" not in refused["fields"]


# -- stream delivery: the reader's side of a stream (ISSUE 37) ----------------

def hand_fed(tokens=(), end=None, **kw):
    """A stream fed by hand, as the engine thread feeds one."""
    st = DecodeStream(4, 64, **kw)
    for tok in tokens:
        st._emit(tok)
    if end == "done":
        st._finish("length")
    elif end == "err":
        st._fail(RuntimeError("step failed"))
    return st


def the_read(request):
    (s,) = [s for s in obs.spans("decode.stream.read")
            if s["fields"]["request"] == request]
    return s, s["fields"]


def test_a_finished_streams_read_span_is_its_readers_account(tiny):
    eng = make_engine(tiny, auto_start=True)
    try:
        h = eng.submit(prompt(5), max_new=9)
        got = list(h.tokens(timeout=60))
    finally:
        eng.stop()
    s, f = the_read(h.id)
    wall = s["t1"] - s["t0"]
    assert got == h.so_far() and f["tokens"] == len(got) == 9
    assert f["end"] == "done"
    assert s["thread"] == threading.current_thread().name
    assert h.t_submit <= s["t0"] < s["t1"]
    assert 0 <= f["wake_max_s"] <= f["wake_s"]
    assert 0 <= f["wake_max_index"] <= 9    # the end is item 9
    assert f["consume_s"] >= 0
    assert f["wake_s"] + f["consume_s"] <= wall
    assert 0 <= f["cpu_s"] <= wall
    # the same id as the engine's side of the stream
    (stream,) = obs.spans("decode.stream")
    assert stream["fields"]["request"] == h.id


def test_a_slow_consumer_is_in_consume_s_and_the_three_parts_are_the_whole():
    """Every item is there before the reader starts, so it never waits on
    an empty queue: what is left of `t1 - t0` is wake + consume."""
    st = hand_fed(range(10), "done")
    for _ in st.tokens(timeout=5):
        time.sleep(0.01)
    s, f = the_read(st.id)
    wall = s["t1"] - s["t0"]
    assert f["tokens"] == 10 and f["end"] == "done"
    assert f["consume_s"] >= 10 * 0.01
    assert f["wake_s"] < 0.1 * f["consume_s"]
    assert f["wake_s"] + f["consume_s"] == pytest.approx(wall, rel=0.01)


def test_waiting_on_an_empty_queue_is_neither_wake_nor_consume():
    st = hand_fed()

    def feed():
        for tok in range(5):
            time.sleep(0.03)
            st._emit(tok)
        st._finish("length")

    t = threading.Thread(target=feed)
    t.start()
    assert list(st.tokens(timeout=5)) == list(range(5))
    t.join(10)
    assert not t.is_alive()
    s, f = the_read(st.id)
    wall = s["t1"] - s["t0"]
    assert wall >= 5 * 0.03
    assert f["wake_s"] + f["consume_s"] <= wall - 5 * 0.03 * 0.5


@pytest.mark.parametrize("end, yielded", [
    ("done", 3), ("err", 3), ("timeout", 3), ("closed", 2)])
def test_the_read_span_says_how_the_stream_ended(end, yielded):
    st = hand_fed(range(3), end if end in ("done", "err") else None)
    gen = st.tokens(timeout=0.05)
    got = []
    if end == "closed":
        got = [next(gen), next(gen)]
        time.sleep(0.02)
        gen.close()                      # the consumer leaves
    elif end == "done":
        got = list(gen)
    else:
        with pytest.raises(RuntimeError if end == "err" else TimeoutError):
            for tok in gen:
                got.append(tok)
    s, f = the_read(st.id)
    assert len(got) == yielded == f["tokens"] and f["end"] == end
    wall = s["t1"] - s["t0"]
    assert f["wake_s"] + f["consume_s"] <= wall
    if end == "closed":                  # it held the last token till then
        assert f["consume_s"] >= 0.02
        assert f["wake_s"] + f["consume_s"] == pytest.approx(wall, rel=0.01)
    if end == "timeout":                 # the wait that gave up is neither
        assert wall >= 0.05 > f["wake_s"] + f["consume_s"]
    assert len(obs.spans("decode.stream.read")) == 1


def test_a_stalled_stream_leaves_a_flight_recorder_event_with_every_threads_spans():
    st = hand_fed([7])
    inside, leave = threading.Event(), threading.Event()

    def engine_thread():
        with obs.span("decode.step.sync"):
            inside.set()
            leave.wait(10)

    t = threading.Thread(target=engine_thread, name="decode-stalled")
    t.start()
    try:
        assert inside.wait(10)
        with pytest.raises(TimeoutError, match="generated 1 so far"):
            list(st.tokens(timeout=0.05))
    finally:
        leave.set()
        t.join(10)
    (ev,) = obs.get_recorder().of("stream_stall")
    assert ev["request"] == st.id and ev["tokens"] == 1
    assert ev["source"] == "serving" and ev["waited_s"] == 0.05
    (frame,) = ev["active"]["decode-stalled"]
    assert frame[0] == "decode.step.sync" and frame[1] >= 0
    assert obs.counter("serving.stream_stall") == 1


def test_off_mode_streams_the_same_tokens_and_reads_no_clock(monkeypatch):
    want = list(hand_fed(range(6), "done").tokens(timeout=5))
    obs.reset()
    monkeypatch.setenv(obs.TELEMETRY_ENV, "off")
    st = hand_fed(range(6), "done")
    clock, me, reads = time.monotonic, threading.current_thread(), []

    def counted():
        if threading.current_thread() is me:
            reads.append(1)
        return clock()

    monkeypatch.setattr(time, "monotonic", counted)
    got = list(st.tokens(timeout=5))
    n_reads = len(reads)
    with pytest.raises(TimeoutError):
        list(hand_fed().tokens(timeout=0.01))
    monkeypatch.undo()
    assert got == want == list(range(6))
    assert n_reads == 0
    assert obs.spans() == [] and obs.get_recorder().of("stream_stall") == []


def test_http_generate_notes_what_the_socket_cost(tiny):
    eng = make_engine(tiny, auto_start=True)
    reg = ModelRegistry()
    reg.publish("gpt", eng)
    srv = ServingServer(reg).start()
    try:
        body = json.dumps({"prompt": prompt(6).tolist(),
                           "max_new_tokens": 7}).encode()
        with urllib.request.urlopen(urllib.request.Request(
                srv.url + "/v1/models/gpt:generate", data=body),
                timeout=60) as r:
            lines = [json.loads(ln) for ln in r.read().splitlines()]
        time.sleep(0.05)
    finally:
        srv.stop()
        eng.stop()
    assert lines[-1]["n_tokens"] == 7
    (http,) = obs.spans("http.generate")
    (read,) = obs.spans("decode.stream.read")
    f, r = http["fields"], read["fields"]
    assert f["chunks"] == f["tokens"] + 1 == 8
    assert 0 < f["write_s"] <= http["t1"] - http["t0"]
    # the handler's thread read the stream, under the request's one id
    assert r["request"] == f["request"] and read["thread"] == http["thread"]
    assert r["tokens"] == 7 and r["end"] == "done"
    assert http["t0"] <= read["t0"] and read["t1"] <= http["t1"]
    # the writes of the seven tokens lie inside what the consumer did
    # with them (the closing chunk's does not: the stream has ended)
    assert r["consume_s"] > 0 and f["write_s"] > 0


def test_stats_has_the_loop_threads_and_the_processs_cpu_both_growing(tiny):
    eng = make_engine(tiny, auto_start=True)
    try:
        eng.submit(prompt(4), max_new=3).result(60)
        before = eng.stats()
        t_a = time.monotonic()
        for h in [eng.submit(prompt(3 + i), max_new=20) for i in range(4)]:
            h.result(60)
        wall = time.monotonic() - t_a
        after = eng.stats()
    finally:
        eng.stop()
    loop = after["loop_cpu_seconds"] - before["loop_cpu_seconds"]
    proc = after["process_cpu_seconds"] - before["process_cpu_seconds"]
    assert 0 < loop <= wall * 1.05
    assert 0 < proc and loop <= proc * 1.05
    # the thread runs for less than its seven phases' wall time
    phases = sum(after[k] - before[k] for k in PHASES)
    assert loop <= phases * 1.05


# -- names a device trace is read by ------------------------------------------

def test_engine_programs_carry_module_names_that_all_hold_fwd(tiny):
    eng = make_engine(tiny)
    preds = [eng._step_pred] + [eng._prefill_preds[b] for b in (8, 16)]
    names = []
    for pred in preds:
        (compiled,) = pred._compiled.values()
        text = compiled.as_text()
        names.append(text.split("HloModule ", 1)[1].split(",", 1)[0])
    assert names == ["jit_fwd_decode_step", "jit_fwd_prefill_8",
                     "jit_fwd_prefill_16"]
    assert all("fwd" in n for n in names)
    eng.stop()


def test_a_disk_entry_is_not_served_under_another_name(tmp_path):
    from paddle_tpu.fluid import compile_cache, unique_name
    from paddle_tpu.fluid.inference import Predictor

    def build(name):
        unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 4])
            y = fluid.layers.fc(x, 3)
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        return Predictor(main, ["x"], [y], scope=scope, name=name)

    feed = {"x": np.ones((2, 4), "float32")}
    prev = compile_cache.activate(str(tmp_path / "cc"))
    try:
        assert build("decode_step").warm(feed) == "compile"
        assert build("decode_step").warm(feed) == "disk"
        assert build("prefill_8").warm(feed) == "compile"
        assert build(None).warm(feed) == "compile"
        assert build(None).warm(feed) == "disk"
    finally:
        compile_cache.activate(prev)


def test_each_lowered_op_is_inside_a_scope_named_for_it():
    import jax

    from paddle_tpu.fluid.lowering import build_step_fn

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.data("x", [None, 4])
        y = fluid.layers.fc(x, 3, name="encoder_layer_7_ffn")
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    state = {v.name: scope[v.name] for v in main.list_vars()
             if getattr(v, "persistable", False)}
    step = build_step_fn(main, ["x"], [y.name], is_test=True,
                         platform="cpu")
    text = jax.jit(step).lower(
        state, {"x": np.ones((2, 4), "float32")},
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    assert "mul/encoder_layer_7_ffn" in text


# -- the operator's command ---------------------------------------------------

def test_trace_command_reads_the_ring_from_a_crash_dump(tmp_path, capsys):
    from paddle_tpu.observability.__main__ import main

    with obs.span("decode.loop.admit"):
        with obs.span("decode.prefill", request=3, slot=0):
            pass
    obs.record_span("decode.queue", time.monotonic() - 0.2,
                    time.monotonic(), request=3)
    dump = obs.get_recorder().crash_dump(str(tmp_path / "dump.json"))
    out = str(tmp_path / "trace.json")
    assert main(["trace", dump, "-o", out]) == 0
    assert "3 spans" in capsys.readouterr().out
    events = [e for e in json.load(open(out))["traceEvents"]
              if e["ph"] == "X"]
    assert sorted(e["name"] for e in events) == [
        "decode.loop.admit", "decode.prefill", "decode.queue"]
    prefill = next(e for e in events if e["name"] == "decode.prefill")
    assert prefill["args"]["request"] == 3
    assert prefill["args"]["parent"] == "decode.loop.admit"
    # a file without spans, like a directory without any, is an error
    empty = tmp_path / "empty.json"
    empty.write_text("{}")
    assert main(["trace", str(empty), "-o", out]) == 1
