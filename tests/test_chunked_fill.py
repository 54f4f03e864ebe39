"""A long prompt fills its slot in chunks with a decode step between chunks
(ISSUE 44): `DecodeModel.build_chunk` / `chunk_rows` (Solar-Open2's:
`models/solar_open2.py` `build_chunk`), the fill in progress that
`DecodeEngine` keeps (`serving/decode.py` `_Fill`), and the flash forward
kernel's query offset (`ops/pallas_attention.py`). CPU, tiny sizes, the
chunk 8 rows long (the model's run length set to 8 for this file): what a
chunked fill serves is what the one-shot bucket program serves, the
neighbour's tokens are its own, the record ends exactly once however the
request ends. What only the chip can show (the gaps between tokens) is in
PERF.md §5/§6."""
import queue
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.models import gpt
from paddle_tpu.models import solar_open2 as solar
from paddle_tpu.ops import pallas_attention as pa
from paddle_tpu.serving import (DeadlineExceededError, DecodeEngine,
                                EngineClosedError)

from benchmark.reference import solar_open2_lm as ref
from test_solar_open2 import CACHE_LEN, M

ROWS, BUCKET = 8, 32
PHASES = ("admit_seconds", "prefill_seconds_total", "dispatch_seconds",
          "sync_seconds", "emit_seconds", "release_seconds", "idle_seconds")
LOOP_SPANS = ("decode.loop.admit", "decode.loop.idle", "decode.step.dispatch",
              "decode.step.sync", "decode.step.decide", "decode.step.emit",
              "decode.step.release", "decode.prefill.chunk")


@pytest.fixture(scope="module")
def model():
    """Solar's tiny cut with runs, and so chunks, of 8 positions: a bucket
    of 32 is four runs of the one-shot program or up to four chunks."""
    was, solar.KDA_PROMPT_ROWS = solar.KDA_PROMPT_ROWS, ROWS
    cfg = solar.SolarOpen2Config.from_hf(M, router_experts=16, first_expert=4)
    yield cfg, ref.make_weights(M, 2147483693)
    solar.KDA_PROMPT_ROWS = was


def engine(model, name, **kw):
    cfg, w = model
    kw.setdefault("slots", 2)
    return DecodeEngine(cfg, w, cache_len=CACHE_LEN, prompt_buckets=[BUCKET],
                        name="chunked-" + name, adopt_params=True, **kw)


def prompt(n, seed=3):
    return np.random.default_rng(seed + n).integers(1, 211, n).astype("int64")


def ends(handle):
    """The ends the stream was handed (``done`` / ``err``), in order."""
    got = []
    while True:
        try:
            kind = handle._q.get_nowait()[0]
        except queue.Empty:
            return got
        if kind != "tok":
            got.append(kind)


@pytest.fixture(scope="module")
def served(model):
    """One started engine for the tests that only serve through it."""
    eng = engine(model, "served")
    eng.warmup(check_hbm=False)
    yield eng
    eng.stop(drain=False, timeout=10)


# -- (a) through the engine: the same tokens, the neighbour's own ------------
@pytest.mark.parametrize("plen,chunks", [(13, 2), (21, 3), (30, 4), (32, 4)])
def test_a_chunked_fill_serves_what_the_one_shot_fill_serves(served, plen,
                                                             chunks):
    """A prompt of 2, 3 and 4 chunks, the last ragged (and one that ends on
    a chunk's edge), filled beside a live stream: its greedy tokens are
    those of the bucket program's fill, and the neighbour's are those it
    gets with nothing filled beside it."""
    eng = served
    p, nb = prompt(plen), prompt(5, seed=11)
    alone = eng.generate(p, max_new=8, timeout=120)       # no slot live
    nb_alone = eng.generate(nb, max_new=40, timeout=120)
    before = eng.stats()
    neighbour = eng.submit(nb, max_new=40)
    assert next(neighbour.tokens(timeout=120)) == nb_alone[0]   # it is live
    beside = eng.submit(p, max_new=8).result(120)
    assert beside == alone
    assert neighbour.result(120) == nb_alone
    after = eng.stats()
    delta = {k: after[k] - before[k] for k in (
        "prefills", "chunked_fills", "fill_chunks", "prefill_rows_chunked",
        "prefill_rows_computed")}
    assert delta == {"prefills": 1, "chunked_fills": 1, "fill_chunks": chunks,
                     "prefill_rows_chunked": chunks * ROWS,
                     "prefill_rows_computed": BUCKET}
    assert after["cache_copy_steps"] == 0 and after["prefill_errors"] == 0


# -- (b) the carried state after the last chunk is the one-shot program's ----
@pytest.fixture(scope="module")
def programs(model):
    cfg, w = model

    def build(fn, *args):
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            v = fn(cfg, *args)
            return fluid.default_main_program(), v

    pprog, pv = build(solar.build_prefill, BUCKET, CACHE_LEN)
    cprog, cv = build(solar.build_chunk, ROWS, CACHE_LEN)
    prefill = Predictor(pprog, pv["feed_names"], pv["fetch_vars"], scope=w,
                        name="prefill_%d" % BUCKET)
    chunk = Predictor(cprog, cv["feed_names"], cv["fetch_vars"], scope=w,
                      name="chunk_%d" % ROWS,
                      donate_feeds=cv["cache_feed_names"])
    return prefill, pv, chunk, cv


@pytest.mark.parametrize("plen", [9, 16, 23, 32])
def test_the_state_after_the_last_chunk_is_the_one_shot_programs(
        model, programs, plen):
    """Chunk after chunk from the carried state against the bucket program
    over the whole prompt: the token, the K/V rows (zero past the prompt),
    the windows and the float32 delta-rule state within the tolerance of
    `kda_scan`'s two-halves test (tests/test_kda_ops.py: 1e-4); every
    carried array is donated and handed back."""
    cfg, _ = model
    prefill, pv, chunk, cv = programs
    p = prompt(plen, seed=5)
    ids = np.full((1, BUCKET), 7, np.int64)
    ids[0, :plen] = p
    want = prefill.run({pv["feed_names"][0]: ids,
                        pv["feed_names"][1]: np.asarray([[plen]])})
    decl = cfg.decode_model(CACHE_LEN).state
    assert cfg.decode_model(CACHE_LEN).chunk_rows == ROWS
    state = [jnp.zeros((1,) + tuple(e.shape), e.dtype) for e in decl]
    for at in range(0, plen, ROWS):
        n = min(ROWS, plen - at)
        part = np.full((1, ROWS), 7, np.int64)
        part[0, :n] = p[at:at + n]
        fed = state
        out = chunk.run(dict(zip(cv["feed_names"], [
            part, np.asarray([[n]]), np.asarray([[at]])] + state)),
            return_numpy=False)
        state = list(out[1:])
        assert all(a.is_deleted() for a in fed)
    assert int(np.asarray(out[0])[0, 0]) == int(want[0][0, 0])
    for e, got, one in zip(decl, state, want[1:]):
        got = np.asarray(got, np.float32)
        np.testing.assert_allclose(got, np.asarray(one, np.float32),
                                   atol=1e-4, err_msg=e.name)
        if e.kind == "rows":
            assert not got[0, plen:].any()


# -- (c) the flash forward kernel with a query offset -------------------------
def _qkv(tq, tk, seed=0, b=1, h=3, d=16):
    rng = np.random.default_rng(seed)
    return (jnp.asarray(rng.normal(size=(b, h, tq, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, h, tk, d)), jnp.float32),
            jnp.asarray(rng.normal(size=(b, h, tk, d)), jnp.float32))


@pytest.mark.parametrize("offset", [0, 16, 48])
def test_flash_attention_with_a_query_offset_is_the_references(offset):
    """A chunk of 32 queries that stand `offset` rows into 96 keys (0, one
    block, three blocks of 16), interpret mode, ONE jitted call for every
    offset: the reference's rows, and key tiles past the chunk's last query
    change nothing (they are never visited)."""
    q, k, v = _qkv(32, 96)
    call = jax.jit(lambda q, k, v, at: pa.flash_attention(
        q, k, v, causal=True, block_q=16, block_k=16, interpret=True,
        q_offset=at))
    got = call(q, k, v, jnp.int32(offset))
    # the reference's causal attention over the whole sequence, on the rows
    # at which the chunk's queries stand
    rows = slice(offset, offset + 32)
    whole = pa.reference_attention(
        jnp.zeros_like(k).at[:, :, rows].set(q), k, v, causal=True)
    assert float(jnp.max(jnp.abs(got - whole[:, :, rows]))) < 2e-5
    junk = k.at[:, :, offset + 32:].set(1e4)
    assert jnp.array_equal(call(q, junk, v, jnp.int32(offset)), got)


def test_a_call_without_offset_is_the_call_it_was():
    """No offset: the call is the kernel it was (`flash_fwd`, no offset
    operand; with one it is `flash_fwd_offset`) and its result the causal
    call's, which offset 0 gives too. An offset on a call that is not causal
    is refused."""
    q, k, v = _qkv(32, 32, seed=1)

    def lowered(**kw):
        return str(jax.make_jaxpr(lambda q, k, v: pa.flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16, interpret=True,
            **kw))(q, k, v))

    assert "flash_fwd_offset" in lowered(q_offset=jnp.int32(0))
    text = lowered()
    assert "flash_fwd_offset" not in text and "flash_fwd" in text
    got = pa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             interpret=True)
    at0 = pa.flash_attention(q, k, v, causal=True, block_q=16, block_k=16,
                             interpret=True, q_offset=jnp.int32(0))
    want = pa.reference_attention(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5
    assert float(jnp.max(jnp.abs(at0 - got))) < 1e-6
    with pytest.raises(ValueError, match="causal"):
        pa.flash_attention(q, k, v, q_offset=jnp.int32(0), interpret=True)


def test_the_op_places_a_chunks_queries_among_the_cached_rows():
    """`layers.gqa_attention(offset=)` on the CPU (the dense products): a
    chunk's queries against the rows so far are the rows of the causal call
    over the whole sequence."""
    from paddle_tpu.fluid import layers

    rng = np.random.default_rng(2)
    t, heads, kv_heads, dh = 24, 4, 2, 8
    q, k, v = (rng.normal(size=(1, t, n * dh)).astype("float32")
               for n in (heads, kv_heads, kv_heads))
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        qv = fluid.data("q", shape=[1, 8, heads * dh], dtype="float32")
        qa = fluid.data("qa", shape=[1, t, heads * dh], dtype="float32")
        kv = fluid.data("k", shape=[1, t, kv_heads * dh], dtype="float32")
        vv = fluid.data("v", shape=[1, t, kv_heads * dh], dtype="float32")
        at = fluid.data("at", shape=[1, 1], dtype="int64")
        part = layers.gqa_attention(qv, kv, vv, heads, kv_heads, offset=at)
        whole = layers.gqa_attention(qa, kv, vv, heads, kv_heads)
        pred = Predictor(fluid.default_main_program(),
                         ["q", "qa", "k", "v", "at"], [part, whole], scope={})
    for start in (0, 8, 16):
        got, want = pred.run({"q": q[:, start:start + 8], "qa": q, "k": k,
                              "v": v, "at": np.asarray([[start]])})
        np.testing.assert_allclose(got, want[:, start:start + 8], atol=1e-5)


# -- (d) when the engine chunks ------------------------------------------------
def test_with_no_live_slot_the_bucket_program_fills(served):
    eng = served
    before = eng.stats()
    eng.generate(prompt(30), max_new=2, timeout=120)
    eng.generate(prompt(9), max_new=2, timeout=120)
    after = eng.stats()
    assert after["prefills"] - before["prefills"] == 2
    assert after["prefill_rows_computed"] - before[
        "prefill_rows_computed"] == 2 * BUCKET
    for key in ("chunked_fills", "fill_chunks", "prefill_rows_chunked"):
        assert after[key] == before[key], key


def test_a_prompt_no_longer_than_a_chunk_is_not_cut(model):
    """Beside a live stream, in a bucket of one chunk's length: there is
    nothing to cut, the bucket program fills."""
    cfg, w = model
    eng = DecodeEngine(cfg, w, slots=2, cache_len=CACHE_LEN,
                       prompt_buckets=[ROWS, BUCKET], name="chunked-short",
                       adopt_params=True)
    try:
        neighbour = eng.submit(prompt(5, seed=11), max_new=30)
        next(neighbour.tokens(timeout=120))
        eng.submit(prompt(6), max_new=2).result(120)
        short = eng.stats()
        eng.submit(prompt(9), max_new=2).result(120)     # bucket 32: cut
        st = eng.stats()
        neighbour.result(120)
    finally:
        eng.stop(drain=False, timeout=10)
    assert short["prefills"] == 2 and short["chunked_fills"] == 0
    assert st["prefills"] == 2 and st["chunked_fills"] == 1


def test_a_model_without_a_chunk_builder_never_opens_a_record():
    """GPT declares none: beside a live stream its prompts still go through
    the bucket programs, and the engine built no chunk program."""
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 5
    cfg = gpt.gpt_tiny(vocab=97, max_len=64)
    gpt.build_gpt_lm(cfg, 16)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(
        fluid.default_startup_program(), scope=scope)
    weights = {n: np.asarray(scope[n]) for n in scope.keys()
               if n.startswith("gpt")}
    model = cfg.decode_model(64)
    assert model.build_chunk is None and model.chunk_rows is None
    eng = DecodeEngine(cfg, weights, slots=2, cache_len=64,
                       prompt_buckets=(8, 16), name="chunked-gpt")
    try:
        assert eng._chunk_pred is None
        opened = []
        real = DecodeEngine._fill_chunk
        DecodeEngine._fill_chunk = lambda self: opened.append(1) or real(self)
        try:
            neighbour = eng.submit(prompt(5, seed=11) % 97, max_new=30)
            next(neighbour.tokens(timeout=120))
            eng.submit(prompt(14) % 97, max_new=3).result(120)
            neighbour.result(120)
        finally:
            DecodeEngine._fill_chunk = real
        st = eng.stats()
    finally:
        eng.stop(drain=False, timeout=10)
    assert not opened and eng._fill is None
    assert st["prefills"] == 2
    assert st["chunked_fills"] == st["fill_chunks"] == 0
    assert st["prefill_rows_chunked"] == 0


# -- (e) a fill that does not reach its seat ends once and frees the slot -----
def open_fill(eng, plen=30, **submit):
    """Drive a never-started engine's loop by hand to the middle of a fill:
    a neighbour seated by the bucket program, then a long prompt's record
    opened and ONE chunk dispatched. -> (the neighbour's stream, the
    filling request's)."""
    neighbour = eng.submit(prompt(5, seed=11), max_new=30)
    eng._admit()
    assert eng._slots[0] is not None and eng._fill is None
    h = eng.submit(prompt(plen), max_new=4, **submit)
    eng._admit()
    assert eng._fill is not None and eng._fill.slot == 1
    eng._fill_chunk()
    assert eng._fill.at == ROWS and eng._slots[1] is None
    return neighbour, h


@pytest.mark.parametrize("how", ["cancel", "deadline", "abort", "stop",
                                 "chunk_raises"])
def test_a_fill_that_ends_midway_ends_once_and_frees_its_slot(model, how,
                                                              monkeypatch):
    eng = engine(model, "mid-" + how, auto_start=False)
    try:
        neighbour, h = open_fill(
            eng, deadline_ms=60000 if how == "deadline" else None)
        carried = list(eng._fill.state)
        third = eng.submit(prompt(12), max_new=2)       # waits its turn
        eng._admit()                                    # a fill is open
        assert eng._q.qsize() == 1
        if how == "cancel":
            h.cancel()
            eng._sweep_cancelled()
        elif how == "deadline":
            eng._fill.req.deadline = time.monotonic() - 1.0
            eng._sweep_cancelled()
        elif how == "abort":                # stop(drain=False), the thread's
            eng._abort = True
            eng._fail_all()
        elif how == "stop":                 # stop() with no thread to do it
            eng.stop(drain=False, timeout=1)
        else:
            def boom(feeds, return_numpy=True):
                raise RuntimeError("chunk failed")

            monkeypatch.setattr(eng._chunk_pred, "run", boom)
            eng._fill_chunk()
        assert eng._fill is None and eng._slots[1] is None
        del carried[:]                      # nothing else holds the arrays
        assert ends(h) == (["done"] if how == "cancel" else ["err"])
        assert h.done
        if how == "cancel":
            assert h.finish_reason == "cancelled" and h.result(1) == []
        else:
            want = {"deadline": DeadlineExceededError,
                    "chunk_raises": RuntimeError}.get(how, EngineClosedError)
            with pytest.raises(want):
                h.result(1)
        st = eng.stats()
        assert st["chunked_fills"] == 0 and st["fill_chunks"] == 1
        assert st["cancelled"] == (how == "cancel")
        assert st["deadline_miss"] == (how == "deadline")
        assert st["prefill_errors"] == (how == "chunk_raises")
        if how in ("cancel", "deadline", "chunk_raises"):
            # the slot is free: the request that waited takes it, chunked
            # in its turn beside the neighbour
            eng._admit()
            assert eng._fill is not None and eng._fill.slot == 1
            assert eng._fill.req.handle is third
        else:
            assert ends(neighbour) == ["err"] and ends(third) == ["err"]
    finally:
        eng.stop(drain=False, timeout=1)
    span, = [s["fields"] for s in obs.spans("decode.prefill")
             if s["fields"]["request"] == h.id]
    assert span["path"] == "chunked" and span["chunks"] == 1
    assert span.get("end") == "cancelled" or span.get("error")


@pytest.mark.parametrize("drain", [True, False])
def test_stop_in_the_middle_of_a_fill(model, drain, monkeypatch):
    """`stop()` while the dispatch thread has a fill open (its second chunk
    blocks until the stop has been asked for): drained, the fill goes on to
    its seat and the request gets all its tokens; not drained, the request
    fails once with the engine's closing error."""
    import threading

    eng = engine(model, "stop-%s" % drain)
    asked, in_fill = threading.Event(), threading.Event()
    real = DecodeEngine._fill_chunk

    def gated(self):
        if self._fill.at:                   # the second chunk on
            in_fill.set()
            asked.wait(30)
        real(self)

    try:
        want = eng.generate(prompt(30), max_new=4, timeout=120)
        monkeypatch.setattr(DecodeEngine, "_fill_chunk", gated)
        neighbour = eng.submit(prompt(5, seed=11), max_new=30)
        next(neighbour.tokens(timeout=120))
        h = eng.submit(prompt(30), max_new=4)
        assert in_fill.wait(60)
        stopper = threading.Thread(
            target=lambda: eng.stop(drain=drain, timeout=60))
        stopper.start()
        while not eng._stop_event.is_set():
            time.sleep(0.001)
        asked.set()
        stopper.join(90)
        assert not stopper.is_alive()
    finally:
        asked.set()
        eng.stop(drain=False, timeout=10)
    assert eng._fill is None and h.done
    if drain:
        assert h.result(1) == want and ends(h) == ["done"]
        assert len(neighbour.result(1)) == 30
        assert eng.stats()["chunked_fills"] == 1
    else:
        with pytest.raises(EngineClosedError):
            h.result(1)
        assert ends(h) == ["err"] and ends(neighbour) == ["err"]
        assert eng.stats()["chunked_fills"] == 0


# -- (f) counters, spans, the phase totals ------------------------------------
def test_the_chunk_spans_lie_under_the_requests_prefill_span(model):
    obs.reset()
    eng = engine(model, "spans", auto_start=False)
    try:
        neighbour, h = open_fill(eng, plen=21)
        eng._admit()                        # chunks to go: nothing happens
        assert eng._fill.at == ROWS
        eng._fill_chunk()
        eng._fill_chunk()
        assert eng._fill.at == 21 and eng._slots[1] is None
        eng._admit()                        # the last chunk is out: the seat
        assert eng._fill is None and eng._slots[1].handle is h
        assert int(eng._pos[1, 0]) == 21 and len(h.so_far()) == 1
        st = eng.stats()
    finally:
        eng.stop(drain=False, timeout=1)
    rid = h.id
    chunks = [s for s in obs.spans("decode.prefill.chunk")
              if s["fields"]["request"] == rid]
    assert [(s["fields"]["slot"], s["fields"]["start"], s["fields"]["rows"])
            for s in chunks] == [(1, 0, 8), (1, 8, 8), (1, 16, 5)]
    fills = [s for s in obs.spans("decode.prefill")
             if s["fields"]["request"] == rid]
    assert len(fills) == 1
    fill = fills[0]
    assert fill["fields"]["path"] == "chunked"
    assert fill["fields"]["chunks"] == 3 and fill["fields"]["plen"] == 21
    # from the first chunk to the seat
    assert fill["t0"] <= chunks[0]["t0"] and chunks[-1]["t1"] <= fill["t1"]
    assert st["chunked_fills"] == 1 and st["fill_chunks"] == 3
    assert st["prefill_rows_chunked"] == 3 * ROWS
    assert st["prefills"] == 1 and st["prefill_rows_computed"] == BUCKET
    # the chunks' dispatches and the seat are the fill's share of the total
    seat = obs.spans("decode.prefill.seat")
    first = [s for s in obs.spans("decode.prefill")
             if s["fields"]["request"] != rid]
    assert st["prefill_seconds_total"] == pytest.approx(sum(
        s["t1"] - s["t0"] for s in chunks + seat + first))


def test_a_sampled_requests_chunks_are_children_of_its_prefill_span(
        model, tmp_path, monkeypatch):
    import json

    from paddle_tpu.observability import distributed as dist

    monkeypatch.setenv("PADDLE_TPU_TRACE_DIR", str(tmp_path))
    obs.reset()
    eng = engine(model, "trace", auto_start=False)
    try:
        open_fill(eng, plen=13, trace_ctx=dist.TraceContext.new())
        eng._fill_chunk()
        eng._admit()
    finally:
        eng.stop(drain=False, timeout=1)
    recs = [json.loads(line) for f in tmp_path.iterdir()
            for line in f.read_text().splitlines()]
    fill = [r for r in recs if r["name"] == "decode.prefill"
            and r.get("args", {}).get("path") == "chunked"]
    assert len(fill) == 1
    kids = [r for r in recs if r["name"] == "decode.prefill.chunk"]
    assert len(kids) == 2
    assert all(r["parent"] == fill[0]["span"]
               and r["trace"] == fill[0]["trace"] for r in kids)
    queue_span = [r for r in recs if r["name"] == "decode.queue"
                  and r["trace"] == fill[0]["trace"]]
    assert fill[0]["parent"] == queue_span[0]["span"]


def test_phase_totals_still_sum_with_fills_in_chunks(model):
    """Chunked fills beside live streams, then an idle stretch: the seven
    phase totals sum to the dispatch thread's wall time (a chunked fill's
    share is its chunks' dispatches and its seat, not the steps between)."""
    from paddle_tpu.fluid import resilience as R

    obs.reset()
    eng = engine(model, "phases", auto_start=False)
    eng.warmup(check_hbm=False)
    eng.start()
    R.FaultInjector.install("dispatch:every=1:slow=0.005")
    try:
        neighbour = eng.submit(prompt(5, seed=11), max_new=4)
        eng.submit(prompt(30), max_new=3).result(120)    # one-off costs
        neighbour.result(120)
        before, t_a = eng.stats(), time.monotonic()
        streams = [eng.submit(prompt(9 + 4 * i), max_new=12)
                   for i in range(6)]
        for s in streams:
            s.result(120)
        time.sleep(0.2)
    finally:
        R.FaultInjector.uninstall()
        eng.stop()
    wall = time.monotonic() - t_a
    after = eng.stats()
    delta = {k: after[k] - before[k] for k in PHASES}
    assert after["chunked_fills"] - before["chunked_fills"] >= 4
    assert all(v > 0 for v in delta.values()), delta
    # as in `test_decode_pipelined_loop.py`: the thread's spans lie one
    # after another and the totals are their sum (a chunk goes out between
    # the admission and the step, under a span of its own); the share of
    # the wall time between two spans is the CPU's, and bounded loosely
    loop = obs.spans(LOOP_SPANS)
    assert all(a["t1"] <= b["t0"] for a, b in zip(loop, loop[1:]))
    assert sum(after[k] for k in PHASES) == pytest.approx(
        sum(s["t1"] - s["t0"] for s in loop))
    assert 0.75 * wall <= sum(delta.values())
