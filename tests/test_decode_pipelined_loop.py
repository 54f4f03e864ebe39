"""The engine loop is software-pipelined by one step (ISSUE 28): after the
sync of step n the loop decides (next feeds, which slots finish), admits,
dispatches step n+1 and only then delivers step n's tokens, so the threads
a delivery wakes run while the device runs. CPU, test size: the served
tokens are those of one-at-a-time generation, nothing is lost or reordered
on any path that fails, retires or abandons streams, and the order shows
in the span ring. What only the chip can show (the period, the idle share)
is in PERF.md §5/§6."""
import queue
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid import resilience as R
from paddle_tpu.models import gpt, nemotron_h as nh
from paddle_tpu.serving import DecodeEngine, EngineClosedError
from paddle_tpu.serving import decode as decode_mod
from paddle_tpu.serving.prefix_pool import SessionTier

from benchmark.reference import nemotron_h_lm as hybrid_ref
from test_nemotron_h_serving import M as HYBRID

PHASES = ("admit_seconds", "prefill_seconds_total", "dispatch_seconds",
          "sync_seconds", "emit_seconds", "release_seconds", "idle_seconds")
LOOP_SPANS = ("decode.loop.admit", "decode.loop.idle", "decode.step.dispatch",
              "decode.step.sync", "decode.step.decide", "decode.step.emit",
              "decode.step.release")


@pytest.fixture(scope="module")
def models():
    """Seeded tiny weights of both served families, as plain scopes."""
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
    hcfg = nh.NemotronHConfig.from_hf(HYBRID, router_experts=32,
                                      first_expert=8)
    return {"gpt": (cfg, weights, 97),
            "hybrid": (hcfg, hybrid_ref.make_weights(HYBRID, 2147483659),
                       211)}


def engine(models, family="gpt", name="pipe", **kw):
    cfg, weights, _ = models[family]
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("prompt_buckets", (8, 16))
    return DecodeEngine(cfg, weights, name="%s-%s" % (name, family), **kw)


def prompt(n, vocab=97, seed=3):
    return np.random.default_rng(seed + n).integers(1, vocab, n).astype(
        "int64")


def drain(handle):
    """What the stream was handed, in order: [(kind, value), ...] (the
    time of each put, which rides beside them, dropped)."""
    got = []
    while True:
        try:
            got.append(handle._q.get_nowait()[:2])
        except queue.Empty:
            return got


def ends(handle):
    return [kind for kind, _ in drain(handle) if kind != "tok"]


# -- (a) the served tokens are those of one-at-a-time generation -----------

@pytest.mark.parametrize("family", ["gpt", "hybrid"])
def test_mixed_eos_and_reused_slots_bit_identical_to_one_at_a_time(
        models, family):
    vocab = models[family][2]
    eng = engine(models, family, "same")
    try:
        prompts = [prompt(n, vocab) for n in (3, 16, 9, 5, 12, 7)]
        lengths = [9, 4, 14, 1, 11, 6]
        alone = [eng.generate(p, max_new=n, timeout=60.0)
                 for p, n in zip(prompts, lengths)]
        # a token of the middle of a sequence as its EOS: it ends there
        eos = [None, None, alone[2][5], None, alone[4][3], None]
        want = [a if e is None else a[:a.index(e) + 1]
                for a, e in zip(alone, eos)]
        # six requests over two slots: every slot is reused, requests are
        # admitted into slots freed at a decide, next to live streams
        streams = [eng.submit(p, max_new=n, eos_id=e)
                   for p, n, e in zip(prompts, lengths, eos)]
        got = [s.result(60.0) for s in streams]
        st = eng.stats()
    finally:
        eng.stop(drain=False)
    assert got == want
    assert [s.finish_reason for s in streams] == [
        "length" if e is None else "eos" for e in eos]
    assert all(ends(s) == ["done"] for s in streams)
    assert st["steps_ahead"] > 0 and st["cache_copy_steps"] == 0


# -- (b) the order, from the span ring ------------------------------------

@pytest.fixture
def emit_times(monkeypatch):
    """Every token a stream is handed, with the time it was handed."""
    seen = []
    real = decode_mod.DecodeStream._emit

    def recording(self, tok):
        seen.append((self.id, time.monotonic()))
        return real(self, tok)

    monkeypatch.setattr(decode_mod.DecodeStream, "_emit", recording)
    return seen


def test_step_n_plus_1_is_dispatched_before_step_n_wakes_a_stream(
        models, emit_times):
    obs.reset()
    eng = engine(models, "gpt", "order", auto_start=False)
    eng.warmup(check_hbm=False)
    eng.start()
    try:
        h = eng.submit(prompt(5), max_new=12)
        assert len(h.result(60.0)) == 12
    finally:
        eng.stop()
    # token 0 is the prefill's; token j the j-th step's
    handed = [t for i, t in emit_times if i == h.id]
    dispatch = obs.spans("decode.step.dispatch")
    sync = obs.spans("decode.step.sync")
    assert len(handed) == 12 and len(dispatch) == len(sync) == 11
    for j in range(1, 11):
        assert sync[j - 1]["t1"] <= dispatch[j]["t1"] <= handed[j], j
        assert handed[j] <= sync[j]["t0"], j
    # the last step has no successor: delivered from its own decide
    assert sync[10]["t1"] <= handed[11]


def test_the_loops_spans_come_in_the_pipelined_order(models):
    obs.reset()
    eng = engine(models, "gpt", "ring", auto_start=False)
    eng.warmup(check_hbm=False)
    eng.start()
    try:
        streams = [eng.submit(prompt(4 + i), max_new=6 + 3 * i)
                   for i in range(3)]
        for s in streams:
            s.result(60.0)
    finally:
        eng.stop()
    names = [s["name"].rsplit(".", 1)[1] for s in sorted(
        obs.spans(LOOP_SPANS), key=lambda s: s["t0"])]
    turn = ["admit", "dispatch", "emit", "release", "sync", "decide"]
    first = names.index("dispatch")
    # the first step of a busy stretch has nothing to deliver
    assert names[first - 1:first + 3] == ["admit", "dispatch", "sync",
                                          "decide"]
    body = names[first + 3:]
    last = len(body) - body[::-1].index("decide")
    assert body[:last] == turn * (last // len(turn))
    # the last decide leaves no live slot: delivered and released before
    # the loop idles or returns
    assert body[last:last + 3] == ["admit", "emit", "release"]
    assert set(body[last + 3:]) <= {"idle", "admit"}


# -- (c) the counter that says it engages ---------------------------------

def test_steps_ahead_is_steps_less_the_first_of_each_busy_stretch(models):
    obs.reset()
    eng = engine(models, "gpt", "ahead", auto_start=False)
    eng.warmup(check_hbm=False)
    eng.start()
    try:
        for n in (5, 1, 8):          # max_new 1: a stretch with no step
            eng.submit(prompt(4), max_new=n).result(60.0)
            time.sleep(0.05)         # the loop idles between them
        pair = [eng.submit(prompt(6), max_new=7),
                eng.submit(prompt(3), max_new=4)]
        for s in pair:
            s.result(60.0)
    finally:
        eng.stop()
    st = eng.stats()
    spans = sorted(obs.spans(("decode.loop.idle", "decode.step.dispatch",
                              "decode.step.decide")), key=lambda s: s["t0"])
    after_idle = sum(
        1 for before, s in zip(spans, spans[1:])
        if s["name"] == "decode.step.dispatch"
        and before["name"] == "decode.loop.idle")
    assert st["steps"] == 4 + 7 + 6 and after_idle == 3
    assert st["steps_ahead"] == st["steps"] - after_idle
    assert obs.counter("serving.decode.steps_ahead") == st["steps_ahead"]


# -- (d) a dispatch that raises -------------------------------------------

def test_dispatch_fault_delivers_the_step_before_then_one_err(models):
    eng = engine(models, "gpt", "fault", auto_start=False)
    eng.warmup(check_hbm=False)
    prompts = [prompt(6), prompt(9)]
    try:
        # dispatches 1 and 2 go out, the third raises with the second
        # step's tokens decided and not yet delivered
        R.FaultInjector.install("dispatch:at=3:RuntimeError")
        try:
            # both queued before the loop runs: seated in its first turn
            streams = [eng.submit(p, max_new=8) for p in prompts]
            eng.start()
            for s in streams:
                with pytest.raises(RuntimeError):
                    s.result(60.0)
        finally:
            R.FaultInjector.uninstall()
        st = eng.stats()
        handed = [drain(s) for s in streams]
        # the loop goes on serving
        want = [eng.generate(p, max_new=8, timeout=60.0) for p in prompts]
    finally:
        eng.stop(drain=False)
    assert st["step_errors"] == 1 and st["steps"] == 2
    for s, got, w in zip(streams, handed, want):
        # the prefill's token and two steps', then exactly one err
        assert [kind for kind, _ in got] == ["tok"] * 3 + ["err"]
        assert [v for _, v in got[:3]] == s.so_far() == w[:3]


# -- (e) stop, drained and not --------------------------------------------

@pytest.mark.parametrize("drain_first", [True, False])
def test_stop_leaves_no_token_undelivered_and_no_stream_open(
        models, drain_first):
    eng = engine(models, "gpt", "stop-%d" % drain_first)
    try:
        prompts = [prompt(3 + i) for i in range(5)]
        want = [eng.generate(p, max_new=20, timeout=60.0) for p in prompts]
        streams = [eng.submit(p, max_new=20) for p in prompts]
        next(streams[0].tokens(timeout=60.0))    # generation is under way
    finally:
        eng.stop(drain=drain_first, timeout=60.0)
    assert not eng._outbox and eng._spent is None
    assert all(s is None for s in eng._slots)
    for s, w in zip(streams[1:], want[1:]):
        got = drain(s)
        kinds = [kind for kind, _ in got]
        assert kinds.count("done") + kinds.count("err") == 1
        assert kinds[-1] in ("done", "err")
        toks = [v for kind, v in got if kind == "tok"]
        assert toks == s.so_far() == w[:len(toks)]
        if drain_first:
            assert kinds[-1] == "done" and toks == w
        elif kinds[-1] == "err":
            assert isinstance(got[-1][1], EngineClosedError)
    assert streams[0].done and streams[0].so_far() == (
        want[0] if drain_first else want[0][:len(streams[0].so_far())])


# -- (f) hibernation at the decide ----------------------------------------

def test_hibernation_hands_over_what_a_lone_stream_hands_over(models):
    """A session that retires next to a live stream is read out of the
    cache at its decide, before the next step (which writes row 0 of every
    free slot) goes out: the same hand-over as from an engine in which
    nothing runs after it."""
    p = prompt(7)

    def hibernated(beside, name):
        tier = SessionTier(wire_dtype="fp32", name=name)
        eng = engine(models, "gpt", name, session_tier=tier)
        try:
            other = eng.submit(prompt(11), max_new=30) if beside else None
            toks = eng.submit(p, max_new=5, session="s").result(60.0)
            if other is not None:
                assert not other.done       # steps go on after the retire
                other.result(60.0)
            assert eng.stats()["hibernated"] == 1
        finally:
            eng.stop(drain=False)
        return toks, tier.peek("s")

    toks, alone = hibernated(False, "tier-alone")
    toks2, beside = hibernated(True, "tier-beside")
    assert toks == toks2
    for h in (alone, beside):
        h.verify()
        assert h.next_token == toks[-1] and h.plen == len(p) + 4
        assert list(h.prompt) == list(p) + toks[:-1]
    for a, b in zip(alone.dense(), beside.dense()):
        assert a.shape == b.shape
        assert np.array_equal(a[:, :alone.plen], b[:, :alone.plen])


# -- (g) the seven phase totals -------------------------------------------

def test_phase_totals_sum_to_the_threads_wall_time(models):
    """With a step of a chip's length (every dispatch stalled 5 ms at the
    chaos site): what no phase holds is the spans' own cost, about 60 us
    a turn. Held as what a shared CPU cannot bend: the loop's spans lie one
    after another on its thread and the seven totals are their sum, every
    second counted once. What lies BETWEEN two spans stretches when six
    test workers share the machine, so the share of the wall time that no
    phase holds (under 2% on an idle machine) has a loose bound."""
    obs.reset()
    eng = engine(models, "gpt", "phases", auto_start=False)
    eng.warmup(check_hbm=False)
    eng.start()
    R.FaultInjector.install("dispatch:every=1:slow=0.005")
    try:
        eng.submit(prompt(4), max_new=3).result(60.0)    # one-off costs
        before, t_a = eng.stats(), time.monotonic()
        streams = [eng.submit(prompt(3 + i), max_new=20) for i in range(6)]
        for s in streams:
            s.result(60.0)
        time.sleep(0.2)                                  # an idle stretch
    finally:
        R.FaultInjector.uninstall()
        eng.stop()                                       # joins the thread
    wall = time.monotonic() - t_a
    after = eng.stats()
    delta = {k: after[k] - before[k] for k in PHASES}
    assert all(v > 0 for v in delta.values()), delta
    assert delta["idle_seconds"] >= 0.2                  # the sleep, at least
    loop = obs.spans(LOOP_SPANS)
    assert all(a["t1"] <= b["t0"] for a, b in zip(loop, loop[1:]))
    assert sum(after[k] for k in PHASES) == pytest.approx(
        sum(s["t1"] - s["t0"] for s in loop))
    assert 0.75 * wall <= sum(delta.values())
    # emit is the deliveries and the decides: the ring holds the same
    ring = sum(s["t1"] - s["t0"] for s in obs.spans(
        ("decode.step.emit", "decode.step.decide")))
    assert after["emit_seconds"] == pytest.approx(ring)


# -- (h) a cancel between the decide and the delivery ----------------------

def test_cancel_between_decide_and_deliver_frees_the_slot_next_turn(
        models, monkeypatch):
    eng = engine(models, "gpt", "cancel", slots=1)
    want = eng.generate(prompt(5), max_new=12, timeout=60.0)
    real = DecodeEngine._decide
    hit = []

    def cancelling(self, slot, tok):
        delivery = real(self, slot, tok)
        h = delivery[0].handle
        if len(h._tokens) == 3 and not hit:
            # three tokens handed over, the fourth decided: cancel now
            hit.append(slot)
            h.cancel()
        return delivery

    monkeypatch.setattr(DecodeEngine, "_decide", cancelling)
    try:
        h = eng.submit(prompt(5), max_new=12)
        queued = eng.submit(prompt(8), max_new=3)
        assert h.result(60.0) == want[:4]
        assert h.finish_reason == "cancelled"
        assert ends(h) == ["done"]
        assert len(queued.result(60.0)) == 3
        st = eng.stats()
    finally:
        eng.stop(drain=False)
    # the sweep of the next turn freed the slot: no further step for it
    # (3 steps for its 4 tokens, 2 for the queued request's 3)
    assert hit and st["steps"] == 3 + 2 + 11
    assert st["cancelled"] == 1
