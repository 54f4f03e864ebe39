"""Decode-native serving (ISSUE 9): slotted KV-cache DecodeEngine with
continuous batching, streaming handles, admission control, the HTTP
chunked ``:generate`` endpoint, and the analyzer/compile-cache wiring.

Exactness bar: every token streamed out of the engine — mixed prompt
lengths sharing one slot batch, requests admitted into freed slots
mid-generation — must be BIT-identical to a solo
``build_gpt_generate`` greedy run of the same prompt (row-independent
ops + per-slot masks; see tests/test_gpt.py for the program-level
proof)."""
import json
import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.serving import (
    DeadlineExceededError, DecodeEngine, EngineClosedError, ModelRegistry,
    ServingServer, ShedError,
)


@pytest.fixture(scope="module")
def m():
    """One trained tiny GPT + a 2-slot DecodeEngine behind an HTTP
    server, shared by the module (the engine snapshots params at
    construction, so later scope churn cannot drift it)."""
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    cfg = gpt.gpt_tiny(vocab=97, max_len=256)
    vs = gpt.build_gpt_lm(cfg, 16)
    fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
    for _ in range(30):
        exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                fetch_list=[vs["loss"]])
    eng = DecodeEngine(cfg, fluid.global_scope(), slots=2, cache_len=64,
                       prompt_buckets=(8,), name="gpt-dec",
                       queue_capacity=64)
    reg = ModelRegistry()
    reg.publish("gpt", eng)
    srv = ServingServer(reg).start()
    yield {"cfg": cfg, "exe": exe, "eng": eng, "reg": reg, "srv": srv,
           "scope": fluid.global_scope()}
    srv.stop()
    eng.stop(drain=False)


def _solo(m, prompt, n_new):
    """Reference: solo build_gpt_generate greedy tokens for `prompt`."""
    from paddle_tpu.fluid import unique_name

    g_prog, g_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_prog, g_st), unique_name.guard():
        gen = gpt.build_gpt_generate(m["cfg"], len(prompt), n_new,
                                     mode="greedy")
    # run against the fixture's trained scope: the conftest autouse
    # fixture swaps in a fresh (empty) global scope per test
    out = np.asarray(m["exe"].run(
        g_prog, feed={"gpt_prompt": np.asarray(prompt).reshape(1, -1)},
        fetch_list=[gen["ids"]], scope=m["scope"])[0])
    return [int(t) for t in out[0, len(prompt) - 1:]]


def _prompt(n, seed=11):
    rng = np.random.default_rng(seed + n)
    return rng.integers(1, 97, n).astype("int64")


# ---------------------------------------------------------------------------
# engine: continuous batching semantics
# ---------------------------------------------------------------------------

def test_mixed_concurrent_streams_bit_identical_to_solo(m):
    """6 concurrent clients, prompt lengths 3/6/8 interleaved through 2
    slots over HTTP chunked streaming: every stream must equal the solo
    generate of its prompt token-for-token."""
    import urllib.request

    lens = (3, 6, 8)
    n_new = 12
    results, errors = {}, []

    def client(cid):
        plen = lens[cid % len(lens)]
        body = json.dumps({"prompt": _prompt(plen).tolist(),
                           "max_new_tokens": n_new}).encode()
        req = urllib.request.Request(
            m["srv"].url + "/v1/models/gpt:generate", data=body,
            headers={"Content-Type": "application/json"})
        try:
            toks = []
            with urllib.request.urlopen(req, timeout=120) as resp:
                assert resp.status == 200
                for line in resp:
                    doc = json.loads(line)
                    if "token" in doc:
                        toks.append(doc["token"])
                    else:
                        assert doc["done"] is True
                        assert doc["finish_reason"] == "length"
                        assert doc["tokens"] == toks
            results[cid] = (plen, toks)
        except Exception as e:  # noqa: BLE001
            errors.append((cid, repr(e)))

    threads = [threading.Thread(target=client, args=(c,))
               for c in range(6)]
    for t in threads:
        t.start()
    util_peak = 0.0
    while any(t.is_alive() for t in threads):
        util_peak = max(util_peak, obs.gauge(
            "serving.decode.slot_utilization.gpt-dec") or 0.0)
        time.sleep(0.002)
    for t in threads:
        t.join()
    assert not errors, errors
    assert len(results) == 6
    # freed slots were refilled mid-flight: both were live at once
    assert util_peak >= 0.75, util_peak
    ref = {plen: _solo(m, _prompt(plen), n_new) for plen in lens}
    for cid, (plen, toks) in results.items():
        assert toks == ref[plen], (cid, plen)


def test_eos_retires_slot_same_step(m):
    """A sequence hitting EOS frees its slot the step the token is
    emitted — the EOS token itself is delivered, then the stream ends."""
    eng = m["eng"]
    p = _prompt(6)
    first = eng.generate(p, max_new=4)[0]
    h = eng.submit(p, max_new=8, eos_id=int(first))
    out = h.result(30.0)
    assert out == [first]
    assert h.finish_reason == "eos"
    deadline = time.monotonic() + 5
    while eng.stats()["live_slots"] and time.monotonic() < deadline:
        time.sleep(0.01)
    assert eng.stats()["live_slots"] == 0


def test_queued_request_admitted_in_flight_no_barrier(m):
    """With both slots busy, a queued request must be prefilled into
    the FIRST freed slot while the other slot is still mid-generation —
    no full-batch barrier — and every result stays bit-identical."""
    eng = m["eng"]
    p_long, p_a, p_b = _prompt(8), _prompt(3), _prompt(6)
    h_long = eng.submit(p_long, max_new=50)   # holds slot for ~50 steps
    h_a = eng.submit(p_a, max_new=3)          # second slot, retires fast
    h_b = eng.submit(p_b, max_new=3)          # queued behind both
    out_b = h_b.result(60.0)
    # b finished while the long request was STILL generating: admission
    # happened in-flight, not at a batch boundary
    assert not h_long.done
    assert out_b == _solo(m, p_b, 3)
    assert h_a.result(60.0) == _solo(m, p_a, 3)
    assert h_long.result(120.0) == _solo(m, p_long, 50)


def test_deadline_expired_queued_request_shed_before_prefill(m):
    """A queued request whose deadline lapses is failed with 504
    semantics BEFORE its prefill — no chip time for an answer nobody is
    waiting for."""
    eng = DecodeEngine(m["cfg"], m["scope"], slots=1, cache_len=24,
                       prompt_buckets=(8,), name="gpt-deadline",
                       auto_start=False)
    ok = eng.submit(_prompt(4), max_new=3)
    doomed = eng.submit(_prompt(5), max_new=3, deadline_ms=1)
    time.sleep(0.05)  # let the deadline lapse while still queued
    eng.start()
    assert ok.result(60.0) == _solo(m, _prompt(4), 3)
    with pytest.raises(DeadlineExceededError):
        doomed.result(60.0)
    st = eng.stats()
    assert st["deadline_miss"] == 1
    assert st["prefills"] == 1  # the doomed request never touched a slot
    eng.stop()


def test_queue_full_sheds_with_retry_after(m):
    eng = DecodeEngine(m["cfg"], m["scope"], slots=1, cache_len=24,
                       prompt_buckets=(8,), name="gpt-shed",
                       queue_capacity=1, auto_start=False)
    eng.submit(_prompt(4), max_new=2)
    with pytest.raises(ShedError) as e:
        eng.submit(_prompt(4), max_new=2)
    assert e.value.retry_after is not None
    assert eng.stats()["shed"] == 1
    eng.stop(drain=False)
    # closed engine: no admission
    with pytest.raises(EngineClosedError):
        eng.submit(_prompt(4), max_new=2)


def test_submit_validation(m):
    eng = m["eng"]
    with pytest.raises(ValueError, match="prompt bucket"):
        eng.submit(_prompt(9), max_new=2)   # largest bucket is 8
    with pytest.raises(ValueError, match="cache_len"):
        eng.submit(_prompt(8), max_new=64)  # 8 + 64 - 1 > 64
    with pytest.raises(ValueError, match="range"):
        eng.submit([0, 1, 200], max_new=2)  # vocab is 97
    with pytest.raises(ValueError, match="empty"):
        eng.submit([], max_new=2)


def test_stream_cancel_frees_slot(m):
    eng = m["eng"]
    h = eng.submit(_prompt(4), max_new=50)
    for tok in h.tokens():
        h.cancel()
        break
    deadline = time.monotonic() + 10
    while not h.done and time.monotonic() < deadline:
        time.sleep(0.01)
    assert h.finish_reason == "cancelled"
    assert len(h.so_far()) < 50


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------

def test_http_non_stream_aggregate_and_statuses(m):
    import urllib.error
    import urllib.request

    def post(doc, path="/v1/models/gpt:generate"):
        req = urllib.request.Request(
            m["srv"].url + path, data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=60)

    p = _prompt(6)
    doc = json.load(post({"prompt": p.tolist(), "max_new_tokens": 5,
                          "stream": False}))
    assert doc["tokens"] == _solo(m, p, 5)
    assert doc["finish_reason"] == "length" and doc["n_tokens"] == 5

    with pytest.raises(urllib.error.HTTPError) as e:
        post({"prompt": list(range(1, 20))})  # too long for the ladder
    assert e.value.code == 400
    with pytest.raises(urllib.error.HTTPError) as e:
        post({"prompt": [1, 2]}, path="/v1/models/nope:generate")
    assert e.value.code == 404
    # :generate against a non-decode engine is a 400, not a crash
    reg2 = ModelRegistry()
    reg2.publish("notdecode", object())
    srv2 = ServingServer(reg2).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as e:
            req = urllib.request.Request(
                srv2.url + "/v1/models/notdecode:generate",
                data=b"{}", headers={"Content-Type": "application/json"})
            urllib.request.urlopen(req, timeout=30)
        assert e.value.code == 400
    finally:
        srv2.stop()
    # healthz reports the decode engine through the registry
    health = json.load(urllib.request.urlopen(
        m["srv"].url + "/healthz", timeout=30))
    assert "gpt" in health["models"]


def test_http_client_disconnect_cancels_slot(m):
    """Killing the connection mid-stream must free the slot at the next
    dispatch iteration instead of decoding the rest to nobody."""
    eng = DecodeEngine(m["cfg"], m["scope"], slots=1, cache_len=256,
                       prompt_buckets=(8,), name="gpt-disc")
    reg = ModelRegistry()
    reg.publish("gptd", eng)
    srv = ServingServer(reg).start()
    try:
        body = json.dumps({"prompt": _prompt(4).tolist(),
                           "max_new_tokens": 240}).encode()
        raw = socket.create_connection((srv.host, srv.port), timeout=30)
        raw.sendall(b"POST /v1/models/gptd:generate HTTP/1.1\r\n"
                    b"Host: t\r\nContent-Type: application/json\r\n"
                    b"Content-Length: %d\r\n\r\n%s" % (len(body), body))
        raw.recv(1024)  # headers + first chunk(s): the stream is live
        raw.close()
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            st = eng.stats()
            if st["cancelled"] >= 1 and st["live_slots"] == 0:
                break
            time.sleep(0.05)
        st = eng.stats()
        assert st["cancelled"] == 1 and st["live_slots"] == 0, st
        assert st["tokens"] < 240  # it did NOT decode to the end
    finally:
        srv.stop()
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# analyzer + compile-cache wiring
# ---------------------------------------------------------------------------

def test_check_hbm_budget_prices_resident_kv_pair(m):
    """The admission estimate holds the slot cache resident across the
    whole step program and prices it ONCE: the step's cache feeds are
    donated and its fetches are those buffers updated in place, so one
    K/V pair plus the step's real transients is the peak (it was a fed
    pair and a fetched pair before the cache was donated)."""
    from paddle_tpu.analysis.diagnostics import ProgramVerifyError

    eng = m["eng"]
    cfg = m["cfg"]
    kv = eng.slots * cfg.num_layers * eng.cache_len * cfg.hidden * 4
    assert eng._cache.nbytes() == 2 * kv
    est = eng.check_hbm_budget(budget_bytes=10 ** 12)
    assert est.param_bytes + 2 * kv <= est.peak_bytes
    # the transients: a few per-layer views of the cache at the peak
    layer = kv // cfg.num_layers
    assert est.peak_bytes < est.param_bytes + 2 * kv + 3 * layer
    with pytest.raises(ProgramVerifyError, match="predicted-oom"):
        eng.check_hbm_budget(budget_bytes=10_000)


def test_warmup_zero_compile_restart(m, tmp_path):
    """An engine rebuilt from the same config resolves every program
    (step + each prefill bucket) through the compile-cache disk tier:
    the restarted server never sees XLA."""
    from paddle_tpu.fluid import compile_cache, unique_name

    prev = compile_cache.activate(str(tmp_path / "cc"))
    try:
        def build():
            # a fresh process numbers program vars from zero — emulated
            # here so both builds fingerprint identically
            unique_name.switch()
            return DecodeEngine(m["cfg"], m["scope"], slots=2,
                                cache_len=24, prompt_buckets=(8,),
                                name="gpt-warm", auto_start=False)

        one = build()
        first = one.warmup(check_hbm=False)
        one.stop()
        two = build()
        second = two.warmup(check_hbm=False)
        two.stop()
    finally:
        compile_cache.activate(prev)
    assert {r["source"] for r in first} <= {"compile", "disk", "memory"}
    assert all(r["source"] != "compile" for r in second), second
    assert len(second) == 2  # step + one prefill bucket


def test_registry_info_and_stats_surface(m):
    info = m["reg"].info()["gpt"]
    assert info["stats"]["requests"] >= 1
    st = m["eng"].stats()
    for k in ("requests", "tokens", "prefills", "steps", "retired",
              "shed", "deadline_miss", "cancelled"):
        assert k in st
    assert m["eng"].queue_depth() == 0
