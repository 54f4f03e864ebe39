"""Disaggregated prefill/decode serving (ISSUE 12): serialized KV
handoff wire, prefill-only replicas, step-only (optionally
int8-resident) decode replicas, the session-affine DisaggRouter with
re-prefill migration, and multi-tenant admission.

Exactness bar: with the lossless ``wire_dtype="fp32"`` handoff and
fp32-resident decode replicas, every token a disaggregated fleet
streams — including streams migrated off a killed decode replica
mid-generation — must be BIT-identical to a solo ``build_gpt_generate``
greedy run of the same prompt. The int8 wire and int8 residency get
tolerance bounds (error <= scale/2 per row) instead."""
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import serving
from paddle_tpu.models import gpt
from paddle_tpu.serving import (
    DeadlineExceededError, DecodeEngine, EngineClosedError, ModelRegistry,
    ServingServer, ShedError,
)
from paddle_tpu.serving.decode import kv_slot_bytes
from paddle_tpu.serving.disagg import (
    KVHandoff, PrefillEngine, TenantSpec, TenantTable, dequantize_rows,
    disagg_fleet, encode_kv, handoff_compression, quantize_rows,
    resolve_priority,
)

pytestmark = pytest.mark.disagg


@pytest.fixture(scope="module")
def m():
    """One trained tiny GPT shared by the module (every engine built in
    a test snapshots params from this scope at construction)."""
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
    yield {"cfg": cfg, "exe": exe, "scope": fluid.global_scope(),
           "ref": {}}


def _solo(m, prompt, n_new):
    """Reference: solo build_gpt_generate greedy tokens for `prompt`
    (memoized — several tests pin the same (plen, n_new) pairs)."""
    from paddle_tpu.fluid import unique_name

    key = (tuple(int(t) for t in prompt), int(n_new))
    if key in m["ref"]:
        return m["ref"][key]
    g_prog, g_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_prog, g_st), unique_name.guard():
        gen = gpt.build_gpt_generate(m["cfg"], len(prompt), n_new,
                                     mode="greedy")
    out = np.asarray(m["exe"].run(
        g_prog, feed={"gpt_prompt": np.asarray(prompt).reshape(1, -1)},
        fetch_list=[gen["ids"]], scope=m["scope"])[0])
    m["ref"][key] = [int(t) for t in out[0, len(prompt) - 1:]]
    return m["ref"][key]


def _prompt(n, seed=11):
    rng = np.random.default_rng(seed + n)
    return rng.integers(1, 97, n).astype("int64")


# ---------------------------------------------------------------------------
# the KV wire (pure numpy — no programs compiled)
# ---------------------------------------------------------------------------

def test_kv_wire_roundtrip_tolerance_and_idempotence():
    """Per-(layer, row) block-scaled int8: round-trip error bounded by
    scale/2 per row, and requantizing a decoded cache is a fixed point
    (the int8-resident step program relies on this for untouched
    rows). Zero rows survive via the scale clamp."""
    rng = np.random.default_rng(0)
    # rows with wildly different magnitudes: per-row scales must keep
    # the small rows from drowning in the large rows' range
    mag = np.exp(rng.uniform(-4.0, 4.0, (2, 16, 1))).astype(np.float32)
    cache = (rng.standard_normal((2, 16, 32)).astype(np.float32) * mag)
    payload, scales = quantize_rows(cache)
    assert payload.dtype == np.int8 and payload.shape == cache.shape
    assert scales.shape == (2, 16, 1) and (scales > 0).all()
    dec = dequantize_rows(payload, scales)
    assert (np.abs(dec - cache) <= scales * 0.5 + 1e-7).all()
    # idempotence: re-encode of the decoded cache returns the same code
    p2, s2 = quantize_rows(dec)
    assert (p2 == payload).all()
    assert np.allclose(s2, scales, rtol=1e-6, atol=0.0)
    # all-zero rows: clamp keeps the scale finite, decode stays zero
    pz, sz = quantize_rows(np.zeros((1, 4, 8), np.float32))
    assert (pz == 0).all() and (sz > 0).all()
    assert (dequantize_rows(pz, sz) == 0).all()


def test_kv_handoff_serialization_and_compression():
    rng = np.random.default_rng(1)
    L, T, H = 2, 16, 32
    k = rng.standard_normal((L, T, H)).astype(np.float32)
    v = rng.standard_normal((L, T, H)).astype(np.float32)
    prompt = _prompt(5)
    h = encode_kv(k, v, 42, 5, prompt, wire_dtype="int8")
    assert h.shape == (L, T, H) and h.next_token == 42 and h.plen == 5
    # wire round-trip is exact: payloads, scales, prompt, metadata
    h2 = KVHandoff.from_wire(h.to_wire())
    assert (h2.k == h.k).all() and (h2.v == h.v).all()
    assert (h2.k_scales == h.k_scales).all()
    assert (h2.v_scales == h.v_scales).all()
    assert (h2.prompt == prompt).all()
    assert (h2.next_token, h2.plen, h2.wire_dtype) == (42, 5, "int8")
    # fp32 mode is lossless (what the bit-identity tests ride on)
    hf = encode_kv(k, v, 42, 5, prompt, wire_dtype="fp32")
    kd, vd = hf.dense()
    assert (kd == k).all() and (vd == v).all()
    assert hf.k_scales is None
    hf2 = KVHandoff.from_wire(hf.to_wire())
    assert (hf2.k == k).all() and hf2.k_scales is None
    # the int8 wire is >3x smaller than fp32 for the same geometry
    # (payload/4 + one fp32 scale per row: 3.56x at hidden 32, ~3.9x
    # at production hidden widths)
    assert handoff_compression(L, T, H, "int8") > 3.0
    assert hf.wire_bytes() > 3.0 * h.wire_bytes()
    # a batched (1, L, T, H) prefill fetch squeezes; batch >1 rejects
    hb = encode_kv(k[None], v[None], 7, 3, prompt[:3])
    assert hb.shape == (L, T, H)
    with pytest.raises(ValueError, match="batch"):
        encode_kv(np.zeros((2, L, T, H), np.float32),
                  np.zeros((2, L, T, H), np.float32), 0, 1, [1])


# ---------------------------------------------------------------------------
# tenancy (pure) + ladder lint
# ---------------------------------------------------------------------------

def test_tenant_table_quotas_and_priority_classes():
    assert resolve_priority(None, default=2) == 2
    assert resolve_priority("interactive") == 0
    assert resolve_priority(2) == 2
    for bad in ("vip", 3, -1, True, 1.5):
        with pytest.raises(ValueError):
            resolve_priority(bad)
    table = TenantTable(
        specs=[TenantSpec("burst", priority="batch", max_live=1,
                          per_token_slo_ms=50.0)],
        model="m")
    spec = table.acquire("burst")
    assert spec.priority == 2 and spec.per_token_slo_ms == 50.0
    with pytest.raises(ShedError, match="quota"):
        table.acquire("burst")
    table.release("burst")
    table.acquire("burst")  # token came back
    # unknown tenants fold into the default spec (degrade, not 403)
    anon = table.resolve("anon")
    assert anon.name == "anon" and anon.priority == 1
    assert anon.max_live is None
    with pytest.raises(ValueError, match="unknown tenant"):
        TenantTable(allow_unknown=False).acquire("ghost")
    st = table.stats()
    assert st["live"]["burst"] == 1 and st["shed"]["burst"] == 1


def test_lint_decode_ladder_counts_disagg_variants():
    """A fleet running both fp32- and int8-resident decode replicas
    doubles the step-program leg of the ladder; the lint's program
    count must reflect it."""
    from paddle_tpu.analysis import tpu_lint

    rep = tpu_lint.lint_decode_ladder(
        (8, 16), slot_counts=(2,), cache_lens=(64, 128),
        kv_dtypes=("fp32", "int8"))
    # 2 cache_lens x (2 prefill buckets + 1 slot count x 2 kv dtypes)
    assert rep.meta["decode_ladder_programs"] == 8
    assert rep.meta["decode_ladder_kv_dtypes"] == ["fp32", "int8"]
    warned = tpu_lint.lint_decode_ladder(
        (8, 16), slot_counts=(2,), cache_lens=(64, 128),
        kv_dtypes=("fp32", "int8"), threshold=7)
    assert any(f.check == "unbounded-shape-vocab"
               for f in warned.findings)
    # the default single-dtype count is unchanged from the pre-disagg
    # ladder (no surprise warnings for existing engines)
    base = tpu_lint.lint_decode_ladder((8, 16), slot_counts=(2,),
                                       cache_lens=(64,))
    assert base.meta["decode_ladder_programs"] == 3


# ---------------------------------------------------------------------------
# PrefillEngine: priority queue, deadlines, shed, handoff product
# ---------------------------------------------------------------------------

def test_prefill_priority_queue_deadline_and_shed(m):
    pre = PrefillEngine(m["cfg"], m["scope"], cache_len=64,
                        prompt_buckets=(8,), wire_dtype="int8",
                        name="pre-prio", auto_start=False)
    t_batch = pre.submit(_prompt(4), priority=2)
    t_std = pre.submit(_prompt(5), priority=1)
    t_int = pre.submit(_prompt(6), priority=0)
    doomed = pre.submit(_prompt(7), priority=0, deadline_ms=1)
    # min-heap: the interactive request runs first despite arriving
    # third; its priority-0 peer queued later loses the FIFO tie
    assert pre._heap[0][2].ticket is t_int
    assert pre.queue_depth() == 4
    time.sleep(0.05)  # the doomed deadline lapses while still queued
    pre.start()
    h = t_int.result(120.0)
    assert isinstance(h, KVHandoff)
    assert h.plen == 6 and h.wire_dtype == "int8"
    assert h.k_scales is not None and (h.prompt == _prompt(6)).all()
    assert 0 <= h.next_token < m["cfg"].vocab
    assert t_std.result(120.0).plen == 5
    assert t_batch.result(120.0).plen == 4
    with pytest.raises(DeadlineExceededError):
        doomed.result(120.0)
    st = pre.stats()
    assert st["prefills"] == 3 and st["deadline_miss"] == 1
    pre.stop()
    with pytest.raises(EngineClosedError):
        pre.submit(_prompt(4))

    # admission: a full queue fast-rejects with a Retry-After hint, and
    # stop(drain=False) fails still-queued tickets
    tiny = PrefillEngine(m["cfg"], m["scope"], cache_len=64,
                         prompt_buckets=(8,), queue_capacity=1,
                         name="pre-shed", auto_start=False)
    queued = tiny.submit(_prompt(4))
    with pytest.raises(ShedError) as e:
        tiny.submit(_prompt(4))
    assert e.value.retry_after is not None
    assert tiny.stats()["shed"] == 1
    with pytest.raises(ValueError, match="prompt bucket"):
        tiny.submit(_prompt(9))
    tiny.stop(drain=False)
    with pytest.raises(EngineClosedError):
        queued.result(5.0)


# ---------------------------------------------------------------------------
# handoff adoption on a DecodeEngine
# ---------------------------------------------------------------------------

def test_fp32_handoff_adoption_bit_identical(m):
    """prefill replica -> lossless handoff -> submit_prefilled on a
    separate engine must stream the exact solo-generate tokens, with
    zero local prefills."""
    pre = PrefillEngine(m["cfg"], m["scope"], cache_len=64,
                        prompt_buckets=(8,), wire_dtype="fp32",
                        name="pre-exact")
    eng = DecodeEngine(m["cfg"], m["scope"], slots=2, cache_len=64,
                       prompt_buckets=(8,), name="gpt-adopt")
    try:
        for plen in (3, 8):
            p = _prompt(plen)
            h = pre.prefill(p, timeout=120.0)
            toks = eng.submit_prefilled(h, max_new=8).result(120.0)
            assert toks == _solo(m, p, 8), plen
            assert toks[0] == h.next_token
        st = eng.stats()
        assert st["adopts"] == 2 and st["prefills"] == 0
        # validation: geometry, plen range, cache fit
        L, H = m["cfg"].num_layers, m["cfg"].hidden
        small = np.zeros((L, 32, H), np.float32)
        with pytest.raises(ValueError, match="geometry"):
            eng.submit_prefilled(
                encode_kv(small, small, 1, 4, [1, 2, 3, 4],
                          wire_dtype="fp32"), max_new=2)
        full = np.zeros((L, 64, H), np.float32)
        with pytest.raises(ValueError, match="plen"):
            eng.submit_prefilled(
                encode_kv(full, full, 1, 0, [], wire_dtype="fp32"),
                max_new=2)
        with pytest.raises(ValueError, match="cache_len"):
            eng.submit_prefilled(
                encode_kv(full, full, 1, 60, _prompt(8),
                          wire_dtype="fp32"), max_new=8)
    finally:
        pre.stop(drain=False)
        eng.stop(drain=False)


def test_int8_handoff_tolerance_and_adoption(m):
    """The int8 wire is lossy but bounded: the dequantized cache sits
    within scale/2 of the lossless handoff's, the first token (computed
    fp32 at prefill) is exact, and adoption still streams a full
    sequence."""
    pre32 = PrefillEngine(m["cfg"], m["scope"], cache_len=64,
                          prompt_buckets=(8,), wire_dtype="fp32",
                          name="pre-f32")
    pre8 = PrefillEngine(m["cfg"], m["scope"], cache_len=64,
                         prompt_buckets=(8,), wire_dtype="int8",
                         name="pre-i8")
    eng = DecodeEngine(m["cfg"], m["scope"], slots=1, cache_len=64,
                       prompt_buckets=(8,), name="gpt-adopt8")
    try:
        p = _prompt(7)
        h32 = pre32.prefill(p, timeout=120.0)
        h8 = pre8.prefill(p, timeout=120.0)
        assert h8.next_token == h32.next_token
        k32, _ = h32.dense()
        k8, _ = h8.dense()
        assert (np.abs(k8 - k32) <= h8.k_scales * 0.5 + 1e-7).all()
        toks = eng.submit_prefilled(h8, max_new=6).result(120.0)
        assert len(toks) == 6 and toks[0] == h32.next_token
        assert all(0 <= t < m["cfg"].vocab for t in toks)
    finally:
        pre32.stop(drain=False)
        pre8.stop(drain=False)
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# int8-resident decode + phase-specialized roles
# ---------------------------------------------------------------------------

def test_int8_resident_kv_multiplies_slots(m):
    """int8 residency prices one slot at >3.5x fewer HBM bytes than
    fp32 (hidden 32; ~3.9x at production widths), the analyzer's
    admission estimate sees the saving, and the engine still decodes."""
    cfg = m["cfg"]
    ratio = (kv_slot_bytes(cfg, 64, "fp32")
             / float(kv_slot_bytes(cfg, 64, "int8")))
    assert 3.5 < ratio < 4.0
    with pytest.raises(ValueError, match="kv_dtype"):
        kv_slot_bytes(cfg, 64, "fp4")
    eng8 = DecodeEngine(cfg, m["scope"], slots=2, cache_len=64,
                        prompt_buckets=(8,), name="gpt-q",
                        kv_dtype="int8")
    try:
        assert eng8.slot_bytes() == kv_slot_bytes(cfg, 64, "int8")
        est8 = eng8.check_hbm_budget(budget_bytes=10 ** 12)
        p = _prompt(6)
        toks = eng8.generate(p, max_new=10, timeout=120.0)
        # the prefill program stays fp32, so the first token is exact;
        # the quantized resident cache bounds but does not zero the
        # drift on later tokens
        assert toks[0] == _solo(m, p, 10)[0]
        assert len(toks) == 10
        assert all(0 <= t < cfg.vocab for t in toks)
        st = eng8.stats()
        assert st["kv_dtype"] == "int8" and st["role"] == "colocated"
    finally:
        eng8.stop(drain=False)
    engf = DecodeEngine(cfg, m["scope"], slots=2, cache_len=64,
                        prompt_buckets=(8,), name="gpt-qf",
                        auto_start=False)
    estf = engf.check_hbm_budget(budget_bytes=10 ** 12)
    res8, resf = eng8._cache.nbytes(), engf._cache.nbytes()
    engf.stop(drain=False)
    # each estimate prices its resident cache once, at its dtype
    assert res8 == 2 * kv_slot_bytes(cfg, 64, "int8")
    assert resf == 2 * kv_slot_bytes(cfg, 64, "fp32")
    assert est8.peak_bytes >= est8.param_bytes + res8
    assert estf.peak_bytes >= estf.param_bytes + resf
    # beyond its cache an int8 step holds the fp32 copy of the ONE layer
    # it attends over, whatever the depth: a handful of fp32 layers, so
    # from a few layers up (this fixture has 2) the estimate sees the
    # whole saving
    layer = resf // (2 * cfg.num_layers)
    assert (est8.peak_bytes - res8
            <= estf.peak_bytes - resf + 8 * layer)


def test_decode_role_is_step_only(m):
    eng = DecodeEngine(m["cfg"], m["scope"], slots=1, cache_len=24,
                       prompt_buckets=(8,), role="decode",
                       name="gpt-steponly", auto_start=False)
    with pytest.raises(RuntimeError, match="submit_prefilled"):
        eng.submit(_prompt(3), max_new=2)
    assert eng.stats()["role"] == "decode"
    # no prefill programs exist to warm: the step program is the whole
    # ladder on a decode-role replica
    report = eng.warmup(check_hbm=False)
    assert [r["program"] for r in report] == ["step"]
    eng.stop(drain=False)


# ---------------------------------------------------------------------------
# the disaggregated fleet
# ---------------------------------------------------------------------------

def test_disagg_fleet_bit_identical_and_tenancy(m):
    """1 prefill + 2 decode replicas over the lossless wire: six
    concurrent sessions stream bit-identical to solo, tenant quotas
    shed with 429 semantics, and a malformed priority releases the
    quota token it briefly held."""
    tenants = TenantTable(
        specs=[TenantSpec("capped", max_live=1)], model="dfleet")
    router = disagg_fleet(
        m["cfg"], m["scope"], n_prefill=1, n_decode=2, slots=2,
        cache_len=64, prompt_buckets=(8,), kv_dtype="fp32",
        wire_dtype="fp32", tenants=tenants, name="dfleet")
    try:
        lens = (3, 6, 8)
        n_new = 10
        handles = [(plen, router.submit(_prompt(plen), max_new=n_new,
                                        tenant="t%d" % i,
                                        priority="interactive"))
                   for i, plen in enumerate(lens * 2)]
        for plen, h in handles:
            assert h.result(120.0) == _solo(m, _prompt(plen), n_new)
        st = router.stats()
        assert st["sessions"] == 6 and st["failed_streams"] == 0
        assert st["migrations"] == 0
        assert st["prefill_live"] == 1 and st["decode_live"] == 2
        assert st["adopts"] == 6 and st["prefills"] >= 6
        assert router.queue_depth() == 0
        # tenant quota: one live session caps the "capped" tenant
        slow = router.submit(_prompt(8), max_new=40, tenant="capped")
        with pytest.raises(ShedError, match="quota"):
            router.submit(_prompt(3), max_new=2, tenant="capped")
        # malformed priority is a 400-class error AND returns the
        # tenant token (the follow-up submit would shed otherwise)
        with pytest.raises(ValueError, match="priority"):
            router.submit(_prompt(3), max_new=2, tenant="t9",
                          priority="vip")
        assert router.tenants.live("t9") == 0
        assert slow.result(120.0) == _solo(m, _prompt(8), 40)
        # ladder validation happens at the router door
        with pytest.raises(ValueError, match="prompt bucket"):
            router.submit(_prompt(9), max_new=2)
        with pytest.raises(ValueError, match="cache_len"):
            router.submit(_prompt(8), max_new=64)
    finally:
        router.stop(drain=False, timeout=10.0)
    with pytest.raises(EngineClosedError):
        router.submit(_prompt(3), max_new=2)


@pytest.mark.chaos
def test_chaos_decode_replica_kill_migrates_streams_exactly(
        m, armed_sanitizers, tmp_path, monkeypatch):
    """SIGKILL-equivalent on a decode replica mid-stream: every live
    session re-prefills ``prompt + so_far()`` and finishes on the
    survivor BIT-identical to solo — zero failed streams. Runs with the
    lock-order/thread sanitizer AND the scope sanitizer armed: the kill
    path must leave zero violations and zero leaked threads. Runs
    traced (ISSUE 14): the migrated streams' re-prefill spans must
    carry the ORIGINAL trace_id plus a ``migration`` annotation, so
    the merged timeline shows the failover instead of losing it."""
    from paddle_tpu import observability as obs

    monkeypatch.setenv(obs.TRACE_DIR_ENV, str(tmp_path))
    router = disagg_fleet(
        m["cfg"], m["scope"], n_prefill=1, n_decode=2, slots=2,
        cache_len=64, kv_dtype="fp32", wire_dtype="fp32",
        name="chaos-fleet")
    try:
        lens = (3, 5, 6, 8)
        n_new = 50
        traces = {plen: obs.TraceContext.new() for plen in lens}
        handles = [(plen, router.submit(_prompt(plen), max_new=n_new,
                                        trace_ctx=traces[plen]))
                   for plen in lens]
        # wait until every session is adopted (first token emitted) —
        # the earliest instant the kill can catch all four mid-stream
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            if all(len(h.so_far()) >= 1 for _, h in handles):
                break
            time.sleep(0.002)
        assert all(len(h.so_far()) >= 1 for _, h in handles)
        with router._lock:
            victim = max(router._sessions,
                         key=lambda r: len(router._sessions[r]))
            victims = len(router._sessions[victim])
        assert victims >= 1
        router.kill_replica(victim)
        for plen, h in handles:
            assert h.result(120.0) == _solo(m, _prompt(plen), n_new), plen
        st = router.stats()
        assert st["failed_streams"] == 0
        assert st["migrations"] >= 1
        assert st["replica_dead"] >= 1
        assert st["decode_live"] == 1
        # each migrated session re-adopted on the survivor
        assert st["adopts"] >= len(lens) + st["migrations"]
        # --- traced failover: re-prefill spans keep the original
        # trace_id and carry the migration annotation ---
        spans = obs.read_spans(str(tmp_path))
        want = {t.trace_id for t in traces.values()}
        got = {s["trace"] for s in spans}
        assert want <= got  # every request traced end to end
        legs = [s for s in spans if s["name"] == "disagg.prefill_leg"]
        migrated = [s for s in legs
                    if (s.get("args") or {}).get("migration", 0) >= 1]
        assert len(legs) >= len(lens) + st["migrations"]
        assert len(migrated) >= st["migrations"]
        # the re-prefill rides the ORIGINAL trace, not a fresh one
        assert all(s["trace"] in want for s in migrated)
        for s in migrated:
            engine_prefills = [
                p for p in spans if p["name"] == "disagg.prefill"
                and p["trace"] == s["trace"]]
            assert len(engine_prefills) >= 2  # original + re-prefill
        # the merged chrome trace keeps one timeline per request with
        # spans from >= 3 logical processes and cross-process flows
        doc = obs.chrome_trace(spans,
                               trace_id=migrated[0]["trace"])
        assert len(doc["otherData"]["processes"]) >= 3
        assert doc["otherData"]["flows"] >= 1
    finally:
        router.stop(drain=False, timeout=10.0)


# ---------------------------------------------------------------------------
# HTTP frontend: tenancy fields + Retry-After on the disagg statuses
# ---------------------------------------------------------------------------

def test_http_generate_disagg_statuses_and_tenancy(m):
    import urllib.error
    import urllib.request

    tenants = TenantTable(
        specs=[TenantSpec("capped", max_live=0)], model="gptdis")
    router = disagg_fleet(
        m["cfg"], m["scope"], n_prefill=1, n_decode=1, slots=2,
        cache_len=64, prompt_buckets=(8,), kv_dtype="fp32",
        wire_dtype="fp32", tenants=tenants, name="gptdis")
    reg = ModelRegistry()
    reg.publish("gptdis", router)
    srv = ServingServer(reg).start()

    def post(doc):
        req = urllib.request.Request(
            srv.url + "/v1/models/gptdis:generate",
            data=json.dumps(doc).encode(),
            headers={"Content-Type": "application/json"})
        return urllib.request.urlopen(req, timeout=120)

    try:
        p = _prompt(5)
        doc = json.load(post({"prompt": p.tolist(), "max_new_tokens": 4,
                              "stream": False, "tenant": "chat",
                              "priority": "interactive"}))
        assert doc["tokens"] == _solo(m, p, 4)
        # the registry health payload names the phase kind
        health = json.load(urllib.request.urlopen(
            srv.url + "/healthz", timeout=30))
        assert health["models"]["gptdis"]["kind"] == "decode"
        # malformed tenancy fields are 400s, not stream-time surprises
        for bad in ({"tenant": ""}, {"priority": "vip"},
                    {"priority": 7}, {"priority": True}):
            body = dict({"prompt": p.tolist(), "max_new_tokens": 2},
                        **bad)
            with pytest.raises(urllib.error.HTTPError) as e:
                post(body)
            assert e.value.code == 400, bad
        # tenant at quota: 429 with a Retry-After, like a full queue
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": p.tolist(), "max_new_tokens": 2,
                  "tenant": "capped"})
        assert e.value.code == 429
        assert int(e.value.headers["Retry-After"]) >= 1
        # a draining fleet: 503 ALSO carries Retry-After (satellite —
        # :generate matches :predict's backpressure contract)
        router.stop(drain=False, timeout=5.0)
        with pytest.raises(urllib.error.HTTPError) as e:
            post({"prompt": p.tolist(), "max_new_tokens": 2})
        assert e.value.code == 503
        assert int(e.value.headers["Retry-After"]) >= 1
    finally:
        srv.stop()
        router.stop(drain=False, timeout=5.0)


def test_fleet_metrics_federate_and_each_request_is_one_timeline(
        m, tmp_path, monkeypatch):
    """A 2-prefill x 2-decode fleet behind HTTP with every request
    sampled: the federated counter totals (`/metrics?scope=fleet`) are
    the sums of the replicas' own `stats()`, and each request's spans
    merge into one timeline across >= 3 logical processes with a flow
    arrow, every phase present and the cost model's prediction beside
    the measured time."""
    import urllib.request

    from paddle_tpu import observability as obs

    monkeypatch.setenv(obs.TRACE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv(obs.TRACE_SAMPLE_ENV, "1.0")
    # the CPU has no row in the device table: pin one so that spans
    # carry predicted-vs-measured annotations
    monkeypatch.setenv("PADDLE_TPU_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("PADDLE_TPU_HBM_BYTES", "16e9")
    monkeypatch.setenv("PADDLE_TPU_HBM_BW", "6e11")
    router = disagg_fleet(
        m["cfg"], m["scope"], n_prefill=2, n_decode=2, slots=2,
        cache_len=64, prompt_buckets=(8,), kv_dtype="fp32",
        wire_dtype="fp32", name="fed-fleet")
    reg = ModelRegistry()
    reg.publish("fed-fleet", router)
    srv = ServingServer(reg).start()
    trace_ids = []
    try:
        for i in range(6):
            body = json.dumps({"prompt": _prompt(3 + i % 5).tolist(),
                               "max_new_tokens": 8,
                               "stream": False}).encode()
            req = urllib.request.Request(
                srv.url + "/v1/models/fed-fleet:generate", data=body,
                headers={"Content-Type": "application/json"})
            doc = json.load(urllib.request.urlopen(req, timeout=120))
            assert len(doc["tokens"]) == 8
            trace_ids.append(doc["trace_id"])
        assert all(trace_ids)

        # one beat later every beacon's metrics document is current
        deadline = time.monotonic() + 10
        while True:
            expected = {}
            for rep in (list(router._prefill.values())
                        + list(router._decode.values())):
                for k, v in rep.engine.stats().items():
                    # process_cpu_seconds is a clock read at the call:
                    # it has moved on by the next one
                    if isinstance(v, (int, float)) \
                            and not isinstance(v, bool) \
                            and k != "process_cpu_seconds":
                        expected[k] = expected.get(k, 0) + v
            totals = router.fleet_metrics().counter_totals()
            if all(totals.get(k) == v for k, v in expected.items()) \
                    or time.monotonic() > deadline:
                break
            time.sleep(0.05)
        assert {k: totals.get(k) for k in expected} == expected
        assert expected["adopts"] == expected["prefills"] == 6
        page = urllib.request.urlopen(
            srv.url + "/metrics?scope=fleet", timeout=30).read().decode()
        assert "paddle_tpu_fleet_replicas 4" in page
        for k in ("adopts", "prefills"):
            assert "paddle_tpu_fleet_%s %g" % (k, expected[k]) in page
    finally:
        srv.stop(close_registry=False)
        router.stop(drain=False, timeout=10.0)
        reg.close()

    merged = obs.collect_trace(str(tmp_path),
                               out=str(tmp_path / "merged.json"))
    assert json.load(open(tmp_path / "merged.json")) == merged
    assert set(trace_ids) <= set(merged["otherData"]["traces"])
    spans = obs.read_spans(str(tmp_path))
    for tid in trace_ids:
        per = obs.chrome_trace(spans, trace_id=tid)["otherData"]
        assert per["spans"] >= 4 and per["flows"] >= 1, per
        assert len(per["processes"]) >= 3, per
    assert [e for e in merged["traceEvents"] if e["ph"] == "X"
            and "predicted_ms" in e.get("args", {})]
    phases = obs.phase_breakdown(spans)
    for phase in ("queue", "prefill", "handoff", "adopt", "decode"):
        assert phases.get(phase, {}).get("count", 0) >= 1, (phase, phases)


def test_serving_package_exports():
    for name in ("DisaggRouter", "DisaggReplica", "DisaggStream",
                 "PrefillEngine", "PrefillTicket", "KVHandoff",
                 "TenantSpec", "TenantTable", "disagg_fleet"):
        assert hasattr(serving, name), name
