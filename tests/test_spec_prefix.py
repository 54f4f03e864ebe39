"""Speculative decoding + prefix-cache KV reuse (ISSUE 19).

Exactness bar: speculation and KV reuse are PERF features — every
token a reuse-path engine emits must be BIT-identical to a solo
``build_gpt_generate`` greedy run of the same transcript. Covered
here: draft-propose/block-verify for k=1..4 (including EOS landing
inside a block and a saboteur draft rejected at position 0 every
round), prefix-pool adopt-then-delta vs cold prefill, pool LRU
eviction, session hibernate/resume through the tier (bit-exact on the
fp32 wire, functional on int8), and the ladder-lint + registry
surfaces. ``pytest -m spec`` runs the slice alone.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.models import gpt
from paddle_tpu.serving import (
    DecodeEngine, DraftModel, ModelRegistry, PrefixPool, SessionTier,
    prefix_digest,
)

pytestmark = pytest.mark.spec


def _train(cfg, seed, steps=30):
    """Train one tiny GPT into its OWN scope (target and draft must not
    share params — a draft that IS the target would accept everything
    and prove nothing)."""
    from paddle_tpu.fluid import unique_name
    from paddle_tpu.fluid.executor import Scope

    scope = Scope()
    prog, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(prog, startup), unique_name.guard():
        startup.random_seed = seed
        vs = gpt.build_gpt_lm(cfg, 16)
        fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16, seed=seed)
    for _ in range(steps):
        exe.run(prog, feed={"gpt_ids": ids, "gpt_labels": labels},
                fetch_list=[vs["loss"]], scope=scope)
    return exe, scope


@pytest.fixture(scope="module")
def m():
    """A trained target + a smaller separately-trained draft, each in
    its own scope (engines snapshot params at construction, so the
    per-test scope churn cannot drift them)."""
    cfg = gpt.gpt_tiny(vocab=97, max_len=128)
    dcfg = gpt.GPTConfig(vocab=97, hidden=16, num_layers=1, heads=2,
                         ffn=32, max_len=128, dropout=0.0)
    exe, tscope = _train(cfg, seed=9)
    _, dscope = _train(dcfg, seed=13)
    return {"cfg": cfg, "dcfg": dcfg, "exe": exe, "tscope": tscope,
            "dscope": dscope}


def _solo(m, prompt, n_new):
    """Reference: solo build_gpt_generate greedy tokens for `prompt`."""
    from paddle_tpu.fluid import unique_name

    g_prog, g_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(g_prog, g_st), unique_name.guard():
        gen = gpt.build_gpt_generate(m["cfg"], len(prompt), n_new,
                                     mode="greedy")
    out = np.asarray(m["exe"].run(
        g_prog, feed={"gpt_prompt": np.asarray(prompt).reshape(1, -1)},
        fetch_list=[gen["ids"]], scope=m["tscope"])[0])
    return [int(t) for t in out[0, len(prompt) - 1:]]


def _prompt(n, seed=11):
    rng = np.random.default_rng(seed + n)
    return rng.integers(1, 97, n).astype("int64")


def _engine(m, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("cache_len", 64)
    kw.setdefault("prompt_buckets", (8, 16, 32))
    kw.setdefault("name", "spec-test")
    return DecodeEngine(m["cfg"], m["tscope"], **kw)


# ---------------------------------------------------------------------------
# speculative decoding: bit-exactness
# ---------------------------------------------------------------------------

def test_spec_bit_exact_k1_to_4(m):
    """Every block width k=1..4: mixed prompt lengths through a
    2-slot speculative engine are token-for-token identical to solo
    greedy decode, and speculation actually ran (rounds + proposals
    recorded, acceptance in [0, 1])."""
    ref = {p: _solo(m, _prompt(p), 16) for p in (5, 8)}
    for k in (1, 2, 3, 4):
        eng = _engine(m, draft=DraftModel(m["dcfg"], m["dscope"], k=k,
                                          name="d%d" % k),
                      name="spec-k%d" % k)
        try:
            for p in (5, 8):
                assert eng.generate(_prompt(p), max_new=16) == ref[p], \
                    (k, p)
            st = eng.stats()
            assert st["spec_rounds"] >= 1, st
            assert st["spec_proposed"] >= k * st["spec_rounds"] // 2, st
            assert 0.0 <= st["spec_accept_rate"] <= 1.0, st
        finally:
            eng.stop(drain=False)


def test_spec_eos_inside_block_stops_exactly(m):
    """EOS produced mid-block retires the slot at the EOS token: no
    dirty over-speculated token after it is ever emitted."""
    p = _prompt(6)
    ref = _solo(m, p, 12)
    # earliest position >= 1 whose token is not already in the stream
    # before it, so generation cannot EOS-stop earlier than intended
    j = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eng = _engine(m, draft=DraftModel(m["dcfg"], m["dscope"], k=4,
                                      name="d-eos"), name="spec-eos")
    try:
        h = eng.submit(p, max_new=12, eos_id=ref[j])
        assert h.result(60.0) == ref[:j + 1]
        assert h.finish_reason == "eos"
    finally:
        eng.stop(drain=False)


class _SaboteurDraft(DraftModel):
    """Draft whose every proposal is shifted off the greedy chain —
    the target must reject at position 0 every round."""

    def propose(self, tok, pos):
        return (super().propose(tok, pos) + 1) % self.cfg.vocab


def test_spec_rejection_at_position_0_still_bit_exact(m):
    """A pathologically wrong draft costs ONLY speed: every round
    degrades to one (target-argmax) token — rejection at position 0 —
    and the stream stays bit-exact."""
    p = _prompt(7)
    eng = _engine(m, slots=1,
                  draft=_SaboteurDraft(m["dcfg"], m["dscope"], k=4,
                                       name="d-sab"), name="spec-sab")
    try:
        assert eng.generate(p, max_new=6) == _solo(m, p, 6)
        st = eng.stats()
        assert st["spec_accepted"] == 0, st
        # prefill emits token 1; each round then emits exactly ONE
        # token == every round rejected at position 0
        assert st["spec_rounds"] == 5, st
    finally:
        eng.stop(drain=False)


# ---------------------------------------------------------------------------
# prefix pool: adopt + delta-prefill parity
# ---------------------------------------------------------------------------

def test_prefix_adopt_then_delta_matches_cold(m, armed_sanitizers):
    """Shared 16-token head: the first (cold) request banks it, the
    second adopts it and delta-prefills only its unique tail, a repeat
    of the first adopts with ZERO prefill dispatch — all three streams
    bit-identical to solo decode."""
    pool = PrefixPool(prefix_lens=(16,), name="t-pool")
    eng = _engine(m, prefix_pool=pool, name="spec-pool")
    try:
        head = _prompt(16, seed=3)
        pa = np.concatenate([head, _prompt(4, seed=5)])
        pb = np.concatenate([head, _prompt(8, seed=6)])
        assert eng.generate(pa, max_new=8) == _solo(m, pa, 8)  # cold
        assert eng.generate(pb, max_new=8) == _solo(m, pb, 8)  # delta
        assert eng.generate(pa, max_new=8) == _solo(m, pa, 8)  # full hit
        st = eng.stats()
        assert st["prefix_full_hits"] == 1, st
        assert st["delta_prefills"] == 1, st
        assert st["prefill_rows_saved"] > 0, st
        info = eng.reuse_info()
        assert info["prefill_rows_saved_pct"] > 0, info
        assert info["prefix_pool"]["hits"] >= 2, info
    finally:
        eng.stop(drain=False)


def test_shared_prefix_load_saves_most_prefill_rows(m):
    """12 requests behind one 24-token system prompt (unique tails of
    4..8 tokens) through a 2-slot engine with a PrefixPool and a k=4
    draft: every stream equals the plain engine's, more than half of
    the prefill rows are adopted instead of computed, and speculation
    ran with some acceptance."""
    rng = np.random.default_rng(0)
    shared = rng.integers(1, 97, 24).astype("int64")
    prompts = [np.concatenate(
        [shared, rng.integers(1, 97, 4 + c % 5).astype("int64")])
        for c in range(12)]

    def drive(eng):
        handles = [eng.submit(p, max_new=12) for p in prompts]
        return [h.result(180.0) for h in handles]

    plain = _engine(m, prompt_buckets=(8, 32), queue_capacity=64,
                    name="spec-plain")
    try:
        ref = drive(plain)
        plain_rows = plain.stats()["prefill_rows_computed"]
    finally:
        plain.stop(drain=False)
    reuse = _engine(
        m, prompt_buckets=(8, 32), queue_capacity=64, name="spec-reuse",
        draft=DraftModel(m["dcfg"], m["dscope"], k=4, name="d-reuse"),
        prefix_pool=PrefixPool(prefix_lens=(24,), name="t-shared"))
    try:
        assert drive(reuse) == ref
        st, info = reuse.stats(), reuse.reuse_info()
        assert info["prefill_rows_saved_pct"] > 50.0, info
        assert info["prefill_rows_computed"] < plain_rows, (info, plain_rows)
        assert st["delta_prefills"] >= 1, st
        assert st["spec_rounds"] >= 1 and st["spec_accept_rate"] > 0.0, st
        assert drive(reuse) == ref     # the pool is warm: same streams
        assert reuse.stats()["prefix_full_hits"] >= 1
    finally:
        reuse.stop(drain=False)


def test_prefix_pool_lru_eviction_and_min_tokens():
    """Byte-budget LRU: inserting past capacity evicts the coldest
    entry; trivially short prefixes are never cached."""
    L, T, H = 2, 32, 8
    k = np.ones((L, T, H), np.float32)
    v = np.ones((L, T, H), np.float32)
    nbytes = 2 * k.nbytes  # one fp32 entry
    pool = PrefixPool(capacity_bytes=2 * nbytes, min_tokens=4,
                      name="lru")
    prompts = [_prompt(8, seed=s) for s in (1, 2, 3)]
    for p in prompts:
        assert pool.put(p, k, v, next_token=1) == 1
    st = pool.stats()
    assert len(pool) == 2 and st["evictions"] == 1, st
    assert pool.lookup(prompts[0]) is None          # evicted (oldest)
    hit = pool.lookup(prompts[2])
    assert hit is not None and hit.plen == 8
    assert hit.digest == prefix_digest(prompts[2])
    assert pool.put(_prompt(2, seed=4), k, v) == 0  # below min_tokens
    st = pool.stats()
    assert st["hits"] == 1 and st["misses"] == 1, st


# ---------------------------------------------------------------------------
# session tiering: hibernate / resume
# ---------------------------------------------------------------------------

def test_session_resume_bit_exact_fp32_wire(m, armed_sanitizers):
    """Turn 2 of a hibernated-and-resumed session equals cold greedy
    decode of the full transcript (fp32 wire ⇒ bitwise)."""
    tier = SessionTier(wire_dtype="fp32", name="t-fp32")
    eng = _engine(m, slots=1, session_tier=tier, name="spec-sess")
    try:
        p1, p2 = _prompt(8, seed=21), _prompt(4, seed=22)
        t1 = eng.submit(p1, max_new=4, session="conv").result(60.0)
        assert len(tier) == 1
        assert tier.stats()["hibernated"] == 1
        t2 = eng.submit(p2, max_new=4, session="conv").result(60.0)
        transcript = np.concatenate([p1, np.asarray(t1, np.int64), p2])
        assert t2 == _solo(m, transcript, 4)
        st = eng.stats()
        assert st["resumed"] == 1 and st["hibernated"] == 2, st
        assert tier.stats()["resumed"] == 1
    finally:
        eng.stop(drain=False)


def test_session_resume_int8_wire_functional(m, armed_sanitizers):
    """Default int8 wire: hibernate/resume round-trips and serves turn
    2 (argmax-stable, asserted functionally — the fp32-wire test pins
    bitwise equality)."""
    tier = SessionTier(name="t-int8")
    eng = _engine(m, slots=1, session_tier=tier, name="spec-sess8")
    try:
        p1, p2 = _prompt(8, seed=31), _prompt(4, seed=32)
        eng.submit(p1, max_new=4, session="c8").result(60.0)
        t2 = eng.submit(p2, max_new=4, session="c8").result(60.0)
        assert len(t2) == 4
        assert all(0 <= t < 97 for t in t2)
        assert eng.stats()["resumed"] == 1
        assert tier.stats()["wire_dtype"] == "int8"
    finally:
        eng.stop(drain=False)


def test_more_sessions_than_slots_all_resume_for_fewer_rows(m):
    """6 conversations on 2 slots: every one hibernates and resumes, a
    second turn equals the cold replay of its whole transcript, and the
    tier computes fewer prefill rows than replaying transcripts does."""
    rng = np.random.default_rng(1)
    turn1 = {c: rng.integers(1, 97, 6 + c % 3).astype("int64")
             for c in range(6)}
    turn2 = {c: rng.integers(1, 97, 4).astype("int64") for c in range(6)}
    tier = SessionTier(wire_dtype="fp32", name="t-many")
    eng = _engine(m, prompt_buckets=(8, 32), session_tier=tier,
                  name="spec-many")
    try:
        first = {c: eng.submit(turn1[c], max_new=6,
                               session="conv%d" % c).result(180.0)
                 for c in range(6)}
        second = {c: eng.submit(turn2[c], max_new=6,
                                session="conv%d" % c).result(180.0)
                  for c in range(6)}
        st = eng.stats()
        assert st["resumed"] == 6 > eng.slots, st
        assert tier.stats()["resumed"] == 6
    finally:
        eng.stop(drain=False)
    cold = _engine(m, prompt_buckets=(8, 32), name="spec-replay")
    try:
        for c in range(6):
            transcript = np.concatenate(
                [turn1[c], np.asarray(first[c], np.int64), turn2[c]])
            assert cold.generate(transcript, max_new=6) == second[c], c
        assert (st["prefill_rows_computed"]
                < cold.stats()["prefill_rows_computed"])
    finally:
        cold.stop(drain=False)


# ---------------------------------------------------------------------------
# analyzer + registry surfaces
# ---------------------------------------------------------------------------

def test_lint_decode_ladder_counts_spec_and_draft_programs():
    from paddle_tpu.analysis import tpu_lint

    rep = tpu_lint.lint_decode_ladder(
        (8, 16), slot_counts=(2,), cache_lens=(64,),
        kv_dtypes=("fp32",), delta_buckets=(8, 16), spec_blocks=(5,),
        draft_buckets=(8, 16, 32, 64))
    meta = rep.meta
    assert meta["decode_ladder_delta_programs"] == 2
    assert meta["decode_ladder_spec_programs"] == 1
    assert meta["decode_ladder_draft_programs"] == 5  # 4 rungs + step
    # 2 prefill + 2 delta + 1 step + 1 verify + 5 draft
    assert meta["decode_ladder_programs"] == 11
    # legacy call shape: new legs default to zero, count unchanged
    old = tpu_lint.lint_decode_ladder((8, 16), slot_counts=(2,),
                                      cache_lens=(64,))
    assert old.meta["decode_ladder_programs"] == 3
    assert old.meta["decode_ladder_spec_programs"] == 0


def test_registry_info_surfaces_reuse(m):
    """/healthz reaches reuse_info(): draft attachment, pool + tier
    stats, and the prefill-rows ledger ride the registry doc."""
    pool = PrefixPool(name="r-pool")
    tier = SessionTier(name="r-tier")
    eng = _engine(m, prefix_pool=pool, session_tier=tier,
                  draft=DraftModel(m["dcfg"], m["dscope"], k=2,
                                   name="d-reg"),
                  name="spec-reg", auto_start=False)
    try:
        reg = ModelRegistry()
        reg.publish("gpt-spec", eng)
        doc = reg.info()["gpt-spec"]
        assert doc["reuse"]["draft"]["k"] == 2
        assert doc["reuse"]["prefix_pool"]["entries"] == 0
        assert doc["reuse"]["session_tier"]["sessions"] == 0
        assert doc["reuse"]["prefill_rows_computed"] == 0
    finally:
        eng.stop(drain=False)
