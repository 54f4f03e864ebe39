"""The decode step updates the K/V cache in place (ISSUE 26): per-layer
slot cache buffers, donated to every program that takes them
(``Predictor(donate_feeds=...)``), one row scatter per cache, nothing
cache-sized copied. CPU, test size; the three programs that take the
whole slot cache — the fp32 step, the int8-resident step and the
speculative verify block — are the cases of each test.

What only the chip can show (device time, no ``slice_bitcast_fusion`` /
stacked ``dynamic-update-slice`` in the trace) is in PERF.md §5/§6."""
import re

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import resilience as R
from paddle_tpu.integrity import SDCSentinel
from paddle_tpu.models import gpt
from paddle_tpu.serving import DecodeEngine
from paddle_tpu.serving.prefix_pool import PrefixPool, SessionTier
from paddle_tpu.serving.spec import DraftModel

PROGRAMS = ["fp32_step", "int8_step", "verify_block"]


@pytest.fixture(scope="module")
def m():
    """Seeded (untrained) tiny GPT weights as a plain name -> array
    scope: greedy decoding over them is as deterministic as over
    trained ones, and nothing here judges the text."""
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
    return {"cfg": cfg, "scope": weights}


def _engine(m, program, name, **kw):
    """An engine whose cache-taking program is ``program``; returns it
    with the attribute name of that program's predictor."""
    kw.setdefault("slots", 3)
    kw.setdefault("cache_len", 32)
    kw.setdefault("prompt_buckets", (8, 16))
    if program == "int8_step":
        kw["kv_dtype"] = "int8"
    if program == "verify_block":
        kw["draft"] = DraftModel(m["cfg"], m["scope"], k=2,
                                 name=name + "-draft")
    eng = DecodeEngine(m["cfg"], m["scope"], name=name, **kw)
    return eng, ("_verify_pred" if program == "verify_block"
                 else "_step_pred")


def _prompt(n, seed=3):
    return np.random.default_rng(seed + n).integers(1, 97, n).astype(
        "int64")


class _Spy:
    """Stands in for a predictor: records, after every run, whether the
    buffers fed under ``donate_feeds`` were consumed; ``fail_after``
    raises once AFTER the real (donating) dispatch, the fault a device
    error in flight would be."""

    def __init__(self, inner, fail_after=None):
        self.inner = inner
        self.consumed = []
        self.fail_after = fail_after

    def run(self, feeds, **kw):
        fed = [feeds[n] for n in self.inner.donate_feeds]
        outs = self.inner.run(feeds, **kw)
        self.consumed.append(all(b.is_deleted() for b in fed))
        if self.fail_after is not None and len(
                self.consumed) == self.fail_after:
            self.fail_after = None
            raise RuntimeError("seeded device fault in flight")
        return outs

    def __getattr__(self, k):
        return getattr(self.inner, k)


# -- (i) the compiled program aliases every cache feed --------------------

@pytest.mark.parametrize("program", PROGRAMS)
def test_compiled_step_aliases_every_cache_feed(m, program):
    eng, attr = _engine(m, program, "alias-" + program, auto_start=False)
    try:
        eng.warmup(check_hbm=False)
        pred = getattr(eng, attr)
        n = len(pred.donate_feeds)
        groups = 4 if program == "int8_step" else 2
        assert n == groups * m["cfg"].num_layers
        (compiled,) = pred._compiled.values()
        head = compiled.as_text().split("\n", 1)[0]
        aliases = re.findall(r"\{(\d+)\}: \((\d+), \{\}", head.split(
            "input_output_alias={", 1)[1].split("}, entry", 1)[0])
        # every cache feed is one distinct parameter aliased to one
        # distinct output, and they are the outputs after the tokens
        assert len(aliases) == n
        assert sorted(int(o) for o, _ in aliases) == list(range(1, n + 1))
        assert len({p for _, p in aliases}) == n
        # ... which leaves the tokens as the only output with a buffer of
        # its own: no un-aliased cache-sized array comes out of a step
        layout = head.split("entry_computation_layout={", 1)[1]
        outputs = layout.split(")->(", 1)[1].split(")}", 1)[0]
        assert len(re.findall(r"\w+\[[\d,]*\]", outputs)) == n + 1
        # the executable ledger lists the cache feeds as donated
        from paddle_tpu import observability as obs

        entry = [e for e in obs.get_ledger().entries()
                 if e["kind"] == pred.ledger_tag][-1]
        assert entry["donated"] == sorted(pred.donate_feeds)
    finally:
        eng.stop(drain=False)


# -- (ii) donated inputs die with the run; the engine never touches one ---

@pytest.mark.parametrize("program", PROGRAMS)
def test_donated_inputs_are_deleted_and_never_touched(m, program):
    """Prefill, prefix-pool insert and adopt, delta prefill, hibernate
    (``kv_wire`` encode) and resume, cancel and retire between steps:
    whatever the engine does with the cache goes through buffers that
    are alive, and every buffer a step was fed is dead after it."""
    kw = {}
    if program != "verify_block":  # speculation needs no pool to be driven
        kw = {"prefix_pool": PrefixPool(capacity_bytes=1 << 20),
              "session_tier": SessionTier(capacity_bytes=1 << 20)}
    eng, attr = _engine(m, program, "touch-" + program, **kw)
    spy = _Spy(getattr(eng, attr))
    setattr(eng, attr, spy)
    try:
        shared = _prompt(8)
        a = eng.submit(shared, max_new=6, session="s1" if kw else None)
        b = eng.submit(_prompt(5), max_new=12)
        c = eng.submit(_prompt(11), max_new=20)
        assert len(a.result(60.0)) == 6
        c.cancel()
        # same prompt again (a full prefix-pool hit), a longer one (a
        # delta prefill over the pooled rows), the session's next turn
        d = eng.submit(shared, max_new=4)
        e = eng.submit(np.concatenate([shared, _prompt(3)]), max_new=4)
        f = eng.submit(_prompt(2), max_new=3,
                       session="s1" if kw else None)
        for h, n in ((b, 12), (d, 4), (e, 4), (f, 3)):
            assert len(h.result(60.0)) == n
        st = eng.stats()
    finally:
        eng.stop(drain=False)
    assert spy.consumed and all(spy.consumed)
    assert st["cache_copy_steps"] == 0 and st["cache_reallocs"] == 0
    for k in ("step_errors", "prefill_errors", "delta_errors",
              "hibernate_errors", "prefix_insert_errors",
              "draft_step_errors", "draft_fill_errors"):
        assert st.get(k, 0) == 0, (k, st[k])
    assert st["cancelled"] == 1
    if kw:
        assert st["prefix_full_hits"] >= 1 and st["delta_prefills"] >= 1
        assert st["hibernated"] >= 1 and st["resumed"] == 1


# -- (iv) a dispatch fault on a donated step -------------------------------

@pytest.mark.parametrize("program", PROGRAMS)
def test_fault_after_donation_fails_live_reallocates_and_serves(m, program):
    ref_eng, _ = _engine(m, program, "fault-ref-" + program)
    try:
        want = ref_eng.generate(_prompt(6), max_new=8, timeout=60.0)
    finally:
        ref_eng.stop(drain=False)
    eng, attr = _engine(m, program, "fault-" + program)
    spy = _Spy(getattr(eng, attr), fail_after=2)
    setattr(eng, attr, spy)
    try:
        doomed = eng.submit(_prompt(7), max_new=10)
        with pytest.raises(RuntimeError, match="seeded device fault"):
            doomed.result(60.0)
        # the cache the fault consumed is replaced, the loop still runs
        got = eng.generate(_prompt(6), max_new=8, timeout=60.0)
        st = eng.stats()
    finally:
        eng.stop(drain=False)
    assert got == want
    assert st["step_errors"] == 1 and st["cache_reallocs"] == 1
    assert st["cache_copy_steps"] == 0


def test_seeded_dispatch_fault_is_survived(m):
    """The chaos site in front of the dispatch (nothing donated yet):
    live streams fail, the cache is kept, the next request is served."""
    eng, _ = _engine(m, "fp32_step", "fault-site")
    try:
        want = eng.generate(_prompt(6), max_new=5, timeout=60.0)
        R.FaultInjector.install("dispatch:at=1:RuntimeError")
        try:
            with pytest.raises(Exception):
                eng.generate(_prompt(7), max_new=5, timeout=60.0)
        finally:
            R.FaultInjector.uninstall()
        assert eng.generate(_prompt(6), max_new=5, timeout=60.0) == want
        st = eng.stats()
        assert st["step_errors"] == 1 and st["cache_reallocs"] == 0
    finally:
        eng.stop(drain=False)


# -- (v) the SDC sentinel replays a sampled step from a copy ---------------

@pytest.mark.parametrize("program", ["fp32_step", "int8_step"])
def test_sentinel_replay_agrees_and_counts_its_copy(m, program):
    plain, _ = _engine(m, program, "sdc-ref-" + program)
    try:
        want = plain.generate(_prompt(6), max_new=9, timeout=60.0)
    finally:
        plain.stop(drain=False)
    eng, _ = _engine(m, program, "sdc-" + program)
    sent = SDCSentinel(check_every=3)
    eng.attach_sentinel(sent)
    try:
        got = eng.generate(_prompt(6), max_new=9, timeout=60.0)
        st = eng.stats()
        # a peer's vote re-runs feeds it is handed: they survive it
        feeds = dict(eng._cache.feeds(
            eng._step_vars["cache_feed_names"]),
            gpt_step_tok=eng._tok, gpt_step_pos=eng._pos)
        eng.sentinel_replay(feeds)
        assert not any(b.is_deleted() for b in eng._cache.bufs)
    finally:
        eng.stop(drain=False)
    assert got == want
    sampled = st["steps"] // 3
    assert sampled >= 2
    assert st["cache_copy_steps"] == sampled
    assert st.get("sdc_disagree", 0) == 0
    assert sent.stats()["pending"] == 0


# -- (vi) a donating predictor and the compile cache's disk tier -----------

def test_donating_predictor_donates_from_the_disk_tier_too(tmp_path):
    import jax.numpy as jnp

    from paddle_tpu.fluid import compile_cache, unique_name
    from paddle_tpu.fluid.inference import Predictor

    def build(donate):
        unique_name.switch()
        main, startup = fluid.Program(), fluid.Program()
        startup.random_seed = 3   # every build: the same weights
        with fluid.program_guard(main, startup):
            x = fluid.data("x", [None, 4])
            acc = fluid.data("acc", [None, 4])
            out = fluid.layers.elementwise_add(acc, fluid.layers.fc(x, 4))
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        return Predictor(main, ["x", "acc"], [out], scope=scope,
                         name="acc_step", donate_feeds=donate)

    def run(pred):
        acc = jnp.zeros((2, 4), "float32")
        (out,) = pred.run({"x": np.ones((2, 4), "float32"), "acc": acc},
                          return_numpy=False)
        return acc.is_deleted(), np.asarray(out)

    with pytest.raises(ValueError, match="donate_feeds"):
        build(("nope",))
    prev = compile_cache.activate(str(tmp_path / "cc"))
    try:
        cold = build(("acc",))
        feed = {"x": np.ones((2, 4), "float32"),
                "acc": np.zeros((2, 4), "float32")}
        assert cold.warm(feed) == "compile"
        deleted, want = run(cold)
        assert deleted
        warm = build(("acc",))
        assert warm.warm(feed) == "disk"
        deleted, got = run(warm)
        assert deleted          # never silently undonated from a disk hit
        np.testing.assert_array_equal(got, want)
        # the donated set is part of the key: the same program without
        # it is another entry (and keeps its input)
        plain = build(())
        assert plain.warm(feed) == "compile"
        deleted, got = run(plain)
        assert not deleted
        np.testing.assert_array_equal(got, want)
        sig = cold._sig(feed)
        keys = {compile_cache.entry_key(
            cold.program, cold.feed_names, cold.fetch_names, sig,
            cold._state_sig, "cpu", kind="predict", name="acc_step",
            donated=d) for d in ((), ("acc",), ("x", "acc"))}
        assert len(keys) == 3
    finally:
        compile_cache.activate(prev)
