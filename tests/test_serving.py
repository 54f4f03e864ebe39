"""Serving engine (ISSUE 5): dynamic micro-batching, shape buckets,
model registry hot reload, HTTP frontend, admission control, and the
compile-cache warm-start path.

Identity note: a coalesced, padded micro-batch is bit-identical to the
same padded batch run directly; against ``Predictor.run`` of one request
alone (another batch shape, another XLA program) it agrees to the last
few units of float32 on a CPU backend (``served_equal`` in conftest.py).
The degenerate 1-row executable may take a different matvec path: the
1-row case asserts allclose. Two runs of one shape stay bit-exact.
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.serving import (
    BucketSpec, DeadlineExceededError, EngineClosedError, ModelRegistry,
    ServingEngine, ServingServer, ShedError,
)


def _build_and_save(dirname, seed=5):
    """A tiny 2-layer softmax model saved as an inference dir; weights
    are deterministic per `seed` (different seeds -> different models)."""
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = seed
    x = fluid.data(name="x", shape=[None, 6], dtype="float32")
    h = fluid.layers.fc(x, size=12, act="relu")
    out = fluid.layers.fc(h, size=3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    fluid.io.save_inference_model(
        str(dirname), ["x"], [out], exe,
        main_program=fluid.default_main_program())


def _mk_engine(tmp_path, seed=5, **opts):
    d = tmp_path / "model"
    if not (d / "__model__").exists():
        _build_and_save(d, seed=seed)
    pred = Predictor.from_model(str(d))
    opts.setdefault("buckets", [BucketSpec({"x": (6,)},
                                           batch_sizes=(1, 2, 4, 8))])
    return ServingEngine(pred, name="t", **opts), pred


# ---------------------------------------------------------------------------
# batcher units
# ---------------------------------------------------------------------------

def test_bucket_spec_and_assembly():
    spec = BucketSpec({"x": (6,)}, batch_sizes=(8, 1, 4, 2, 2))
    assert spec.batch_sizes == (1, 2, 4, 8)
    assert spec.signature() == (("x", (6,), "float32"),)
    feeds = spec.feeds_for(4)
    assert feeds["x"].shape == (4, 6) and feeds["x"].dtype == np.float32

    assert serving.round_up_pow2(1) == 1
    assert serving.round_up_pow2(3) == 4
    assert serving.round_up_pow2(8) == 8
    with pytest.raises(ValueError):
        serving.round_up_pow2(0)
    with pytest.raises(ValueError):
        BucketSpec({})
    with pytest.raises(ValueError):
        BucketSpec({"x": (6,)}, batch_sizes=())

    class R:
        def __init__(self, a):
            self.feeds = {"x": a}

    a = np.arange(12, dtype=np.float32).reshape(2, 6)
    b = np.arange(6, dtype=np.float32).reshape(1, 6) + 100
    out = serving.batcher.assemble(["x"], [R(a), R(b)], 4)
    assert out["x"].shape == (4, 6)
    np.testing.assert_array_equal(out["x"][:2], a)
    np.testing.assert_array_equal(out["x"][2], b[0])
    np.testing.assert_array_equal(out["x"][3], b[0])  # edge padding


def test_tail_signature_groups_by_trailing_shape():
    f1 = {"x": np.zeros((2, 6), "float32")}
    f2 = {"x": np.zeros((5, 6), "float32")}
    f3 = {"x": np.zeros((2, 7), "float32")}
    assert serving.tail_signature(f1) == serving.tail_signature(f2)
    assert serving.tail_signature(f1) != serving.tail_signature(f3)


# ---------------------------------------------------------------------------
# predictor satellites
# ---------------------------------------------------------------------------

def test_from_model_uses_private_scope(tmp_path):
    """Loading two models with identical var names must not clobber —
    params live in a per-predictor scope, not global_scope()."""
    d1, d2 = tmp_path / "m1", tmp_path / "m2"
    _build_and_save(d1, seed=7)
    _build_and_save(d2, seed=11)
    # drop the training-time global-scope params so the check below sees
    # only what from_model loads
    from paddle_tpu.fluid import executor as executor_mod

    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    p1 = Predictor.from_model(str(d1))
    p2 = Predictor.from_model(str(d2))
    assert not list(fluid.global_scope().keys()), \
        "from_model leaked params into the process-wide scope"
    xv = np.ones((2, 6), np.float32)
    o1 = p1.run({"x": xv})[0]
    o2 = p2.run({"x": xv})[0]
    assert not np.allclose(o1, o2), \
        "two models with overlapping var names clobbered each other"
    # and p1 STILL answers like p1 after p2 loaded (no late clobber)
    np.testing.assert_array_equal(p1.run({"x": xv})[0], o1)


def test_get_exec_thread_safe_single_compile(tmp_path):
    """N concurrent first callers of one signature -> exactly one
    compile (the check-then-compile race is locked per signature).
    Runs under the armed scope sanitizer: the serving path must not
    trip a single cross-thread scope-write violation."""
    from paddle_tpu.analysis import sanitizer

    d = tmp_path / "m"
    _build_and_save(d)
    pred = Predictor.from_model(str(d))
    obs.reset()
    xv = np.random.default_rng(0).normal(size=(4, 6)).astype(np.float32)
    outs, errs = [], []

    def hit():
        try:
            outs.append(pred.run({"x": xv})[0])
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    import os

    # off by default (zero hot-path cost) unless the environment armed
    # it process-wide
    if os.environ.get(sanitizer.SANITIZER_ENV, "").lower() \
            not in ("1", "on", "true"):
        assert not sanitizer.armed()
    was_armed = sanitizer.armed()
    sanitizer.arm()
    sanitizer.reset()
    try:
        threads = [threading.Thread(target=hit) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    finally:
        if not was_armed:
            sanitizer.disarm()
    assert not errs
    assert sanitizer.violations() == []
    sanitizer.reset()
    assert pred.profile()["n_engines"] == 1
    assert len(obs.get_recorder().of("compile_start")) == 1
    for o in outs[1:]:
        np.testing.assert_array_equal(o, outs[0])


def test_predictor_device_array_passthrough_and_monotonic(tmp_path):
    import jax

    d = tmp_path / "m"
    _build_and_save(d)
    pred = Predictor.from_model(str(d))
    xv = np.random.default_rng(1).normal(size=(2, 6)).astype(np.float32)
    ref = pred.run({"x": xv})[0]
    dev = jax.device_put(xv)
    np.testing.assert_array_equal(pred.run({"x": dev})[0], ref)
    # same signature either way: one engine, one compile_seconds entry
    prof = pred.profile()
    assert prof["n_engines"] == 1
    (dt,) = prof["compile_seconds"].values()
    assert 0 <= dt < 300  # monotonic delta, not an epoch timestamp
    # dtype coercion happens at prepare: float64 input still hits the
    # float32 engine instead of compiling a second one
    np.testing.assert_array_equal(
        pred.run({"x": xv.astype(np.float64)})[0], ref)
    assert pred.profile()["n_engines"] == 1


# ---------------------------------------------------------------------------
# engine: coalescing, bit-identity, admission control
# ---------------------------------------------------------------------------

def test_concurrent_clients_coalesce_bit_identical(tmp_path, served_equal):
    obs.reset()
    engine, pred = _mk_engine(
        tmp_path, max_batch_size=8, max_wait_ms=60.0, auto_start=False)
    rng = np.random.default_rng(0)
    reqs = {i: rng.normal(size=(2 + i % 2, 6)).astype(np.float32)
            for i in range(8)}
    refs = {i: pred.run({"x": v})[0] for i, v in reqs.items()}
    futs = {i: engine.submit({"x": v}) for i, v in reqs.items()}
    engine.start()  # everything queued first -> coalescing is guaranteed
    for i, f in futs.items():
        out, = f.result(timeout=30)
        assert served_equal(out, refs[i])
    stats = engine.stats()
    assert stats["requests"] == 8
    assert stats["coalesced"] >= 1
    assert stats["batches"] < 8, "nothing coalesced"
    hist = obs.histogram("serving.batch_size")
    assert hist and hist["max"] >= 2
    assert obs.histogram("serving.queue_wait_seconds")["count"] == 8
    assert obs.histogram("serving.request_seconds")["count"] == 8
    waste = obs.histogram("serving.padding_waste")
    assert waste and 0.0 <= waste["max"] < 1.0
    engine.stop()


def test_single_row_requests_coalesce_close(tmp_path):
    """1-row requests batch too; XLA's 1-row matvec path may differ in
    the last bit from the batched kernel, so this case is allclose."""
    engine, pred = _mk_engine(
        tmp_path, max_batch_size=4, max_wait_ms=60.0, auto_start=False)
    rng = np.random.default_rng(3)
    reqs = [rng.normal(size=(1, 6)).astype(np.float32) for _ in range(4)]
    refs = [pred.run({"x": v})[0] for v in reqs]
    futs = [engine.submit({"x": v}) for v in reqs]
    engine.start()
    for f, ref in zip(futs, refs):
        np.testing.assert_allclose(
            f.result(timeout=30)[0], ref, rtol=1e-6, atol=1e-7)
    assert engine.stats()["coalesced"] >= 1
    engine.stop()


def test_queue_full_sheds_with_event(tmp_path):
    obs.reset()
    engine, _ = _mk_engine(tmp_path, queue_capacity=2, auto_start=False)
    xv = np.ones((2, 6), np.float32)
    f1 = engine.submit({"x": xv})
    f2 = engine.submit({"x": xv})
    with pytest.raises(ShedError):
        engine.submit({"x": xv})
    assert engine.stats()["shed"] == 1
    assert obs.counter("serving.shed") == 1
    evs = obs.get_recorder().of("shed")
    assert evs and evs[0]["source"] == "serving" and evs[0]["rows"] == 2
    engine.start()  # queued work still completes after the shed
    assert f1.result(timeout=30)[0].shape == (2, 3)
    assert f2.result(timeout=30)[0].shape == (2, 3)
    engine.stop()


def test_deadline_expiry_rejects_queued_request(tmp_path):
    obs.reset()
    engine, _ = _mk_engine(tmp_path, auto_start=False)
    xv = np.ones((2, 6), np.float32)
    ok = engine.submit({"x": xv})  # no deadline
    doomed = engine.submit({"x": xv}, deadline_ms=1)
    time.sleep(0.05)
    engine.start()
    with pytest.raises(DeadlineExceededError):
        doomed.result(timeout=30)
    assert ok.result(timeout=30)[0].shape == (2, 3)
    assert engine.stats()["deadline_miss"] == 1
    assert obs.counter("serving.deadline_miss") == 1
    evs = obs.get_recorder().of("deadline_miss")
    assert evs and evs[0]["source"] == "serving"
    engine.stop()


def test_graceful_drain_and_closed_reject(tmp_path):
    engine, _ = _mk_engine(tmp_path, auto_start=False)
    xv = np.ones((3, 6), np.float32)
    futs = [engine.submit({"x": xv}) for _ in range(5)]
    engine.start()
    engine.stop(drain=True)
    for f in futs:
        assert f.result(timeout=1)[0].shape == (3, 3)  # all served
    with pytest.raises(EngineClosedError):
        engine.submit({"x": xv})
    # a never-started engine fails its queue loudly on non-drain stop
    engine2, _ = _mk_engine(tmp_path, auto_start=False)
    f = engine2.submit({"x": xv})
    engine2.stop(drain=False)
    with pytest.raises(EngineClosedError):
        f.result(timeout=1)


def test_drain_vs_submit_race_never_strands_a_request(tmp_path):
    """Regression (ISSUE 7 satellite): a submit that passed the cheap
    closed check while ``stop(drain=True)`` ran concurrently used to
    land its queue.put AFTER the drain finished — a silent drop (the
    future never resolved). Admission and the stop-side closed flip are
    now atomic under the admit lock, so the request either reaches the
    queue before the drain starts (and gets served) or raises
    EngineClosedError. This test pins the interleaving with a gated
    queue: the submitter is paused INSIDE admission, stop() is issued,
    and stop must block until the put completes."""
    engine, _ = _mk_engine(tmp_path, auto_start=True, max_wait_ms=1.0)
    entered, release = threading.Event(), threading.Event()
    inner = engine._q

    class GatedQueue:
        def put_nowait(self, item):
            entered.set()
            assert release.wait(timeout=10), "gate never released"
            return inner.put_nowait(item)

        def __getattr__(self, name):
            return getattr(inner, name)

    engine._q = GatedQueue()
    xv = np.ones((2, 6), np.float32)
    result = {}

    def submitter():
        result["future"] = engine.submit({"x": xv})

    t_submit = threading.Thread(target=submitter, daemon=True)
    t_submit.start()
    assert entered.wait(timeout=10)  # paused mid-admission, lock held

    t_stop = threading.Thread(
        target=engine.stop, kwargs={"drain": True}, daemon=True)
    t_stop.start()
    time.sleep(0.1)
    # the fix under test: stop() must NOT have completed the drain
    # while a submitter is inside admission
    assert t_stop.is_alive(), \
        "stop() finished around an in-progress submit"
    release.set()
    t_submit.join(timeout=10)
    t_stop.join(timeout=10)
    engine._q = inner
    # the raced request was either served or failed loudly — never
    # silently stranded
    out, = result["future"].result(timeout=10)
    assert out.shape == (2, 3)
    with pytest.raises(EngineClosedError):
        engine.submit({"x": xv})


def test_warmup_covers_buckets_no_recompile_in_traffic(tmp_path):
    engine, pred = _mk_engine(tmp_path, max_wait_ms=1.0)
    report = engine.warmup()
    assert len(report) == 4  # batch_sizes (1, 2, 4, 8)
    assert pred.profile()["n_engines"] == 4
    # a 3-row request pads into the 4-bucket: no new executable
    out, = engine.predict({"x": np.ones((3, 6), np.float32)})
    assert out.shape == (3, 3)
    assert pred.profile()["n_engines"] == 4
    engine.stop()


def test_row_misalignment_and_bad_feeds_error(tmp_path):
    engine, _ = _mk_engine(tmp_path, auto_start=True)
    with pytest.raises(ValueError):
        engine.submit({"x": np.ones((0, 6), np.float32)})
    with pytest.raises(KeyError):
        engine.submit({"nope": np.ones((2, 6), np.float32)})
    engine.stop()


# ---------------------------------------------------------------------------
# registry: isolation + hot reload
# ---------------------------------------------------------------------------

def test_registry_multi_model_isolation(tmp_path):
    d1, d2 = tmp_path / "a", tmp_path / "b"
    _build_and_save(d1, seed=7)
    _build_and_save(d2, seed=11)
    reg = ModelRegistry(max_wait_ms=1.0)
    reg.load("a", d1, buckets=[BucketSpec({"x": (6,)},
                                          batch_sizes=(2, 4))])
    reg.load("b", d2, buckets=[BucketSpec({"x": (6,)},
                                          batch_sizes=(2, 4))])
    assert reg.names() == ["a", "b"]
    xv = np.ones((2, 6), np.float32)
    oa = reg.get("a").predict({"x": xv})[0]
    ob = reg.get("b").predict({"x": xv})[0]
    assert not np.allclose(oa, ob)
    info = reg.info()
    assert info["a"]["version"] == 1 and info["a"]["stats"]["requests"] == 1
    assert reg.get("missing") is None
    with pytest.raises(KeyError):
        reg.reload("missing")
    engine_a = reg.get("a")
    reg.close()
    assert engine_a.closed and reg.names() == []
    with pytest.raises(EngineClosedError):
        engine_a.submit({"x": xv})


def test_hot_reload_swaps_mid_traffic(tmp_path):
    """Traffic hammers model `m` while v2 (different weights) swaps in:
    no request errors, outputs flip from v1's to v2's, version bumps,
    and the old engine drains."""
    d1, d2 = tmp_path / "v1", tmp_path / "v2"
    _build_and_save(d1, seed=7)
    _build_and_save(d2, seed=11)
    reg = ModelRegistry(max_wait_ms=1.0)
    reg.load("m", d1)
    xv = np.ones((2, 6), np.float32)
    ref1 = reg.get("m").predict({"x": xv})[0]
    old_engine = reg.get("m")

    stop = threading.Event()
    outs, errs = [], []

    def hammer():
        while not stop.is_set():
            try:
                outs.append(reg.get("m").predict({"x": xv})[0])
            except EngineClosedError:
                pass  # benign: raced the swap into a draining engine
            except Exception as e:  # noqa: BLE001
                errs.append(e)

    threads = [threading.Thread(target=hammer) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.05)
    reg.reload("m", d2)  # atomic swap; old engine drains in background
    ref2 = reg.get("m").predict({"x": xv})[0]
    time.sleep(0.1)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errs, errs[:3]
    assert reg.version("m") == 2
    assert not np.allclose(ref1, ref2)
    matched = sum(
        1 for o in outs
        if np.array_equal(o, ref1) or np.array_equal(o, ref2))
    assert matched == len(outs), "a request saw a half-loaded model"
    assert any(np.array_equal(o, ref2) for o in outs[-3:]) or \
        np.array_equal(reg.get("m").predict({"x": xv})[0], ref2)
    deadline = time.monotonic() + 10
    while not old_engine.closed and time.monotonic() < deadline:
        time.sleep(0.01)
    assert old_engine.closed, "old version was not drained"
    reg.close()


def test_reload_failure_leaves_current_version_serving(
        tmp_path, monkeypatch):
    """ISSUE 7 satellite: a reload whose replacement fails mid-build
    (corrupt dir) or mid-warmup must leave v1 published and serving —
    same engine object, same version, zero request errors, no limbo."""
    from paddle_tpu.serving import registry as registry_mod

    d1 = tmp_path / "v1"
    _build_and_save(d1, seed=7)
    reg = ModelRegistry(max_wait_ms=1.0)
    reg.load("m", d1, buckets=[BucketSpec({"x": (6,)},
                                          batch_sizes=(2, 4))])
    v1_engine = reg.get("m")
    xv = np.ones((2, 6), np.float32)
    ref1 = v1_engine.predict({"x": xv})[0]

    stop, errs = threading.Event(), []

    def hammer():
        while not stop.is_set():
            try:
                out = reg.get("m").predict({"x": xv})[0]
                np.testing.assert_array_equal(out, ref1)
            except Exception as e:  # noqa: BLE001
                errs.append(e)
                return

    threads = [threading.Thread(target=hammer, daemon=True)
               for _ in range(2)]
    for t in threads:
        t.start()

    # failure 1: the replacement predictor cannot even build
    with pytest.raises(Exception):
        reg.reload("m", tmp_path / "no-such-dir")
    assert reg.version("m") == 1 and reg.get("m") is v1_engine

    # failure 2: the replacement builds but its warmup blows up
    class BoomEngine(ServingEngine):
        def warmup(self):
            raise RuntimeError("seeded warmup failure")

    monkeypatch.setattr(registry_mod, "ServingEngine", BoomEngine)
    obs.reset()
    with pytest.raises(RuntimeError, match="seeded warmup failure"):
        reg.reload("m", d1)
    assert obs.get_recorder().of("model_load_failed")
    monkeypatch.undo()

    # no version limbo: v1 still the published engine, still serving
    assert reg.version("m") == 1
    assert reg.get("m") is v1_engine and not v1_engine.closed
    time.sleep(0.05)
    stop.set()
    for t in threads:
        t.join(timeout=10)
    assert not errs, errs[:3]
    # and a clean reload still works afterwards
    reg.reload("m", d1)
    assert reg.version("m") == 2
    reg.close()


# ---------------------------------------------------------------------------
# HTTP frontend
# ---------------------------------------------------------------------------

def _post(url, doc, timeout=30):
    req = urllib.request.Request(
        url, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.load(resp)
    except urllib.error.HTTPError as e:
        return e.code, json.load(e)


def test_http_errors_and_health(tmp_path):
    d = tmp_path / "m"
    _build_and_save(d)
    reg = ModelRegistry(max_wait_ms=1.0)
    reg.load("m", d)
    srv = ServingServer(reg).start()
    try:
        code, doc = _post(srv.url + "/v1/models/nope:predict",
                          {"feeds": {"x": [[0.0] * 6]}})
        assert code == 404
        code, doc = _post(srv.url + "/v1/models/m:predict", {"oops": 1})
        assert code == 400 and "bad request" in doc["error"]
        code, doc = _post(srv.url + "/v1/models/m:predict",
                          {"feeds": {"wrong_name": [[0.0] * 6]}})
        assert code == 400
        with urllib.request.urlopen(srv.url + "/healthz", timeout=10) as r:
            health = json.load(r)
        assert health["status"] == "ok" and "m" in health["models"]
        status = urllib.request.urlopen(
            srv.url + "/nothing-here", timeout=10)
    except urllib.error.HTTPError as e:
        assert e.code == 404
    else:
        raise AssertionError("GET /nothing-here returned %s" % status)
    finally:
        srv.stop(close_registry=True)


def test_http_429_retry_after_and_error_body(tmp_path):
    """ISSUE 7 satellite: a shed response carries a ``Retry-After``
    header derived from the engine's observed queue drain rate, and the
    JSON body names the shedding model (and replica, when the engine is
    fleet-addressed)."""
    d = tmp_path / "m"
    _build_and_save(d)
    reg = ModelRegistry()
    engine = reg.load("tiny", d, warm=False, queue_capacity=1,
                      auto_start=False)
    srv = ServingServer(reg).start()
    try:
        engine.submit({"x": np.zeros((1, 6), np.float32)})  # queue full
        # a known drain rate makes the hint deterministic:
        # (depth 1 + 1) / 0.5 req/s = 4 s
        engine.drain_rate = lambda: 0.5
        req = urllib.request.Request(
            srv.url + "/v1/models/tiny:predict",
            data=json.dumps({"feeds": {"x": [[0.0] * 6]}}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=10)
        e = ei.value
        assert e.code == 429
        assert e.headers["Retry-After"] == "4"
        doc = json.load(e)
        assert doc["model"] == "tiny"
        assert "replica" in doc  # None for a solo engine, rid in a fleet
        assert doc["retry_after_s"] == 4.0
        assert "queue full" in doc["error"]
    finally:
        srv.stop(close_registry=True)


def test_http_acceptance_mixed_shape_clients(tmp_path, served_equal):
    """ISSUE 5 acceptance (in-process half): N=8 concurrent clients
    with mixed shapes through the HTTP frontend get the results of a
    direct Predictor.run (to the last few units), with >= 1 coalesced
    batch, >= 1 shed under a full queue, and p50/p99 + padding-waste
    visible in /metrics."""
    obs.reset()
    d = tmp_path / "m"
    _build_and_save(d)
    baseline = Predictor.from_model(str(d))
    reg = ModelRegistry()
    # auto_start=False: requests pile up queued until start() below —
    # deterministic coalescing under test, not a timing lottery
    engine = reg.load(
        "m", d, buckets=[BucketSpec({"x": (6,)}, batch_sizes=(1, 2, 4, 8))],
        max_batch_size=8, max_wait_ms=30.0, auto_start=False)
    srv = ServingServer(reg).start()
    try:
        rng = np.random.default_rng(7)
        reqs = {i: rng.normal(size=(2 + i % 3, 6)).astype(np.float32)
                for i in range(8)}
        refs = {i: baseline.run({"x": v})[0] for i, v in reqs.items()}
        results, errors = {}, []

        def client(i):
            try:
                code, doc = _post(
                    srv.url + "/v1/models/m:predict",
                    {"feeds": {"x": reqs[i].tolist()}}, timeout=60)
                assert code == 200, doc
                o = doc["outputs"][0]
                results[i] = np.asarray(
                    o["data"], dtype=o["dtype"]).reshape(o["shape"])
            except Exception as e:  # noqa: BLE001
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in reqs]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 20
        while engine.queue_depth() < 8 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert engine.queue_depth() == 8
        engine.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors[:3]
        for i in reqs:
            assert served_equal(results[i], refs[i])

        stats = engine.stats()
        assert stats["coalesced"] >= 1, stats
        assert stats["batches"] < 8, stats

        # shed half: a capacity-1, never-started second model -> 429s
        shed_engine = reg.load(
            "tiny", d, warm=False, queue_capacity=1, auto_start=False)
        # server-side wait (timeout_s) must sit well under the client
        # socket timeout or request 1's 504-vs-client-timeout race flips
        # under load
        codes = [
            _post(srv.url + "/v1/models/tiny:predict",
                  {"feeds": {"x": [[0.0] * 6]},
                   "timeout_s": 5}, timeout=30)[0]
            for _ in range(3)
        ]
        # request 1 queues; 2 and 3 hit the full queue
        assert codes.count(429) == 2, codes
        assert obs.counter("serving.shed") >= 2
        shed_engine.stop(drain=False)

        prom = urllib.request.urlopen(
            srv.url + "/metrics", timeout=10).read().decode()
        assert 'paddle_tpu_serving_request_seconds_bucket{le="' in prom
        assert "paddle_tpu_serving_request_seconds_count" in prom
        assert "paddle_tpu_serving_padding_waste" in prom
        assert "paddle_tpu_serving_shed" in prom
        # legacy summary exposition stays reachable behind the flag
        assert ('paddle_tpu_serving_request_seconds{quantile="0.99"}'
                in obs.render_prom(style="summary"))
    finally:
        srv.stop(close_registry=True)


# ---------------------------------------------------------------------------
# two-process warm start (acceptance, restart half)
# ---------------------------------------------------------------------------

_CHILD = """
import json, sys
import numpy as np
import paddle_tpu  # noqa: F401
from paddle_tpu import observability as obs
from paddle_tpu import serving
from paddle_tpu.fluid.inference import Predictor

model_dir = sys.argv[1]
pred = Predictor.from_model(model_dir)
engine = serving.ServingEngine(
    pred, buckets=[serving.BucketSpec({"x": (6,)}, batch_sizes=(2, 4))],
    max_wait_ms=1.0, name="warm")
report = engine.warmup()
out, = engine.predict(
    {"x": (np.arange(12, dtype="float32") / 11.0).reshape(2, 6)})
engine.stop()
print(json.dumps({
    "out": np.asarray(out).tolist(),
    "sources": sorted(r["source"] for r in report),
    "disk_hit": obs.counter("compile_cache.disk_hit"),
    "store": obs.counter("compile_cache.store"),
    "compile_start": len(obs.get_recorder().of("compile_start")),
}))
"""


@pytest.mark.perf
def test_two_process_serving_warm_start(tmp_path):
    """ISSUE 5 acceptance (restart half): a restarted serving process
    sharing the compile-cache dir serves its first request having
    emitted ZERO compile_start events — every bucket executable came
    off the disk tier."""
    d = tmp_path / "model"
    _build_and_save(d)
    child = tmp_path / "child.py"
    child.write_text(_CHILD)
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_TELEMETRY": "on",
        "PADDLE_TPU_COMPILE_CACHE_DIR": str(tmp_path / "cache"),
        "PYTHONPATH": os.pathsep.join(p for p in (
            os.path.dirname(os.path.dirname(
                os.path.abspath(paddle_tpu.__file__))),
            env.get("PYTHONPATH"),
        ) if p),
    })

    def run_once():
        proc = subprocess.run(
            [sys.executable, str(child), str(d)], env=env, timeout=240,
            capture_output=True, text=True)
        assert proc.returncode == 0, proc.stderr[-2000:]
        return json.loads(proc.stdout.strip().splitlines()[-1])

    r1 = run_once()
    assert r1["sources"] == ["compile", "compile"]
    assert r1["compile_start"] == 2
    assert r1["store"] >= 2
    r2 = run_once()
    assert r2["sources"] == ["disk", "disk"]
    assert r2["compile_start"] == 0, \
        "restarted server must warm-start from the disk tier"
    assert r2["disk_hit"] >= 2
    np.testing.assert_array_equal(
        np.asarray(r1["out"]), np.asarray(r2["out"]))
