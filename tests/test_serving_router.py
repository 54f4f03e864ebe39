"""Serving fleet (ISSUE 7): ServingRouter over N replicas — least-
loaded dispatch, shed-aware failover, heartbeat-driven death + standby
backfill, drain-vs-kill preemption, autoscale, rolling version rollout
with auto-rollback, and the FileStore per-process transport.

Identity note: same contract as test_serving.py. A request the fleet
serves alone at its own batch shape equals direct ``Predictor.run``
bit for bit, across failovers, kills and the JSON wire format (float32
JSON round-trips are exact); one served from a coalesced or padded
micro-batch equals it to the last few units of float32
(``served_equal`` in conftest.py).
"""
import os
import subprocess
import sys
import threading
import time
from concurrent.futures import Future

import numpy as np
import pytest

from paddle_tpu import observability as obs
from paddle_tpu.fluid.inference import Predictor
from paddle_tpu.fluid.resilience import FaultInjector
from paddle_tpu.parallel.elastic import ElasticConfig, FileStore
from paddle_tpu.serving import BucketSpec, EngineClosedError, ShedError
from paddle_tpu.serving.router import (
    LocalReplica, NoReplicasError, ReplicaWorker, RolloutError,
    ServingRouter, StoreReplica, local_fleet, make_engine_factory,
)
from test_serving import _build_and_save

BUCKETS = [BucketSpec({"x": (6,)}, batch_sizes=(1, 2, 4, 8))]


def _cfg(**kw):
    """Fast heartbeat config so death detection fits in a test."""
    kw.setdefault("heartbeat_interval", 0.05)
    kw.setdefault("miss_threshold", 3)
    kw.setdefault("startup_grace", 5.0)
    return ElasticConfig(**kw)


def _fleet(dirname, n_replicas=2, **kw):
    kw.setdefault("config", _cfg())
    kw.setdefault("buckets", BUCKETS)
    kw.setdefault("max_wait_ms", 1.0)
    return local_fleet(dirname, n_replicas=n_replicas, name="m", **kw)


@pytest.fixture()
def model_dir(tmp_path):
    d = tmp_path / "m"
    _build_and_save(d)
    return d


# ---------------------------------------------------------------------------
# dispatch: balance + bit identity
# ---------------------------------------------------------------------------

def test_fleet_bit_identity_and_balance(model_dir, served_equal):
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        rng = np.random.default_rng(3)
        reqs = [rng.normal(size=(2 + i % 3, 6)).astype(np.float32)
                for i in range(16)]
        refs = [base.run({"x": v})[0] for v in reqs]
        futs = [router.submit({"x": v}) for v in reqs]
        for f, ref in zip(futs, refs):
            out, = f.result(timeout=30)
            assert served_equal(out, ref)
        stats = router.stats()
        assert stats["requests"] == 16
        assert stats["router_requests"] == 16
        assert stats["replicas_live"] == 2
        # both replicas actually served work (depth ties rotate
        # round-robin, so even a strictly serial stream spreads)
        per = [r.stats()["requests"] for r in router._live.values()]
        assert sum(per) == 16 and all(n > 0 for n in per), per
    finally:
        router.stop()


def test_router_wears_engine_duck_type(model_dir):
    router = _fleet(model_dir, n_replicas=2)
    try:
        assert router.queue_depth() == 0
        assert not router.closed
        assert router.request_timeout_s > 0
        assert isinstance(router.retry_after_hint(), float)
        out, = router.predict({"x": np.zeros((2, 6), np.float32)})
        assert out.shape == (2, 3)
    finally:
        router.stop()
    assert router.closed
    with pytest.raises(EngineClosedError):
        router.submit({"x": np.zeros((2, 6), np.float32)})


def test_bad_feeds_fail_fast_not_retried(model_dir):
    router = _fleet(model_dir, n_replicas=2)
    try:
        with pytest.raises((ValueError, KeyError)):
            router.submit({"wrong": np.zeros((2, 6), np.float32)})
        assert router.stats().get("router_retry", 0) == 0
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# failover
# ---------------------------------------------------------------------------

class _ShedFirst:
    """Wrap a replica so its first `n` submits shed — the router must
    steer those requests to a peer (and count the failovers)."""

    def __init__(self, inner, n=1):
        self._inner = inner
        self._left = n

    def submit(self, feeds, deadline_ms=None):
        if self._left > 0:
            self._left -= 1
            raise ShedError("synthetic shed", model=self._inner.name,
                            replica=self._inner.rid, retry_after=0.01)
        return self._inner.submit(feeds, deadline_ms=deadline_ms)

    def __getattr__(self, name):
        return getattr(self._inner, name)


def test_shed_failover_moves_request_to_peer(model_dir):
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        for rid in list(router._live):
            router._live[rid] = _ShedFirst(router._live[rid], n=1)
        x = np.random.default_rng(4).normal(size=(2, 6)).astype(np.float32)
        # first dispatch pass: candidate 1 sheds -> candidate 2 sheds ->
        # backoff round -> both shed quotas spent -> success
        out, = router.predict({"x": x}, timeout=30)
        np.testing.assert_array_equal(out, base.run({"x": x})[0])
        stats = router.stats()
        assert stats["failovers"] >= 2
        assert stats["router_retry"] >= 1
        assert obs.counter("serving.failovers") >= 2
        assert obs.counter("serving.router_retry") >= 1
    finally:
        router.stop()


def test_all_replicas_shedding_exhausts_to_shed_error(model_dir):
    router = _fleet(model_dir, n_replicas=2,
                    router_opts={"max_retries": 2, "retry_base_s": 0.01})
    try:
        for rid in list(router._live):
            router._live[rid] = _ShedFirst(router._live[rid], n=10_000)
        fut = router.submit({"x": np.zeros((2, 6), np.float32)})
        with pytest.raises(ShedError) as ei:
            fut.result(timeout=30)
        assert ei.value.model == "m"
        assert ei.value.retry_after is not None
    finally:
        router.stop()


def test_kill_replays_queued_requests_on_survivor(model_dir, served_equal):
    """The drain-then-kill contract, kill side: a dead replica's queued
    requests fail internally with EngineClosedError and the router
    replays every one on a survivor — zero client-visible failures."""
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        # replica 0 accepts work but never dispatches it (engine not
        # started): everything routed there is stranded until the kill
        victim = router._live[0]
        victim.engine.stop(drain=False, timeout=0.1)
        victim.engine._closed = False          # accept, don't dispatch
        victim.engine._stop_event.clear()
        rng = np.random.default_rng(5)
        reqs = [rng.normal(size=(2, 6)).astype(np.float32)
                for _ in range(8)]
        refs = [base.run({"x": v})[0] for v in reqs]
        futs = [router.submit({"x": v}) for v in reqs]
        assert victim.engine.queue_depth() > 0  # some landed on the victim
        victim.kill()
        for f, ref in zip(futs, refs):
            out, = f.result(timeout=30)
            assert served_equal(out, ref)
        assert obs.counter("serving.failovers") >= 1
    finally:
        router.stop()


def test_dead_replica_detected_and_standby_backfills(model_dir):
    obs.reset()
    router = _fleet(model_dir, n_replicas=2, n_standby=1)
    try:
        assert router.replicas_live() == [0, 1]
        router._live[0].kill()
        deadline = time.monotonic() + 10
        while 0 in router.replicas_live() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.replicas_live() == [1, 2]  # standby 2 backfilled
        assert obs.counter("serving.replica_dead") == 1
        out, = router.predict({"x": np.zeros((2, 6), np.float32)})
        assert out.shape == (2, 3)
    finally:
        router.stop()


def test_remove_replica_drains_queued_work(model_dir, served_equal):
    """Drain side of the preemption contract: planned removal finishes
    the replica's queue instead of replaying it."""
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        victim = router._live[0]
        victim.engine.stop(drain=False, timeout=0.1)
        victim.engine._closed = False
        victim.engine._stop_event.clear()
        x = np.random.default_rng(6).normal(size=(2, 6)).astype(np.float32)
        futs = [router.submit({"x": x}) for _ in range(4)]
        queued = victim.engine.queue_depth()
        assert queued > 0

        done = threading.Event()

        def remove():
            victim.engine.start()  # dispatch resumes so the drain ends
            router.remove_replica(0, drain=True)
            done.set()

        threading.Thread(target=remove, daemon=True).start()
        for f in futs:
            out, = f.result(timeout=30)
            assert served_equal(out, base.run({"x": x})[0])
        assert done.wait(timeout=30)
        assert router.replicas_live() == [1]
        assert obs.gauge("serving.replicas_live") == 1
        # clean departure: the survivor never declared it dead
        assert obs.counter("serving.replica_dead") == 0
        with pytest.raises(KeyError):
            router.remove_replica(0)
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# autoscale
# ---------------------------------------------------------------------------

class _FakeReplica:
    """Dispatch-surface stub with a settable queue depth; resolves
    every submit immediately (autoscale tests exercise the pressure
    loop, not the model)."""

    def __init__(self, rid, name="m"):
        self.rid = rid
        self.name = name
        self.depth = 0
        self.stopped = False

    def submit(self, feeds, deadline_ms=None):
        fut = Future()
        fut.set_result([np.zeros((1, 3), np.float32)])
        return fut

    def queue_depth(self):
        return self.depth

    def stats(self):
        return {}

    def retry_after_hint(self):
        return None

    def stop(self, drain=True, timeout=30.0):
        self.stopped = True


def test_autoscale_up_on_pressure_then_park_on_idle(tmp_path):
    obs.reset()
    store_cfg = _cfg(startup_grace=60.0)  # fakes never beat: stay "alive"
    live = [_FakeReplica(0), _FakeReplica(1)]
    standby = [_FakeReplica(2)]
    from paddle_tpu.parallel.elastic import InMemoryStore

    router = ServingRouter(
        live, store=InMemoryStore(), name="m", config=store_cfg,
        standby=standby, scale_up_depth=4, scale_down_depth=1,
        scale_window_s=0.2, health_interval=0.02)
    try:
        for r in live:
            r.depth = 8  # sustained pressure on every live replica
        deadline = time.monotonic() + 10
        while 2 not in router.replicas_live() and \
                time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.replicas_live() == [0, 1, 2]
        assert router._scaled_up == [2]

        for r in live + standby:
            r.depth = 0  # sustained idleness: scaled-up replica parks
        deadline = time.monotonic() + 10
        while 2 in router.replicas_live() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert router.replicas_live() == [0, 1]
        assert router._scaled_up == []
        assert [r.rid for r in router._standby] == [2]
        assert not standby[0].stopped  # parked WARM, not stopped
    finally:
        router.stop()


def test_scale_down_never_below_min_replicas(tmp_path):
    from paddle_tpu.parallel.elastic import InMemoryStore

    router = ServingRouter(
        [_FakeReplica(0)], store=InMemoryStore(), name="m",
        config=_cfg(startup_grace=60.0), min_replicas=1,
        start_health=False)
    try:
        router._scaled_up = [0]  # even if bookkeeping said scalable,
        router._scale_down()     # the floor holds
        assert router.replicas_live() == [0]
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# rolling reload
# ---------------------------------------------------------------------------

def _hammer(router, base, stop_evt, errors, results):
    rng = np.random.default_rng(os.getpid() & 0xFFFF)
    while not stop_evt.is_set():
        x = rng.normal(size=(2, 6)).astype(np.float32)
        try:
            out, = router.predict({"x": x}, timeout=30)
        except Exception as e:  # noqa: BLE001 — the assertion target
            errors.append(e)
            return
        results.append((x, out))


def test_rolling_reload_zero_downtime(model_dir, tmp_path, served_equal):
    obs.reset()
    d2 = tmp_path / "v2"
    _build_and_save(d2, seed=11)  # genuinely different weights
    base_v1 = Predictor.from_model(str(model_dir))
    base_v2 = Predictor.from_model(str(d2))
    router = _fleet(model_dir, n_replicas=2)
    try:
        stop_evt, errors, results = threading.Event(), [], []
        threads = [threading.Thread(
            target=_hammer, args=(router, base_v1, stop_evt, errors,
                                  results), daemon=True)
            for _ in range(3)]
        for t in threads:
            t.start()
        time.sleep(0.1)
        done = router.rolling_reload(
            d2, probe_feeds={"x": np.zeros((1, 6), np.float32)})
        time.sleep(0.1)
        stop_evt.set()
        for t in threads:
            t.join(timeout=30)
        assert not errors, errors[:3]  # ZERO failed requests
        assert sorted(done) == [0, 1]
        assert router.dirname == str(d2)
        assert all(r.version == 2 for r in router._live.values())
        # every mid-rollout answer is ONE of the two versions' (old
        # engine finishing vs new engine) — never a blend
        mismatched = 0
        for x, out in results:
            v1 = base_v1.run({"x": x})[0]
            v2 = base_v2.run({"x": x})[0]
            if not (served_equal(out, v1) or served_equal(out, v2)):
                mismatched += 1
        assert mismatched == 0
        # steady state after the rollout: v2 answers only
        x = np.random.default_rng(9).normal(size=(2, 6)).astype(np.float32)
        out, = router.predict({"x": x})
        np.testing.assert_array_equal(out, base_v2.run({"x": x})[0])
    finally:
        router.stop()


def test_rolling_reload_rolls_back_on_seeded_bad_version(
        model_dir, tmp_path):
    """Replica 0 upgrades fine; replica 1's reload is seeded to fail —
    the rollout must roll replica 0 BACK to v1 and raise, leaving the
    fleet uniformly on v1 with zero downtime."""
    obs.reset()
    d2 = tmp_path / "v2"
    _build_and_save(d2, seed=11)
    base_v1 = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        flaky = router._live[1]
        orig_reload = flaky.reload

        def seeded(dirname):
            if str(dirname) == str(d2):
                raise RuntimeError("seeded bad version")
            return orig_reload(dirname)

        flaky.reload = seeded
        stop_evt, errors, results = threading.Event(), [], []
        t = threading.Thread(
            target=_hammer, args=(router, base_v1, stop_evt, errors,
                                  results), daemon=True)
        t.start()
        with pytest.raises(RolloutError, match="seeded bad version"):
            router.rolling_reload(
                d2, probe_feeds={"x": np.zeros((1, 6), np.float32)})
        stop_evt.set()
        t.join(timeout=30)
        assert not errors, errors[:3]
        assert router.dirname == str(model_dir)  # rollout never landed
        assert router.replicas_live() == [0, 1]
        assert all(r.dirname == str(model_dir)
                   for r in router._live.values())
        assert obs.gauge("serving.rollout_state") == 2
        # uniformly v1: bit-identical to the v1 baseline
        x = np.random.default_rng(10).normal(size=(2, 6)) \
            .astype(np.float32)
        for _ in range(4):
            out, = router.predict({"x": x})
            np.testing.assert_array_equal(out, base_v1.run({"x": x})[0])
    finally:
        router.stop()


def test_rolling_reload_corrupt_dir_leaves_v1_serving(model_dir, tmp_path):
    """First replica's rebuild raises (missing model dir): no swap ever
    happens, the rollout aborts, and v1 keeps serving everywhere."""
    base_v1 = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    try:
        with pytest.raises(RolloutError):
            router.rolling_reload(tmp_path / "no-such-model")
        assert router.replicas_live() == [0, 1]
        assert router.dirname == str(model_dir)
        x = np.random.default_rng(12).normal(size=(2, 6)) \
            .astype(np.float32)
        out, = router.predict({"x": x})
        np.testing.assert_array_equal(out, base_v1.run({"x": x})[0])
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# FileStore transport (per-process replicas)
# ---------------------------------------------------------------------------

def test_store_replica_roundtrip_and_ctl_reload(
        model_dir, tmp_path, served_equal):
    base = Predictor.from_model(str(model_dir))
    store = FileStore(tmp_path / "store")
    cfg = _cfg()
    worker = ReplicaWorker(
        store, 0, make_engine_factory(name="m", replica_id=0, warm=False),
        model_dir, name="m", config=cfg)
    wt = threading.Thread(target=worker.run_forever, daemon=True)
    wt.start()
    proxy = StoreReplica(0, store, name="m", config=cfg)
    router = ServingRouter([proxy], store=store, name="m", config=cfg,
                           dirname=model_dir)
    try:
        x = np.random.default_rng(13).normal(size=(3, 6)) \
            .astype(np.float32)
        out, = router.predict({"x": x}, timeout=30)
        # the float32 JSON round-trip is exact; the worker pads the 3
        # rows to the next power of two
        assert served_equal(out, base.run({"x": x})[0])

        assert proxy.reload(model_dir, timeout=30) == 2
        assert worker.version == 2
        out2, = router.predict({"x": x}, timeout=30)
        np.testing.assert_array_equal(out2, out)  # same shape, same bits
    finally:
        router.stop()
        wt.join(timeout=10)
    assert not wt.is_alive()  # ctl stop terminated the worker loop


def test_store_replica_ctl_reload_failure_acks_error(model_dir, tmp_path):
    store = FileStore(tmp_path / "store")
    cfg = _cfg()
    worker = ReplicaWorker(
        store, 0, make_engine_factory(name="m", replica_id=0, warm=False),
        model_dir, name="m", config=cfg)
    wt = threading.Thread(target=worker.run_forever, daemon=True)
    wt.start()
    proxy = StoreReplica(0, store, name="m", config=cfg)
    try:
        with pytest.raises(RolloutError, match="failed reload"):
            proxy.reload(tmp_path / "nope", timeout=30)
        assert worker.version == 1  # no swap, no limbo
    finally:
        proxy.stop(timeout=10)
        wt.join(timeout=10)


def test_silent_store_replica_requests_replay_on_survivor(
        model_dir, tmp_path, served_equal):
    """A store replica whose worker never comes up: its in-flight
    requests are orphaned until the health loop declares it dead
    (startup grace), fails them with ReplicaGoneError, and the router
    replays each on the live local replica — zero client failures."""
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    store = FileStore(tmp_path / "store")
    cfg = _cfg(startup_grace=0.4)
    ghost = StoreReplica(0, store, name="m", config=cfg)  # no worker
    real = LocalReplica(
        1, make_engine_factory(name="m", replica_id=1, warm=False,
                               buckets=BUCKETS, max_wait_ms=1.0),
        store, name="m", config=cfg, dirname=str(model_dir))
    router = ServingRouter([ghost, real], store=store, name="m",
                           config=cfg, dirname=model_dir)
    try:
        rng = np.random.default_rng(14)
        reqs = [rng.normal(size=(2, 6)).astype(np.float32)
                for _ in range(6)]
        refs = [base.run({"x": v})[0] for v in reqs]
        futs = [router.submit({"x": v}) for v in reqs]
        for f, ref in zip(futs, refs):
            out, = f.result(timeout=30)
            assert served_equal(out, ref)
        assert router.replicas_live() == [1]
        assert obs.counter("serving.replica_dead") == 1
    finally:
        router.stop()


# ---------------------------------------------------------------------------
# fault-site drills
# ---------------------------------------------------------------------------

@pytest.mark.faults
@pytest.mark.chaos
def test_replica_fault_drill_absorbed_by_failover(
        model_dir, armed_sanitizers):
    obs.reset()
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2)
    FaultInjector.install("replica:at=1:RuntimeError")
    try:
        x = np.random.default_rng(15).normal(size=(2, 6)) \
            .astype(np.float32)
        for _ in range(4):  # first admission blows up; request survives
            out, = router.predict({"x": x}, timeout=30)
            np.testing.assert_array_equal(out, base.run({"x": x})[0])
        assert obs.counter("serving.failovers") >= 1
    finally:
        FaultInjector.uninstall()
        router.stop()


@pytest.mark.faults
@pytest.mark.chaos
def test_dispatch_and_slow_fault_drills(
        model_dir, monkeypatch, armed_sanitizers):
    base = Predictor.from_model(str(model_dir))
    router = _fleet(model_dir, n_replicas=2,
                    router_opts={"retry_base_s": 0.01})
    monkeypatch.setenv("PADDLE_TPU_FAULT_SLOW_S", "0.02")
    FaultInjector.install("dispatch:at=1:RuntimeError;replica:every=3:slow")
    try:
        x = np.random.default_rng(16).normal(size=(2, 6)) \
            .astype(np.float32)
        for _ in range(6):  # dispatch blip -> backoff retry; slow
            out, = router.predict({"x": x}, timeout=30)  # brownouts ride
            np.testing.assert_array_equal(out, base.run({"x": x})[0])
        assert router.stats()["router_retry"] >= 1
    finally:
        FaultInjector.uninstall()
        router.stop()


# ---------------------------------------------------------------------------
# process fleet (SIGKILL drill — the chaos lane's in-suite twin)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.chaos
@pytest.mark.multihost
def test_process_fleet_survives_sigkill(
        model_dir, tmp_path, armed_sanitizers):
    base = Predictor.from_model(str(model_dir))
    store_dir = tmp_path / "store"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    buckets_json = '[{"feeds": {"x": [6]}, "batch_sizes": [1,2,4,8]}]'
    procs = [subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving.router",
         "--store", str(store_dir), "--rid", str(rid), "--name", "m",
         "--model-dir", str(model_dir), "--no-warm",
         "--heartbeat-interval", "0.1", "--buckets", buckets_json],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT)
        for rid in (0, 1)]
    store = FileStore(store_dir)
    cfg = ElasticConfig(heartbeat_interval=0.1, miss_threshold=4,
                        startup_grace=120.0)
    router = ServingRouter(
        [StoreReplica(r, store, name="m", config=cfg) for r in (0, 1)],
        store=store, name="m", config=cfg, dirname=model_dir)
    try:
        x = np.random.default_rng(17).normal(size=(2, 6)) \
            .astype(np.float32)
        ref = base.run({"x": x})[0]
        out, = router.predict({"x": x}, timeout=120)
        np.testing.assert_array_equal(out, ref)

        procs[0].kill()  # SIGKILL: no drain, no goodbye
        deadline = time.monotonic() + 30
        while 0 in router.replicas_live() and \
                time.monotonic() < deadline:
            time.sleep(0.05)
        assert router.replicas_live() == [1]
        for _ in range(4):
            out, = router.predict({"x": x}, timeout=60)
            np.testing.assert_array_equal(out, ref)
    finally:
        router.stop()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()


# ---------------------------------------------------------------------------
# registry + HTTP integration
# ---------------------------------------------------------------------------

def test_published_router_behind_http(model_dir):
    import json
    import urllib.request

    from paddle_tpu.serving import ModelRegistry, ServingServer
    from test_serving import _post

    obs.reset()
    base = Predictor.from_model(str(model_dir))
    reg = ModelRegistry()
    router = _fleet(model_dir, n_replicas=2)
    reg.publish("m", router, dirname=model_dir)
    srv = ServingServer(reg).start()
    try:
        x = np.random.default_rng(18).normal(size=(2, 6)) \
            .astype(np.float32)
        code, doc = _post(srv.url + "/v1/models/m:predict",
                          {"feeds": {"x": x.tolist()}})
        assert code == 200
        o = doc["outputs"][0]
        np.testing.assert_array_equal(
            np.asarray(o["data"], dtype=o["dtype"]).reshape(o["shape"]),
            base.run({"x": x})[0])
        # /healthz reads the router through the registry's engine surface
        with urllib.request.urlopen(srv.url + "/healthz", timeout=10) as r:
            health = json.load(r)
        assert health["models"]["m"]["stats"]["replicas_live"] == 2

        # one synthetic full-shed pass so every fleet metric exists
        saved = dict(router._live)
        for rid in list(router._live):
            router._live[rid] = _ShedFirst(router._live[rid], n=1)
        code, _doc = _post(srv.url + "/v1/models/m:predict",
                           {"feeds": {"x": x.tolist()}})
        assert code == 200  # retried inside the router, client never saw it
        router._live.update(saved)
        prom = urllib.request.urlopen(
            srv.url + "/metrics", timeout=10).read().decode()
        assert "paddle_tpu_serving_replicas_live" in prom
        assert obs.gauge("serving.replicas_live") == 2
        assert "paddle_tpu_serving_failovers" in prom
        assert "paddle_tpu_serving_router_retry" in prom
        assert "paddle_tpu_serving_rollout_state" in prom

        # published engines reload through their own surface, not the
        # registry's build-and-swap
        with pytest.raises(ValueError, match="rolling_reload"):
            reg.reload("m")
    finally:
        srv.stop()
        router.stop()


def test_stopped_router_maps_to_503(model_dir):
    from paddle_tpu.serving import ModelRegistry, ServingServer
    from test_serving import _post

    reg = ModelRegistry()
    router = _fleet(model_dir, n_replicas=1)
    reg.publish("m", router)
    srv = ServingServer(reg).start()
    try:
        router.stop()
        code, doc = _post(srv.url + "/v1/models/m:predict",
                          {"feeds": {"x": [[0.0] * 6]}})
        assert code == 503
        assert doc["model"] == "m"
    finally:
        srv.stop()
