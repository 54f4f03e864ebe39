"""Elastic fleet guard (parallel/elastic.py): heartbeats, straggler /
partition detection, collective deadlines, consensus checkpoints, and
the shrink-to-survivors acceptance run.

The end-to-end test is the ISSUE acceptance criterion: an N=4 simulated
fleet (threads sharing an InMemoryStore, one jax device per worker)
trains, one worker is killed mid-run through the ``collective`` fault
site (after a consensus checkpoint exists), the survivors detect the
silence, shrink the mesh, restore the last fleet-consistent checkpoint,
and finish with a finite loss — while a watchdog asserts no host-side
collective wait outlived its deadline.
"""
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid import resilience as R
from paddle_tpu.parallel import checkpoint as ckpt
from paddle_tpu.parallel import elastic as E
from paddle_tpu.parallel import fleet as fleet_mod
from paddle_tpu.parallel.mesh import build_mesh, shrink_mesh

pytestmark = pytest.mark.faults


@pytest.fixture(autouse=True)
def _no_leaked_injector():
    R.FaultInjector.uninstall()
    yield
    R.FaultInjector.uninstall()


def _cfg(**kw):
    """Test-speed knobs: sub-second detection, generous startup."""
    kw.setdefault("heartbeat_interval", 0.05)
    kw.setdefault("miss_threshold", 4)
    kw.setdefault("collective_timeout", 5.0)
    kw.setdefault("startup_grace", 2.0)
    return E.ElasticConfig(**kw)


# ---------------------------------------------------------------------------
# stores
# ---------------------------------------------------------------------------


def test_inmemory_store_roundtrip_and_isolation():
    s = E.InMemoryStore()
    s.put("hb", 0, {"step": 1})
    s.put("hb", 1, {"step": 2})
    s.put("other", 0, {"step": 99})
    assert s.all("hb") == {"0": {"step": 1}, "1": {"step": 2}}
    # returned dicts are copies: mutating them must not corrupt the store
    s.all("hb")["0"]["step"] = -1
    assert s.all("hb")["0"]["step"] == 1
    assert s.all("empty") == {}
    # consumers GC their mailboxes; deleting a missing key is a no-op
    s.delete("hb", 0)
    s.delete("hb", "never-existed")
    assert s.all("hb") == {"1": {"step": 2}}


def test_file_store_roundtrip_torn_write_and_hierarchy(tmp_path):
    s = E.FileStore(str(tmp_path / "store"))
    s.put("heartbeat", 3, {"step": 7, "state": "alive"})
    s.put("barrier/g0/shrink/1", 0, {"worker": 0})
    assert s.all("heartbeat") == {"3": {"step": 7, "state": "alive"}}
    assert s.all("barrier/g0/shrink/1") == {"0": {"worker": 0}}
    # a torn (half-written) beacon must be skipped, not crash readers
    d = os.path.join(s.root, "heartbeat")
    with open(os.path.join(d, "9.json"), "w") as f:
        f.write('{"step": 1')  # truncated JSON
    with open(os.path.join(d, "notes.txt"), "w") as f:
        f.write("not a beacon")
    assert s.all("heartbeat") == {"3": {"step": 7, "state": "alive"}}
    # a second write wins atomically
    s.put("heartbeat", 3, {"step": 8, "state": "alive"})
    assert s.all("heartbeat")["3"]["step"] == 8
    # delete GCs the beacon file (and a missing key is a no-op)
    s.delete("heartbeat", 3)
    s.delete("heartbeat", "never-existed")
    assert "3" not in s.all("heartbeat")


def test_file_store_mtime_cache_serves_repeats_without_rescanning(tmp_path):
    # counter deltas use >=: other tests' leftover daemon beaters may
    # poll their own FileStores and bump the same process-wide counters
    s = E.FileStore(str(tmp_path / "store"))
    s.put("hb", 0, {"step": 1})
    s.put("hb", 1, {"step": 2})
    # let the directory mtime tick age past the slack window so the
    # first scan is allowed to validate its cache entry
    time.sleep(s.MTIME_SLACK_NS / 1e9 + 0.05)
    first = s.all("hb")
    assert first == {"0": {"step": 1}, "1": {"step": 2}}
    assert s._cache, "first quiet scan did not populate the cache"
    cached_before = obs.counter("elastic.store_scan_cached")
    second = s.all("hb")
    third = s.all("hb")
    assert obs.counter("elastic.store_scan_cached") >= cached_before + 2
    # cached reads are equal to the fresh scan but independent copies
    assert second == first and third == first
    second["0"]["step"] = -99
    assert s.all("hb")["0"]["step"] == 1


def test_file_store_put_invalidates_mtime_cache(tmp_path):
    s = E.FileStore(str(tmp_path / "store"))
    s.put("hb", 0, {"step": 1})
    time.sleep(s.MTIME_SLACK_NS / 1e9 + 0.05)
    s.all("hb")
    assert s._cache, "quiet scan did not populate the cache"
    # a write drops the cache entry: the next read is a full scan that
    # observes the new payload, even within the same mtime tick
    s.put("hb", 0, {"step": 2})
    assert not s._cache, "put() left a stale cache entry behind"
    full_before = obs.counter("elastic.store_scan_full")
    assert s.all("hb")["0"]["step"] == 2
    assert obs.counter("elastic.store_scan_full") >= full_before + 1
    # delete() invalidates the same way
    time.sleep(s.MTIME_SLACK_NS / 1e9 + 0.05)
    s.all("hb")
    assert s._cache
    s.delete("hb", 0)
    assert not s._cache
    assert s.all("hb") == {}


# ---------------------------------------------------------------------------
# config
# ---------------------------------------------------------------------------


def test_elastic_config_env_knobs(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_HEARTBEAT_INTERVAL", "0.5")
    monkeypatch.setenv("PADDLE_TPU_HEARTBEAT_MISSES", "7")
    monkeypatch.setenv("PADDLE_TPU_COLLECTIVE_TIMEOUT", "12")
    monkeypatch.setenv("PADDLE_TPU_STRAGGLER_FACTOR", "2.5")
    monkeypatch.setenv("PADDLE_TPU_STRAGGLER_LAG", "6")
    cfg = E.ElasticConfig()
    assert cfg.heartbeat_interval == 0.5
    assert cfg.miss_threshold == 7
    assert cfg.collective_timeout == 12.0
    assert cfg.straggler_factor == 2.5
    assert cfg.straggler_lag == 6
    assert cfg.dead_after == pytest.approx(3.5)
    # explicit kwargs beat the env
    assert E.ElasticConfig(miss_threshold=2).miss_threshold == 2
    # garbage env values fall back to defaults instead of crashing
    monkeypatch.setenv("PADDLE_TPU_HEARTBEAT_INTERVAL", "soon")
    assert E.ElasticConfig().heartbeat_interval == 0.25


# ---------------------------------------------------------------------------
# heartbeat classification
# ---------------------------------------------------------------------------


def test_heartbeat_dead_detection_transition_and_leave():
    store = E.InMemoryStore()
    cfg = _cfg(heartbeat_interval=0.02, miss_threshold=2)  # dead at 0.04s
    m0 = E.HeartbeatMonitor(store, 0, 2, config=cfg)
    m1 = E.HeartbeatMonitor(store, 1, 2, config=cfg)
    m0.beat(1)
    m1.beat(1)
    assert m0.dead_peers() == set()
    time.sleep(cfg.dead_after + 0.05)
    m0.beat(2)  # we keep beating; peer 1 went silent
    assert m0.dead_peers() == {1}
    assert m0.dead_peers() == {1}
    # worker_dead fires once per transition, heartbeat_miss per probe
    assert m0.log.counters["worker_dead"] == 1
    assert m0.log.counters["heartbeat_miss"] >= 2
    miss = [e for e in m0.log.events if e["kind"] == "heartbeat_miss"][0]
    assert miss["worker"] == 1 and miss["threshold"] == cfg.dead_after
    # a resurrected beacon clears the classification...
    m1.beat(2)
    assert m0.dead_peers() == set()
    # ...and a clean leave() never reads as death, even after silence
    m1.leave()
    time.sleep(cfg.dead_after + 0.05)
    assert m0.dead_peers() == set()


def test_heartbeat_startup_grace_for_silent_birth():
    store = E.InMemoryStore()
    slow = E.HeartbeatMonitor(store, 0, 2, config=_cfg(startup_grace=30))
    slow.beat(1)
    # worker 1 never appeared, but is inside its startup grace
    assert slow.dead_peers() == set()
    fast = E.HeartbeatMonitor(store, 0, 2, config=_cfg(
        startup_grace=0.01, heartbeat_interval=0.01, miss_threshold=1))
    fast.beat(1)
    time.sleep(0.05)
    assert fast.dead_peers() == {1}


def test_straggler_step_lag_flag_and_recovery():
    store = E.InMemoryStore()
    cfg = _cfg(straggler_lag=3)
    m0 = E.HeartbeatMonitor(store, 0, 2, config=cfg)
    m1 = E.HeartbeatMonitor(store, 1, 2, config=cfg)
    m0.beat(10)
    m1.beat(4)          # lag 6 > 3
    assert m0.stragglers() == {1}
    assert m0.log.counters["straggler"] == 1
    ev = [e for e in m0.log.events if e["kind"] == "straggler"][0]
    assert ev["worker"] == 1 and ev["lag"] == 6
    m1.beat(10)         # caught up
    assert m0.stragglers() == set()
    assert m0.log.counters["straggler_recovered"] == 1


def test_straggler_latency_vs_fleet_median():
    store = E.InMemoryStore()
    cfg = _cfg(straggler_factor=3.0, straggler_lag=1000)
    mons = [E.HeartbeatMonitor(store, w, 3, config=cfg) for w in range(3)]
    mons[0].beat(5, latency=0.1)
    mons[1].beat(5, latency=0.1)
    mons[2].beat(5, latency=1.0)   # 10x the fleet median
    assert mons[0].stragglers() == {2}
    ev = [e for e in mons[0].log.events if e["kind"] == "straggler"][0]
    assert ev["latency"] == 1.0 and ev["median_latency"] == pytest.approx(0.1)


def test_partition_detection_via_stale_generation():
    store = E.InMemoryStore()
    m0 = E.HeartbeatMonitor(store, 0, 2, config=_cfg())
    m1 = E.HeartbeatMonitor(store, 1, 2, config=_cfg())
    m0.generation = 1          # this side joined the membership change
    m0.beat(5)
    m1.beat(5)                 # still beating on generation 0
    assert m0.partitioned_peers() == {1}
    assert m0.log.counters["partition"] == 1
    # the partitioned side itself sees nothing unusual
    assert m1.partitioned_peers() == set()
    # once the peer adopts the new generation, the split heals
    m1.generation = 1
    m1.beat(6)
    assert m0.partitioned_peers() == set()


def test_heartbeat_fault_site_kills_the_beacon():
    store = E.InMemoryStore()
    m = E.HeartbeatMonitor(store, 0, 2, config=_cfg())
    R.FaultInjector.install("heartbeat:at=2:RuntimeError")
    m.beat(1)
    with pytest.raises(RuntimeError, match="injected fault"):
        m.beat(2)
    # the fatal beat never landed: peers still see step 1
    assert m.table()[0]["step"] == 1


# ---------------------------------------------------------------------------
# collective deadlines + op-lowering guards
# ---------------------------------------------------------------------------


def test_collective_deadline_nesting_keeps_tighter():
    assert R.deadline_remaining() is None
    with R.collective_deadline(30):
        outer = R.deadline_remaining()
        assert 29 < outer <= 30
        with R.collective_deadline(0.5):
            assert R.deadline_remaining() <= 0.5
        with R.collective_deadline(100):  # looser nest must NOT extend
            assert R.deadline_remaining() <= 30
        assert 29 < R.deadline_remaining() <= 30
    assert R.deadline_remaining() is None
    with R.collective_deadline(None):     # no-op context
        assert R.deadline_remaining() is None


def test_collective_check_raises_on_expiry_and_fault():
    with R.collective_deadline(0):
        with pytest.raises(R.CollectiveTimeoutError, match="deadline"):
            R.collective_check("test-op")
    R.collective_check("test-op")  # unarmed: no-op
    R.FaultInjector.install("collective:at=1:ConnectionError")
    with pytest.raises(ConnectionError, match="injected fault"):
        R.collective_check("test-op")


class _Ctx:
    mesh_axes = {}


def test_collective_op_lowerings_hit_the_guard():
    from paddle_tpu.ops.registry import LOWERINGS

    x = np.ones(3, dtype=np.float32)
    # clean path: world-size-1 identity
    out = LOWERINGS["c_allreduce_sum"](_Ctx(), {"X": [x]}, {})
    np.testing.assert_array_equal(np.asarray(out["Out"][0]), x)
    # injected fault fires at trace time, before anything reaches XLA
    R.FaultInjector.install("collective:at=1:RuntimeError")
    with pytest.raises(RuntimeError, match="injected fault"):
        LOWERINGS["c_allgather"](_Ctx(), {"X": [x]}, {})
    R.FaultInjector.uninstall()
    # an expired deadline refuses to issue ANY collective, including
    # the world-size-1 identity path (entry point == accounting unit)
    with R.collective_deadline(0):
        for op in ("c_allreduce_sum", "c_allreduce_max", "c_allreduce_min",
                   "c_allreduce_prod", "c_allgather", "c_broadcast",
                   "c_reducescatter", "ppermute", "all_to_all"):
            with pytest.raises(R.CollectiveTimeoutError):
                LOWERINGS[op](_Ctx(), {"X": [x]}, {})
        with pytest.raises(R.CollectiveTimeoutError):
            LOWERINGS["barrier"](_Ctx(), {"X": [x]}, {})


def test_barrier_op_lowering_uses_barrier_site():
    from paddle_tpu.ops.registry import LOWERINGS

    x = np.ones(2, dtype=np.float32)
    R.FaultInjector.install("barrier:at=1:OSError")
    # collective ops don't consume barrier-site clauses
    LOWERINGS["c_allreduce_sum"](_Ctx(), {"X": [x]}, {})
    with pytest.raises(OSError, match="injected fault"):
        LOWERINGS["barrier"](_Ctx(), {"X": [x]}, {})


# ---------------------------------------------------------------------------
# fleet hardening + barrier timeouts
# ---------------------------------------------------------------------------


def test_uninitialized_fleet_apis_raise_typed_error():
    fl = fleet_mod.Fleet()
    with pytest.raises(fleet_mod.FleetNotInitializedError, match="init"):
        fl.barrier_worker()

    class Sloppy(fleet_mod.RoleMakerBase):
        def __init__(self):
            pass  # forgot super().__init__()

    rm = Sloppy()
    with pytest.raises(fleet_mod.FleetNotInitializedError):
        rm.generate_role()
    with pytest.raises(fleet_mod.FleetNotInitializedError):
        rm.worker_num()
    with pytest.raises(fleet_mod.FleetNotInitializedError):
        rm.worker_index()
    # a properly constructed role maker works
    ok = fleet_mod.UserDefinedRoleMaker(current_id=1, worker_num=4)
    ok.generate_role()
    assert ok._role_generated and ok.worker_num() == 4


def test_initialized_barrier_honors_fault_site_and_deadline():
    fl = fleet_mod.Fleet().init(
        fleet_mod.UserDefinedRoleMaker(worker_num=1))
    fl.barrier_worker()  # single-controller no-op
    R.FaultInjector.install("barrier:at=1:RuntimeError")
    with pytest.raises(RuntimeError, match="injected fault"):
        fl.barrier_worker()
    R.FaultInjector.uninstall()
    with R.collective_deadline(0):
        with pytest.raises(R.CollectiveTimeoutError):
            fl.barrier_worker()


def test_elastic_barrier_times_out_within_budget():
    store = E.InMemoryStore()
    guard = E.FleetGuard(None, store=store, worker_index=0, world_size=2,
                         config=_cfg(collective_timeout=0.3,
                                     startup_grace=30))
    fl = fleet_mod.Fleet().init(
        fleet_mod.UserDefinedRoleMaker(worker_num=2)).attach_elastic(guard)
    t0 = time.monotonic()
    with pytest.raises(R.CollectiveTimeoutError, match="timed out"):
        fl.barrier_worker()
    elapsed = time.monotonic() - t0
    assert elapsed < 2.0, "barrier blocked way past its 0.3s budget"
    # the wait was logged for the watchdog
    what, blocked = guard.block_log[-1]
    assert "barrier" in what and blocked <= 0.3 + 0.5


def test_armed_deadline_caps_barrier_budget():
    store = E.InMemoryStore()
    guard = E.FleetGuard(None, store=store, worker_index=0, world_size=2,
                         config=_cfg(collective_timeout=30,
                                     startup_grace=30))
    t0 = time.monotonic()
    with R.collective_deadline(0.2):
        with pytest.raises(R.CollectiveTimeoutError):
            guard.barrier("capped")
    assert time.monotonic() - t0 < 2.0


def test_wait_aborts_early_on_confirmed_dead_peer():
    store = E.InMemoryStore()
    cfg = _cfg(heartbeat_interval=0.02, miss_threshold=2,
               collective_timeout=10.0)
    guard = E.FleetGuard(None, store=store, worker_index=0, world_size=2,
                         config=cfg)
    peer = E.HeartbeatMonitor(store, 1, 2, config=cfg)
    guard.monitor.beat(1)
    peer.beat(1)
    time.sleep(cfg.dead_after + 0.1)   # peer goes silent
    t0 = time.monotonic()
    with pytest.raises(E.DeadPeerError) as exc:
        guard.barrier("doomed")
    assert exc.value.dead == frozenset({1})
    # DeadPeerError must beat the 10s timeout by a wide margin
    assert time.monotonic() - t0 < 3.0
    assert isinstance(exc.value, R.CollectiveTimeoutError)  # typed subset


def test_allreduce_mean_over_live_members():
    store = E.InMemoryStore()
    cfg = _cfg()
    guards = [E.FleetGuard(None, store=store, worker_index=w, world_size=2,
                           config=cfg) for w in range(2)]
    for g in guards:
        g.monitor.beat(1)
    results = [None, None]

    def run(w):
        results[w] = guards[w].allreduce_mean(
            np.full(3, float(w * 2 + 1)), tag="t1")

    threads = [threading.Thread(target=run, args=(w,)) for w in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10)
    for w in range(2):
        np.testing.assert_allclose(results[w], np.full(3, 2.0))  # (1+3)/2


# ---------------------------------------------------------------------------
# consensus checkpoints + corruption fallback
# ---------------------------------------------------------------------------


def test_consensus_markers_full_set_required(tmp_path):
    d = str(tmp_path)
    assert ckpt.latest_consensus_step(d) is None
    ckpt.mark_save_complete(d, 5, 0, world_size=2)
    assert ckpt.latest_consensus_step(d) is None      # worker 1 missing
    marker = ckpt.mark_save_complete(d, 5, 1, world_size=2)
    assert ckpt.latest_consensus_step(d) == 5
    with open(marker) as f:
        rec = json.load(f)
    assert rec["worker"] == 1 and rec["world"] == 2 and rec["step"] == 5
    assert rec["members"] == [0, 1]
    # a newer but incomplete step must NOT displace the consensus point
    ckpt.mark_save_complete(d, 7, 0, world_size=2)
    assert ckpt.latest_consensus_step(d) == 5
    assert ckpt.latest_consensus_step(d, world_size=2) == 5


def test_consensus_with_non_contiguous_survivor_set(tmp_path):
    # after a shrink the members are {0, 2, 3} — consensus must come
    # from the recorded member set, not range(world)
    d = str(tmp_path)
    for w in (0, 2, 3):
        ckpt.mark_save_complete(d, 9, w, world_size=4, members=[0, 2, 3])
    assert ckpt.latest_consensus_step(d) == 9
    # but demanding the full original world rejects it
    assert ckpt.latest_consensus_step(d, world_size=4) is None


def test_restore_latest_consensus_round_trip(tmp_path):
    d = str(tmp_path)
    for w in range(2):
        state = {"w0": np.full((2, 2), float(w)), "b0": np.arange(3.0)}
        ckpt.save_checkpoint(ckpt.worker_dir(d, w), state, step=3,
                             wait=True)
        ckpt.mark_save_complete(d, 3, w, world_size=2)
    step, state = ckpt.restore_latest_consensus(d, worker_index=1)
    assert step == 3
    np.testing.assert_array_equal(state["w0"], np.full((2, 2), 1.0))
    ckpt.finalize(ckpt.worker_dir(d, 0))
    ckpt.finalize(ckpt.worker_dir(d, 1))


def test_two_threads_of_one_process_save_at_the_same_moment(tmp_path):
    """The simulated fleet's workers are threads of one process, each
    with its own worker directory. orbax keeps ONE current operation id
    a process; two saves that enqueue together used to take each
    other's temporary directory away (FileExistsError / "Directory not
    empty" in the finalize thread). Twenty rounds released by a
    barrier: every step of either thread verifies and restores to what
    that thread saved."""
    d = str(tmp_path)
    rounds = 20
    gate = threading.Barrier(2)
    errors = {}

    def state_of(w, r):
        return {"w0": np.full((8, 8), 100.0 * w + r, "float32"),
                "b0": np.arange(5, dtype="int64") + 7 * w + r}

    def worker(w):
        try:
            for r in range(1, rounds + 1):
                gate.wait(timeout=30)
                ckpt.save_checkpoint(ckpt.worker_dir(d, w), state_of(w, r),
                                     step=r, wait=True)
        except BaseException as e:  # noqa: BLE001 — collected for asserts
            errors[w] = e
            gate.abort()

    threads = [threading.Thread(target=worker, args=(w,)) for w in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not any(t.is_alive() for t in threads)
    assert errors == {}
    for w in range(2):
        wdir = ckpt.worker_dir(d, w)
        assert ckpt.all_steps(wdir) == list(range(rounds, 0, -1))
        for r in range(1, rounds + 1):
            got = ckpt.load_checkpoint(wdir, step=r)
            assert ckpt.verify_checkpoint(wdir, r, state=got)
            want = state_of(w, r)
            assert sorted(got) == sorted(want)
            for k in want:
                np.testing.assert_array_equal(got[k], want[k])
        ckpt.finalize(wdir)


def test_corrupt_checkpoint_skipped_with_fallback(tmp_path):
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, {"w": np.full(4, 1.0)}, step=1, wait=True)
    ckpt.save_checkpoint(d, {"w": np.full(4, 2.0)}, step=2, wait=True)
    assert ckpt.all_steps(d) == [2, 1]
    assert ckpt.verify_checkpoint(d, 1) and ckpt.verify_checkpoint(d, 2)

    # scenario A: step dir that passes the cheap probe but cannot
    # restore (unreadable payload) -> warn + fall back to step 2
    fake = os.path.join(d, "3")
    os.makedirs(fake)
    with open(os.path.join(fake, "garbage.bin"), "wb") as f:
        f.write(b"\x00not a checkpoint")
    assert ckpt.verify_checkpoint(d, 3)       # probe can't tell
    with pytest.warns(UserWarning, match="failed to restore"):
        step, state = ckpt.restore_latest(d)
    assert step == 2
    np.testing.assert_array_equal(state["w"], np.full(4, 2.0))

    # scenario B: truncated payload in step 2 -> probe rejects it,
    # restore falls back another step
    for root, _dirs, files in os.walk(os.path.join(d, "2")):
        for fname in files:
            p = os.path.join(root, fname)
            if os.path.getsize(p) > 0:
                with open(p, "w"):
                    pass  # truncate to zero bytes
    assert not ckpt.verify_checkpoint(d, 2)
    with pytest.warns(UserWarning, match="corrupt/incomplete"):
        step, state = ckpt.restore_latest(d)
    assert step == 1
    np.testing.assert_array_equal(state["w"], np.full(4, 1.0))
    ckpt.finalize(d)


def test_interrupted_atomic_save_detected(tmp_path):
    # a leftover orbax tmp entry is the signature of a process killed
    # mid-rename: the step must fail the probe
    d = str(tmp_path / "ck")
    ckpt.save_checkpoint(d, {"w": np.ones(2)}, step=1, wait=True)
    os.makedirs(os.path.join(d, "1", "state.orbax-checkpoint-tmp-123"))
    assert not ckpt.verify_checkpoint(d, 1)
    assert ckpt.restore_latest(d) is None or True  # may warn; no crash
    ckpt.finalize(d)


# ---------------------------------------------------------------------------
# mesh / LocalSGD shrink
# ---------------------------------------------------------------------------


def test_shrink_mesh_survivors_and_dead():
    mesh = build_mesh({"dp": 8})
    devs = list(np.asarray(mesh.devices).flat)
    small = shrink_mesh(mesh, survivors=[1, 5])
    assert small.shape == {"dp": 2}
    assert list(np.asarray(small.devices).flat) == [devs[1], devs[5]]
    assert shrink_mesh(mesh, dead={0, 1}).shape == {"dp": 6}
    with pytest.raises(ValueError, match="no survivors"):
        shrink_mesh(mesh, survivors=[])
    with pytest.raises(ValueError, match="out of range"):
        shrink_mesh(mesh, survivors=[0, 99])
    tp = build_mesh({"dp": 4, "tp": 2})
    with pytest.raises(NotImplementedError, match="pure-dp"):
        shrink_mesh(tp, survivors=[0, 1])


def _build_lsgd_fleet(seed=11):
    fl = fleet_mod.Fleet().init()
    fluid.default_startup_program().random_seed = seed
    fluid.default_main_program().random_seed = seed
    x = fluid.data("shx", shape=[None, 6], dtype="float32")
    y = fluid.data("shy", shape=[None, 1], dtype="float32")
    h = fluid.layers.fc(x, 12, act="tanh")
    p = fluid.layers.fc(h, 1)
    loss = fluid.layers.reduce_mean(fluid.layers.square_error_cost(p, y))
    s = fleet_mod.DistributedStrategy()
    s.use_local_sgd = True
    s.local_sgd_k_steps = 2
    fl.distributed_optimizer(fluid.optimizer.SGD(0.05), s).minimize(loss)
    return fl, loss


def test_local_sgd_shrink_dp_rescales_denominator():
    rng = np.random.default_rng(0)
    x = rng.standard_normal((32, 6)).astype("float32")
    y = (x @ rng.standard_normal((6, 1))).astype("float32")
    fl, loss = _build_lsgd_fleet()
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for _ in range(2):
        exe.run(fl.main_program, feed={"shx": x, "shy": y},
                fetch_list=[loss])
    prog = fl._distributed_program
    scope = fluid.global_scope()
    pname = fluid.default_main_program().global_block() \
        .all_parameters()[0].name
    assert np.asarray(scope.find_value(pname)).shape[0] == 8

    # validation happens before any mutation
    with pytest.raises(ValueError, match=">= 2 surviving"):
        prog.shrink_dp(scope, [0])
    with pytest.raises(ValueError, match="out of range"):
        prog.shrink_dp(scope, [0, 11])
    assert np.asarray(scope.find_value(pname)).shape[0] == 8

    keep = [0, 2, 4, 6]
    before = np.asarray(scope.find_value(pname))
    new_mesh = prog.shrink_dp(scope, keep)
    assert new_mesh.shape == {"dp": 4}
    after = np.asarray(scope.find_value(pname))
    assert after.shape[0] == 4
    np.testing.assert_array_equal(after, before[keep])
    # the shrunken program keeps training with finite loss (pmean now
    # averages over 4 shards — a stale denominator would skew updates,
    # a stale jit cache would crash on the new stacked shapes)
    vals = []
    for _ in range(4):
        out = exe.run(prog, feed={"shx": x, "shy": y}, fetch_list=[loss])
        vals.append(float(np.asarray(out[0])))
    assert all(np.isfinite(v) for v in vals), vals
    assert vals[-1] <= vals[0], vals


# ---------------------------------------------------------------------------
# end-to-end: kill one of four workers mid-run
# ---------------------------------------------------------------------------


def _build_worker_net(seed=7):
    fluid.default_startup_program().random_seed = seed
    fluid.default_main_program().random_seed = seed
    x = fluid.data("ex", shape=[None, 4], dtype="float32")
    y = fluid.data("ey", shape=[None, 1], dtype="float32")
    p = fluid.layers.fc(x, 1)
    loss = fluid.layers.reduce_mean(fluid.layers.square_error_cost(p, y))
    fluid.optimizer.SGD(0.05).minimize(loss)
    return loss


def _feed_fn(step, guard=None):
    rng = np.random.default_rng(1000 + step)
    x = rng.standard_normal((8, 4)).astype("float32")
    return {"ex": x,
            "ey": (x.sum(1, keepdims=True) * 0.5).astype("float32")}


def _spawn_fleet(ckpt_dir, world=4, steps=20, cfg=None, fault_specs=None,
                 save_every=5, store=None):
    """Build `world` identical worker programs sequentially (real SPMD:
    every host builds the SAME program, so var names must line up),
    then run each worker's FleetGuard.train in a thread."""
    from paddle_tpu.fluid import executor as executor_mod
    from paddle_tpu.fluid import framework, unique_name

    store = store if store is not None else E.InMemoryStore()
    # the guards are built one after the other before any thread beats:
    # on a loaded machine that alone can outlast a 2 s startup grace
    cfg = cfg or _cfg(startup_grace=30.0)
    fault_specs = fault_specs or {}
    guards = []
    for w in range(world):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        old_gen = unique_name.switch()
        scope = executor_mod.Scope()
        loss = _build_worker_net()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        guards.append(E.FleetGuard(
            exe, program=fluid.default_main_program(), store=store,
            worker_index=w, world_size=world, config=cfg,
            ckpt_dir=ckpt_dir, fetch_list=[loss], feed_fn=_feed_fn,
            scope=scope, save_every=save_every, sync_every=1,
            fault_spec=fault_specs.get(w)))
        unique_name.switch(old_gen)
    results, errors = {}, {}

    def run(w):
        try:
            results[w] = guards[w].train(num_steps=steps)
        except BaseException as e:  # noqa: BLE001 — collected for asserts
            errors[w] = e

    threads = [threading.Thread(target=run, args=(w,), name="worker-%d" % w)
               for w in range(world)]
    for t in threads:
        t.start()
    # ONE deadline for the fleet: a wedge costs a minute, not a minute
    # (or two) for every thread in turn
    deadline = time.monotonic() + 60.0
    for t in threads:
        t.join(timeout=max(0.0, deadline - time.monotonic()))
    wedged = [t.name for t in threads if t.is_alive()]
    assert not wedged, "fleet wedged: %s (errors so far: %r)" % (
        wedged, errors)
    return guards, results, errors


def test_elastic_end_to_end_kill_detect_shrink_resume(tmp_path):
    """The acceptance run: 4 workers, worker 1 killed mid-run; the
    survivors detect the silence, shrink to {0, 2, 3}, restore the last
    fleet-consistent checkpoint, and finish with finite loss — no host
    wait outliving its deadline.

    The victim dies at its 13th ``collective`` check: two parameters are
    all-reduced a step, so that is the first all-reduce of step 7, which
    it enters only after the whole fleet posted step 6 — and a worker
    posts step 6 only after its step-5 save and done-marker. The
    consensus checkpoint of step 5 therefore EXISTS when the victim
    dies, whatever the threads' compiles did to the clock. What is
    asserted is what the fleet guarantees (who died, in which order the
    survivors reacted, from which step they resumed, the watchdog's
    bound); how many seconds a loaded CPU took to notice is not."""
    cfg = _cfg(heartbeat_interval=0.1, miss_threshold=5,
               collective_timeout=5.0, startup_grace=30.0)
    guards, results, errors = _spawn_fleet(
        str(tmp_path / "ck"), world=4, steps=20, cfg=cfg,
        fault_specs={1: "collective:at=13:RuntimeError"}, save_every=5)

    # the victim died of the injected fault; nobody else errored
    assert set(errors) == {1}, errors
    assert "injected fault" in str(errors[1])
    assert set(results) == {0, 2, 3}

    survivors = [0, 2, 3]
    for w in survivors:
        summary = results[w]
        # finished the full run on the shrunken fleet
        assert summary["final_step"] == 20
        assert summary["members"] == survivors
        assert summary["generation"] >= 1
        c = summary["counters"]
        assert c["worker_dead"] >= 1
        assert c["shrink"] >= 1
        assert c["restore"] >= 1          # consensus checkpoint applied
        assert c["resume"] >= 1
        events = summary["events"]

        def first(kind, **match):
            for i, e in enumerate(events):
                if e["kind"] == kind and all(
                        e.get(k) == v for k, v in match.items()):
                    return i
            raise AssertionError(
                "worker %d recorded no %r %r" % (w, kind, match))

        # detection is an ORDER: the victim's silence is seen, then it
        # is declared dead, then the fleet shrinks, then it restores
        assert (first("heartbeat_miss", worker=1)
                < first("worker_dead", worker=1)
                < first("shrink") < first("restore")), [
                    e["kind"] for e in events]
        # every recorded miss of the victim was past the threshold, and
        # the victim is the only worker anybody missed or buried
        misses = [e for e in events if e["kind"] == "heartbeat_miss"]
        assert {e["worker"] for e in misses} == {1}
        assert all(e["silent"] > cfg.dead_after for e in misses)
        dead_ev = [e for e in events if e["kind"] == "worker_dead"]
        assert [e["worker"] for e in dead_ev] == [1]
        # shrink recorded the right membership transition, and the
        # fleet resumed from the checkpoint that existed at the death
        shrink_ev = events[first("shrink")]
        assert shrink_ev["dead"] == [1]
        assert shrink_ev["survivors"] == survivors
        assert events[first("restore")]["step"] == 5
        # WATCHDOG: no host-side collective wait outlived its deadline
        assert guards[w].block_log, "no waits recorded"
        worst = max(s for _, s in guards[w].block_log)
        assert worst <= cfg.collective_timeout + 1.0, (
            "a wait outlived its deadline: %.2fs" % worst)
        assert summary["max_blocked"] == pytest.approx(worst)
        # finite final loss on the shrunken fleet (StepReport is the
        # fetch list)
        final = np.asarray(guards[w].last_report[0])
        assert np.isfinite(final).all()
    # survivors' meshes shrank to a 3-wide dp over the surviving devices
    for w in survivors:
        assert guards[w].mesh is not None
        assert guards[w].mesh.shape == {"dp": 3}
        dead_dev = guards[w]._device_of[1]
        live_devs = list(np.asarray(guards[w].mesh.devices).flat)
        # NB: with 4 workers on >= 4 virtual devices the victim's device
        # must have left the mesh (devices don't wrap around here)
        assert dead_dev not in live_devs
    # parameters converged to the same values on every survivor (the
    # store all-reduce keeps the fleet consistent after the shrink)
    p0 = np.asarray(guards[0]._scope.find_value(
        guards[0]._sync_names(guards[0]._program)[0]))
    for w in (2, 3):
        pw = np.asarray(guards[w]._scope.find_value(
            guards[w]._sync_names(guards[w]._program)[0]))
        np.testing.assert_allclose(pw, p0, rtol=1e-6, atol=1e-7)
    for w in range(4):
        ckpt.finalize(ckpt.worker_dir(str(tmp_path / "ck"), w))


def test_elastic_fleet_clean_run_no_faults(tmp_path):
    """Control: with no faults the fleet finishes at generation 0 with
    full membership and zero shrink/restore activity."""
    guards, results, errors = _spawn_fleet(
        str(tmp_path / "ck"), world=2, steps=6, save_every=3)
    assert errors == {}
    for w in range(2):
        s = results[w]
        assert s["final_step"] == 6 and s["generation"] == 0
        assert s["members"] == [0, 1]
        assert "shrink" not in s["counters"]
        assert s["counters"]["save"] == 2     # steps 3 and 6
    assert ckpt.latest_consensus_step(str(tmp_path / "ck")) == 6
    for w in range(2):
        ckpt.finalize(ckpt.worker_dir(str(tmp_path / "ck"), w))


@pytest.mark.slow
def test_elastic_chaos_survives_aggressive_faults(tmp_path):
    """Chaos lane: transient run-site faults on every worker PLUS a
    mid-run death. Guarded retries absorb the transients; the shrink
    path absorbs the death; the watchdog bound must still hold."""
    cfg = _cfg(heartbeat_interval=0.05, miss_threshold=5,
               collective_timeout=8.0, startup_grace=3.0)
    guards, results, errors = _spawn_fleet(
        str(tmp_path / "ck"), world=4, steps=24, cfg=cfg,
        fault_specs={
            0: "run:every=9:ConnectionError",
            1: "heartbeat:at=70:RuntimeError",
            2: "run:every=11:OSError",
            3: "run:every=13:ConnectionError",
        }, save_every=4)
    # at least the non-victim workers must finish; the watchdog holds
    # for everyone, finished or not
    finished = set(results)
    assert finished >= {0, 2, 3}, (finished, errors)
    for w in finished:
        assert results[w]["final_step"] == 24
        assert np.isfinite(np.asarray(guards[w].last_report[0])).all()
    for g in guards:
        if g.block_log:
            assert max(s for _, s in g.block_log) \
                <= cfg.collective_timeout + 1.5
    for w in range(4):
        ckpt.finalize(ckpt.worker_dir(str(tmp_path / "ck"), w))
