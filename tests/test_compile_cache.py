"""Persistent AOT compile cache (fluid/compile_cache.py): fingerprint
stability, disk-tier hits for fresh executors, corrupt-entry fallback,
TrainGuard co-location, and the scripted two-process warm-start
acceptance (a second process sharing PADDLE_TPU_COMPILE_CACHE_DIR must
record disk hits, emit zero compile_start events, and fetch identical
values)."""
import glob
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs
from paddle_tpu.fluid import compile_cache
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.fluid.executor import Scope


def _const_net():
    x = fluid.data("x", [None, 4], dtype="float32")
    y = fluid.layers.fc(
        x, size=3,
        param_attr=fluid.ParamAttr(
            name="ccw", initializer=fluid.initializer.Constant(0.25)),
        bias_attr=fluid.ParamAttr(
            name="ccb", initializer=fluid.initializer.Constant(0.5)))
    return x, y


def _entry_files(d):
    return glob.glob(os.path.join(str(d), "*" + compile_cache._SUFFIX))


# -- fingerprinting ---------------------------------------------------------

def test_program_fingerprint_stable_across_builds():
    def build(scale):
        unique_name.switch()
        prog = framework.Program()
        with fluid.program_guard(prog, framework.Program()):
            x = fluid.data("fx", [None, 8], dtype="float32")
            fluid.layers.scale(x, scale=scale)
        return prog

    a, b = build(2.0), build(2.0)
    assert a._uid != b._uid  # uids differ, fingerprints must not
    assert compile_cache.program_fingerprint(a) == \
        compile_cache.program_fingerprint(b)
    # a semantic difference (op attr) must change the hash
    c = build(3.0)
    assert compile_cache.program_fingerprint(a) != \
        compile_cache.program_fingerprint(c)


def test_unfingerprintable_program_raises():
    prog = framework.Program()
    with fluid.program_guard(prog, framework.Program()):
        x = fluid.data("ux", [None, 2], dtype="float32")
        fluid.layers.scale(x, scale=1.0)
    # a Python callable attr has no cross-process identity
    prog.global_block().ops[-1].attrs["callback"] = lambda: None
    with pytest.raises(compile_cache.Unfingerprintable):
        compile_cache.program_fingerprint(prog)


def test_activate_and_env_precedence(monkeypatch, tmp_path):
    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    prev = compile_cache.activate(str(tmp_path / "prog"))
    try:
        assert compile_cache.cache_dir() == str(tmp_path / "prog")
        assert compile_cache.enabled()
        # operator env var beats programmatic activation
        monkeypatch.setenv(compile_cache.CACHE_DIR_ENV,
                           str(tmp_path / "env"))
        assert compile_cache.cache_dir() == str(tmp_path / "env")
    finally:
        compile_cache.activate(prev)


def test_checkpoint_colocation_helper(tmp_path):
    from paddle_tpu.parallel import checkpoint as ckpt

    d = ckpt.compile_cache_dir(str(tmp_path))
    assert d == os.path.join(str(tmp_path), ckpt.COMPILE_CACHE_SUBDIR)
    # non-numeric subdir: the step scanner must never mistake it for a
    # checkpoint step
    os.makedirs(d)
    assert ckpt.latest_step(str(tmp_path)) is None


# -- the disk tier, in process ---------------------------------------------

def test_fresh_executor_hits_disk_tier(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "on")
    _, y = _const_net()
    feed = {"x": np.ones((2, 4), "float32")}
    exe1 = fluid.Executor(fluid.CPUPlace())
    exe1.run(fluid.default_startup_program())
    (out1,) = exe1.run(feed=feed, fetch_list=[y])
    assert _entry_files(tmp_path), "expected serialized cache entries"

    # a FRESH executor + fresh scope (empty in-memory LRU, params not
    # yet initialized) models a warm restart: its compiles must come
    # from disk with no compile_start emitted
    hits0 = obs.counter("compile_cache.disk_hit")
    starts0 = len(obs.get_recorder().of("compile_start"))
    exe2 = fluid.Executor(fluid.CPUPlace())
    s2 = Scope()
    exe2.run(fluid.default_startup_program(), scope=s2)
    (out2,) = exe2.run(feed=feed, fetch_list=[y], scope=s2)
    assert obs.counter("compile_cache.disk_hit") - hits0 >= 1
    assert len(obs.get_recorder().of("compile_start")) == starts0
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_corrupt_entry_falls_back_to_recompile(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.CACHE_DIR_ENV, str(tmp_path))
    monkeypatch.setenv("PADDLE_TPU_TELEMETRY", "on")
    _, y = _const_net()
    feed = {"x": np.ones((2, 4), "float32")}
    exe1 = fluid.Executor(fluid.CPUPlace())
    exe1.run(fluid.default_startup_program())
    (out1,) = exe1.run(feed=feed, fetch_list=[y])
    files = _entry_files(tmp_path)
    assert files
    for path in files:
        with open(path, "wb") as f:
            f.write(b"not a serialized export")

    corrupt0 = obs.counter("compile_cache.corrupt")
    stores0 = obs.counter("compile_cache.store")
    exe2 = fluid.Executor(fluid.CPUPlace())
    s2 = Scope()
    exe2.run(fluid.default_startup_program(), scope=s2)
    (out2,) = exe2.run(feed=feed, fetch_list=[y], scope=s2)
    # corrupt entries were evicted, recompiled, and re-stored
    assert obs.counter("compile_cache.corrupt") - corrupt0 >= 1
    assert obs.counter("compile_cache.store") - stores0 >= 1
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))
    for path in _entry_files(tmp_path):
        assert os.path.getsize(path) > 100, "refilled entry looks torn"


def test_trainguard_colocates_compile_cache(monkeypatch, tmp_path):
    from paddle_tpu.fluid.resilience import TrainGuard
    from paddle_tpu.parallel import checkpoint as ckpt

    monkeypatch.delenv(compile_cache.CACHE_DIR_ENV, raising=False)
    prev = compile_cache._default_dir
    try:
        x = fluid.data("x", [None, 4], dtype="float32")
        y = fluid.layers.fc(x, size=2)
        loss = fluid.layers.reduce_mean(y)
        fluid.optimizer.SGD(learning_rate=0.01).minimize(loss)
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(fluid.default_startup_program())
        guard = TrainGuard(
            exe, ckpt_dir=str(tmp_path), fetch_list=[loss],
            feed_fn=lambda step: {
                "x": np.full((2, 4), 0.1 * step, "float32")},
            save_every=0, final_save=False, compile_cache=True)
        guard.train(num_steps=2)
        cache_d = ckpt.compile_cache_dir(str(tmp_path))
        assert compile_cache.cache_dir() == os.path.abspath(cache_d)
        assert _entry_files(cache_d), \
            "TrainGuard(compile_cache=True) stored nothing"
    finally:
        compile_cache.activate(prev)
    # without ckpt_dir there is nowhere to co-locate
    with pytest.raises(ValueError):
        TrainGuard(exe, compile_cache=True)


# -- scripted acceptance: two processes, one cache directory ----------------

_CHILD = r"""
import json
import numpy as np
import paddle_tpu.fluid as fluid
from paddle_tpu import observability as obs

x = fluid.data("x", [None, 4], dtype="float32")
y = fluid.layers.fc(
    x, size=3,
    param_attr=fluid.ParamAttr(
        name="w", initializer=fluid.initializer.Constant(0.25)),
    bias_attr=fluid.ParamAttr(
        name="b", initializer=fluid.initializer.Constant(0.5)))
loss = fluid.layers.reduce_mean(y)
exe = fluid.Executor(fluid.CPUPlace())
exe.run(fluid.default_startup_program())
feed = {"x": (np.arange(8, dtype="float32") / 7.0).reshape(2, 4)}
out = exe.run(feed=feed, fetch_list=[y, loss])
print(json.dumps({
    "out": [np.asarray(v).tolist() for v in out],
    "disk_hit": obs.counter("compile_cache.disk_hit"),
    "disk_miss": obs.counter("compile_cache.disk_miss"),
    "store": obs.counter("compile_cache.store"),
    "compile_start": len(obs.get_recorder().of("compile_start")),
}))
"""


def _run_child(script_path, cache_dir):
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PADDLE_TPU_TELEMETRY": "on",
        "PADDLE_TPU_COMPILE_CACHE_DIR": str(cache_dir),
        "PYTHONPATH": os.pathsep.join(
            p for p in (
                os.path.dirname(os.path.dirname(
                    os.path.abspath(paddle_tpu.__file__))),
                env.get("PYTHONPATH"),
            ) if p),
    })
    proc = subprocess.run(
        [sys.executable, str(script_path)], env=env, timeout=240,
        capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.perf
def test_two_process_warm_start(tmp_path):
    """ISSUE 4 acceptance: the second of two processes sharing one
    PADDLE_TPU_COMPILE_CACHE_DIR records disk hits, emits ZERO
    compile_start events for the cached signatures, and fetches
    identical values."""
    child = tmp_path / "child.py"
    child.write_text(_CHILD)
    cache_dir = tmp_path / "cache"
    r1 = _run_child(child, cache_dir)
    assert r1["disk_hit"] == 0
    assert r1["compile_start"] >= 1  # cold: startup + main compiles
    assert r1["store"] >= 1
    r2 = _run_child(child, cache_dir)
    assert r2["disk_hit"] >= 1
    assert r2["compile_start"] == 0, \
        "warm process must not compile cached signatures"
    assert r2["disk_miss"] == 0
    np.testing.assert_array_equal(np.asarray(r1["out"][0]),
                                  np.asarray(r2["out"][0]))
    np.testing.assert_array_equal(np.asarray(r1["out"][1]),
                                  np.asarray(r2["out"][1]))
