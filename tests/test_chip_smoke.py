"""The chip bring-up contract, as far as a CPU can check it: chip_smoke.py
refuses to run without a TPU and its explicit rehearsal walks every
phase's code; the device a Place names is never silently another one;
the XLA compile cache is placed by one rule; the native runtime is keyed
on its source and a failed build is loud."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import paddle_tpu.fluid as fluid

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(args, timeout, **env_extra):
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_COMPILATION_CACHE_DIR", "XLA_FLAGS")}
    env["JAX_PLATFORMS"] = "cpu"
    env.update(env_extra)
    return subprocess.run(
        [sys.executable] + args, capture_output=True, text=True,
        timeout=timeout, env=env, cwd=ROOT)


# ---------------------------------------------------------------------------
# chip_smoke.py
# ---------------------------------------------------------------------------
def test_chip_smoke_without_a_chip_exits_nonzero_and_prints_no_result():
    r = _run(["chip_smoke.py"], 120)
    assert r.returncode != 0
    assert r.stdout == ""
    assert "no TPU" in r.stderr and "platform=cpu" in r.stderr


def test_chip_smoke_rehearsal_walks_every_phase(tmp_path):
    """Tiny sizes, 4 virtual CPU devices so the multi-chip phase runs too,
    kernels in interpret mode, cache placed from outside."""
    r = _run(["chip_smoke.py", "--rehearse-cpu"], 600,
             XLA_FLAGS="--xla_force_host_platform_device_count=4",
             JAX_COMPILATION_CACHE_DIR=str(tmp_path / "xla"))
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL")
    assert json.loads(lines[-1]) == {
        "rehearsal_passed": True, "platform": "cpu", "devices": 4}
    # a rehearsal never prints the device result line
    assert '"ok"' not in r.stdout
    notes = {}
    for ln in lines:
        if ln.startswith("["):
            tag, doc = ln.split("] ", 1)
            notes.setdefault(tag[1:], []).append(json.loads(doc))
    assert notes["done"][0]["phases_passed"] == [
        "device", "trainer", "server", "kernels", "callback", "multichip"]
    assert notes["device"][0]["xla_cache_dir"] == str(tmp_path / "xla")
    assert notes["device"][0]["native_runtime"] in ("built", "loaded")
    assert [(n["kernel"], n["compiled_by"], len(n["cases"]))
            for n in notes["kernels"]] == [
                ("flash_attention", "interpreter", 8)]
    assert [n["mode"] for n in notes["multichip"]] == [
        "data_parallel", "dp_x_tp"]


# ---------------------------------------------------------------------------
# XLA compile-cache placement: one function, placed from outside
# ---------------------------------------------------------------------------
_CACHE_PROBE = """
import json, sys, tempfile
import jax
calls = []
_update = jax.config.update
jax.config.update = lambda k, v: (calls.append(k), _update(k, v))[1]
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import compile_cache, resilience
returned = compile_cache.configure_xla_cache()
x = fluid.data("x", [None, 4], dtype="float32")
loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 2))
resilience.TrainGuard(fluid.Executor(), ckpt_dir=tempfile.mkdtemp(),
                      fetch_list=[loss], compile_cache=True)
print(json.dumps({
    "returned": returned, "calls": calls,
    "dir": jax.config.jax_compilation_cache_dir,
    "min_s": jax.config.jax_persistent_cache_min_compile_time_secs}))
"""


def test_xla_cache_dir_from_the_environment_sets_nothing_in_code():
    r = _run(["-c", _CACHE_PROBE], 120, JAX_COMPILATION_CACHE_DIR="/x")
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    assert doc["dir"] == "/x" and doc["returned"] == "/x"
    assert doc["calls"] == []


def test_xla_cache_dir_defaults_to_the_checkout():
    r = _run(["-c", _CACHE_PROBE], 120)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(r.stdout.strip().splitlines()[-1])
    want = os.path.join(ROOT, ".jax_cache")
    assert doc["dir"] == want and doc["returned"] == want
    # the directory, once; never the min-compile-time (jax's own default)
    assert doc["calls"] == ["jax_compilation_cache_dir"]
    assert doc["min_s"] == 1.0


def test_only_compile_cache_module_names_the_xla_cache_options():
    named = set()
    for top, dirs, files in os.walk(ROOT):
        dirs[:] = [d for d in dirs
                   if not d.startswith(".") and d != "chiprun_out"]
        for name in files:
            if name.endswith((".py", ".sh")):
                path = os.path.join(top, name)
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                if ("jax_compilation_cache_dir" in text or
                        "jax_persistent_cache_min_compile_time_secs" in text):
                    named.add(os.path.relpath(path, ROOT))
    assert named == {"paddle_tpu/fluid/compile_cache.py",
                     "tests/test_chip_smoke.py"}


# ---------------------------------------------------------------------------
# places name a device or raise
# ---------------------------------------------------------------------------
def test_tpu_place_raises_on_a_cpu_only_backend():
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.TPUPlace(0).jax_device()
    with pytest.raises(RuntimeError, match="no accelerator"):
        fluid.CUDAPlace(0).jax_device()
    assert fluid.tpu_places() == []
    assert isinstance(fluid.Executor().place, fluid.CPUPlace)


def test_place_id_out_of_range_raises_rather_than_wraps(monkeypatch):
    import jax

    assert fluid.CPUPlace(7).jax_device() == jax.devices("cpu")[7]
    with pytest.raises(RuntimeError, match="out of range"):
        fluid.CPUPlace(8).jax_device()

    class _Chip:
        platform = "tpu"

    chips = [_Chip(), _Chip()]
    monkeypatch.setattr(jax, "devices", lambda *a: chips)
    assert fluid.TPUPlace(1).jax_device() is chips[1]
    with pytest.raises(RuntimeError, match="out of range"):
        fluid.TPUPlace(99).jax_device()


def test_data_parallel_batch_must_divide_the_device_count():
    x = fluid.data("x", [None, 4], dtype="float32")
    loss = fluid.layers.reduce_mean(fluid.layers.fc(x, 2))
    fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    cp = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(loss_name=loss.name)
    ok = exe.run(cp, feed={"x": np.ones((16, 4), "float32")},
                 fetch_list=[loss])
    assert np.isfinite(ok[0]).all()
    with pytest.raises(ValueError, match="do not divide"):
        exe.run(cp, feed={"x": np.ones((12, 4), "float32")},
                fetch_list=[loss])


# ---------------------------------------------------------------------------
# native runtime: keyed on its source, loud when the build fails
# ---------------------------------------------------------------------------
def test_native_library_is_keyed_on_a_hash_of_its_source():
    import hashlib

    from paddle_tpu.native import build

    assert build.load_native() is not None
    assert build.status() in ("built", "loaded")
    sha = hashlib.sha256(open(build._SRC, "rb").read()).hexdigest()[:12]
    assert build._lib_path().endswith("libpaddle_tpu_native-%s.so" % sha)
    assert os.path.exists(build._lib_path())


def test_native_build_failure_is_loud(tmp_path, monkeypatch):
    from paddle_tpu.native import build

    bad = tmp_path / "dataloader.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(build, "_SRC", str(bad))
    monkeypatch.setattr(build, "_HERE", str(tmp_path))
    monkeypatch.setattr(build, "_status", None)
    monkeypatch.setattr(build, "_lib", None)
    with pytest.raises(RuntimeError, match="native runtime build failed"):
        build.load_native()
    assert build.status() is None
