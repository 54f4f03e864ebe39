"""int8-quantized parameter-averaging collective (EQuARX-inspired;
parallel/quantized_collectives.py) and its LocalSGD opt-in.

Bars: the quantized pmean's element error stays within the analytic
bound (pmax|x|/254 plus float slack); LocalSGD with quantized_sync
still converges; the flag defaults OFF so the k=1 ≡ plain-dp exactness
guarantee elsewhere in the suite is untouched.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.parallel.quantized_collectives import pmean_int8
# same new/old-jax fallback the library uses (local_sgd.py)
from paddle_tpu.parallel.local_sgd import shard_map


def _mesh_dp():
    return Mesh(np.array(jax.devices()), ("dp",))


def _smap(fn, mesh, in_specs, out_specs):
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


def test_pmean_int8_error_within_bound():
    mesh = _mesh_dp()
    n = mesh.shape["dp"]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((n, 64, 33)).astype("float32") * 3.0

    def local(xs):
        return pmean_int8(xs[0], "dp")[None]

    out = jax.jit(_smap(local, mesh, (P("dp"),), P("dp")))(x)
    got = np.asarray(out)[0]
    want = x.mean(axis=0)
    bound = np.abs(x).max() / 254.0 + 1e-5
    assert np.abs(got - want).max() <= bound, (
        np.abs(got - want).max(), bound)
    # every shard got the SAME averaged value
    for i in range(1, n):
        np.testing.assert_array_equal(np.asarray(out)[i], got)


def test_pmean_int8_zero_and_int_passthrough():
    mesh = _mesh_dp()
    n = mesh.shape["dp"]

    def local(z, i):
        return pmean_int8(z[0], "dp")[None], pmean_int8(i[0], "dp")[None]

    z = np.zeros((n, 8), "float32")
    iv = np.arange(n * 4, dtype="int32").reshape(n, 4)
    zo, io = jax.jit(_smap(local, mesh, (P("dp"), P("dp")),
                           (P("dp"), P("dp"))))(z, iv)
    np.testing.assert_array_equal(np.asarray(zo)[0], np.zeros(8))
    np.testing.assert_allclose(np.asarray(io)[0],
                               iv.astype("float64").mean(0))


def test_local_sgd_quantized_sync_converges():
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as exmod
    import paddle_tpu.parallel.fleet as fleet_mod

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    exmod._scope_stack[:] = [exmod.Scope()]
    fl = fleet_mod.Fleet().init()
    x = fluid.data("qsx", shape=[None, 6], dtype="float32")
    y = fluid.data("qsy", shape=[None, 1], dtype="float32")
    pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="tanh"), 1)
    loss = fluid.layers.reduce_mean(
        fluid.layers.square_error_cost(pred, y))
    s = fleet_mod.DistributedStrategy()
    s.use_local_sgd = True
    s.local_sgd_k_steps = 2
    s.local_sgd_quantized_sync = True
    fl.distributed_optimizer(
        fluid.optimizer.SGD(0.1), strategy=s).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(0)
    xv = rng.standard_normal((32, 6)).astype("float32")
    yv = (xv @ rng.standard_normal((6, 1))).astype("float32")
    losses = [float(np.asarray(exe.run(
        fl.main_program, feed={"qsx": xv, "qsy": yv},
        fetch_list=[loss])[0])) for _ in range(8)]
    assert all(np.isfinite(v) for v in losses), losses
    assert losses[-1] < losses[0] * 0.5, losses


def test_quantized_sync_defaults_off():
    import paddle_tpu.parallel.fleet as fleet_mod
    from paddle_tpu.parallel.local_sgd import LocalSGDProgram

    assert fleet_mod.DistributedStrategy() \
        .local_sgd_quantized_sync is False
    import inspect

    sig = inspect.signature(LocalSGDProgram.__init__)
    assert sig.parameters["quantized_sync"].default is False


def test_quantized_sync_small_lr_tracks_exact():
    """The delta-payload design's whole point: at SMALL learning rates
    the int8 noise is bounded by pmax|delta|/254 (shrinks with the
    updates), so quantized training must track the exact run closely —
    absolute-value quantization would drown a 1e-3-lr update in
    weight-magnitude noise and stall."""
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as exmod
    import paddle_tpu.parallel.fleet as fleet_mod

    def run(quantized, steps=24):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        exmod._scope_stack[:] = [exmod.Scope()]
        fluid.default_startup_program().random_seed = 6
        fl = fleet_mod.Fleet().init()
        x = fluid.data("slx", shape=[None, 6], dtype="float32")
        y = fluid.data("sly", shape=[None, 1], dtype="float32")
        pred = fluid.layers.fc(fluid.layers.fc(x, 16, act="tanh"), 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, y))
        s = fleet_mod.DistributedStrategy()
        s.use_local_sgd = True
        s.local_sgd_k_steps = 2
        s.local_sgd_quantized_sync = quantized
        fl.distributed_optimizer(
            fluid.optimizer.SGD(1e-3), strategy=s).minimize(loss)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        rng = np.random.default_rng(0)
        xv = rng.standard_normal((32, 6)).astype("float32")
        yv = (xv @ rng.standard_normal((6, 1))).astype("float32")
        return [float(np.asarray(exe.run(
            fl.main_program, feed={"slx": xv, "sly": yv},
            fetch_list=[loss])[0])) for _ in range(steps)]

    exact = run(False)
    quant = run(True)
    # monotone-ish progress AND tight tracking of the exact losses
    assert quant[-1] < quant[0], quant
    np.testing.assert_allclose(quant, exact, rtol=0.02)
