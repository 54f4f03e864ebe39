"""Distributed semantics on the 8-virtual-CPU-device mesh (SURVEY §4):
collective ops, dp grad-allreduce equivalence, tp matmul sharding, ring
attention vs full attention, pipeline parallel."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

import paddle_tpu.fluid as fluid
from paddle_tpu.parallel.mesh import build_mesh
from paddle_tpu.parallel.sharding import DistributedProgram, ShardingRule

pytestmark = pytest.mark.skipif(
    len(jax.devices()) < 8, reason="needs 8 virtual devices")


def _train_once(dist=None, batch=8, seed=3):
    """Tiny MLP classifier one SGD step; returns (loss0, w_after)."""
    fluid.default_main_program().random_seed = 11
    fluid.default_startup_program().random_seed = 11
    x = fluid.data("x", [None, 16], dtype="float32")
    y = fluid.data("y", [None, 1], dtype="int64")
    h = fluid.layers.fc(
        x, size=32, act="relu",
        param_attr=fluid.ParamAttr(
            name="w1", initializer=fluid.initializer.Constant(0.05)))
    logits = fluid.layers.fc(
        h, size=4,
        param_attr=fluid.ParamAttr(
            name="w2", initializer=fluid.initializer.Constant(0.02)))
    loss = fluid.layers.reduce_mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    fluid.optimizer.SGD(learning_rate=0.5).minimize(loss)

    rng = np.random.default_rng(seed)
    x_np = rng.standard_normal((batch, 16)).astype("float32")
    y_np = rng.integers(0, 4, (batch, 1)).astype("int64")

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    target = dist if dist is not None else fluid.default_main_program()
    if dist is not None:
        out = exe.run(dist, feed={"x": x_np, "y": y_np}, fetch_list=[loss])
    else:
        out = exe.run(feed={"x": x_np, "y": y_np}, fetch_list=[loss])
    from paddle_tpu.fluid.executor import global_scope
    return float(np.asarray(out[0])), np.asarray(global_scope()["w1"]).copy()


def test_dp_matches_single_device():
    """Same global batch, dp=8 vs single device: identical loss + params."""
    loss_1, w_1 = _train_once(dist=None)

    # fresh programs/scope via conftest fixture requires a second test body,
    # so re-create manually here
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as executor_mod
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]

    mesh = build_mesh({"dp": 8})
    # build the program, then wrap
    fluid.default_main_program().random_seed = 11
    dist_holder = {}

    def make_dist():
        dist_holder["d"] = DistributedProgram(
            fluid.default_main_program(), mesh, feed_axis="dp")
        return dist_holder["d"]

    # _train_once builds program first, then uses dist; emulate by building
    # inside and wrapping the default program lazily:
    loss_8, w_8 = _train_once(
        dist=_LazyDist(mesh), batch=8)
    assert abs(loss_1 - loss_8) < 1e-5
    np.testing.assert_allclose(w_1, w_8, rtol=1e-5, atol=1e-6)


class _LazyDist:
    """Defers wrapping default_main_program until the executor call."""

    def __init__(self, mesh):
        self.mesh = mesh

    def _executor_run(self, executor, feed, fetch_list, scope, return_numpy):
        d = DistributedProgram(
            fluid.default_main_program(), self.mesh, feed_axis="dp")
        return d._executor_run(executor, feed, fetch_list, scope,
                               return_numpy)


def test_tp_sharded_matmul_matches_replicated():
    """Column-parallel fc over tp axis == unsharded fc."""
    mesh = build_mesh({"tp": 8})
    rng = np.random.default_rng(0)
    x_np = rng.standard_normal((4, 16)).astype("float32")

    x = fluid.data("x", [None, 16], dtype="float32")
    y = fluid.layers.fc(
        x, size=32,
        param_attr=fluid.ParamAttr(
            name="wt", initializer=fluid.initializer.Constant(0.03)),
        bias_attr=False)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (ref,) = exe.run(feed={"x": x_np}, fetch_list=[y])
    ref = np.asarray(ref)

    dist = DistributedProgram(
        fluid.default_main_program(), mesh,
        param_rules=[ShardingRule("wt", P(None, "tp"))],
        feed_axis=None)
    (out,) = exe.run(dist, feed={"x": x_np}, fetch_list=[y])
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5, atol=1e-6)


def test_collective_allreduce_psum_semantics():
    """lax.psum over shard_map mesh axis sums shard contributions."""
    mesh = build_mesh({"dp": 8})
    x = np.arange(8, dtype=np.float32)
    f = jax.shard_map(lambda v: jax.lax.psum(v, "dp"), mesh=mesh,
                  in_specs=P("dp"), out_specs=P("dp"))
    out = np.asarray(f(x))
    np.testing.assert_allclose(out, np.full(8, x.sum()))


def test_collective_layer_ops_single_rank_identity():
    """World-size-1 execution: collective layers behave as identity."""
    from paddle_tpu.fluid.layers import collective as coll

    x = fluid.data("x", [4], dtype="float32")
    y = coll._c_allreduce(x, reduce_type="sum")
    z = coll._c_broadcast(x, root=0)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    x_np = np.array([1.0, 2.0, 3.0, 4.0], "float32")
    y_v, z_v = exe.run(feed={"x": x_np}, fetch_list=[y, z])
    np.testing.assert_allclose(np.asarray(y_v), x_np)
    np.testing.assert_allclose(np.asarray(z_v), x_np)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(causal):
    from paddle_tpu.parallel.ring_attention import (
        full_attention, ring_attention_sharded)

    mesh = build_mesh({"sp": 8})
    rng = np.random.default_rng(1)
    B, T, H, D = 2, 64, 2, 8
    q = rng.standard_normal((B, T, H, D)).astype("float32")
    k = rng.standard_normal((B, T, H, D)).astype("float32")
    v = rng.standard_normal((B, T, H, D)).astype("float32")

    ref = np.asarray(full_attention(jnp.array(q), jnp.array(k),
                                    jnp.array(v), causal=causal))
    out = np.asarray(ring_attention_sharded(q, k, v, mesh, axis="sp",
                                            causal=causal))
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)


def test_compiled_program_with_data_parallel():
    x = fluid.data("x", [None, 16], dtype="float32")
    y = fluid.layers.fc(
        x, size=2,
        param_attr=fluid.ParamAttr(
            name="wdp", initializer=fluid.initializer.Constant(0.1)))
    loss = fluid.layers.reduce_mean(y)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    compiled = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
        loss_name=loss.name)
    x_np = np.ones((8, 16), "float32")
    (out,) = exe.run(compiled, feed={"x": x_np}, fetch_list=[loss])
    assert np.isfinite(float(np.asarray(out)))


def test_fleet_distributed_optimizer_runs():
    from paddle_tpu.parallel import fleet

    fleet.init(is_collective=True)
    x = fluid.data("x", [None, 8], dtype="float32")
    y = fluid.layers.fc(x, size=2)
    loss = fluid.layers.reduce_mean(y)
    opt = fleet.distributed_optimizer(fluid.optimizer.SGD(0.1))
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    out = exe.run(feed={"x": np.ones((8, 8), "float32")},
                  fetch_list=[loss])
    assert np.isfinite(float(np.asarray(out[0])))


def test_fleet_zero_shards_optimizer_state():
    """sharding_degree=2 (ZeRO-1): optimizer moments shard over dp while
    the parameters stay replicated (VERDICT #8 'done' bar)."""
    from paddle_tpu.fluid.executor import global_scope
    from paddle_tpu.parallel import fleet

    fleet.init(is_collective=True)
    x = fluid.data("zx", [None, 16], dtype="float32")
    y = fluid.layers.fc(x, size=8)
    loss = fluid.layers.reduce_mean(y)
    strategy = fleet.DistributedStrategy()
    strategy.sharding_degree = 2
    opt = fleet.distributed_optimizer(
        fluid.optimizer.Adam(learning_rate=0.01), strategy,
    )
    opt.minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    out = exe.run(fleet.fleet.main_program,
                  feed={"zx": np.ones((8, 16), "float32")},
                  fetch_list=[loss])
    assert np.isfinite(float(np.asarray(out[0])))
    scope = global_scope()
    moment_specs = []
    ndev = len(jax.devices())
    prog = fleet.fleet.main_program._program
    for name, var in prog.global_block().vars.items():
        arr = scope.find_value(name)
        shape = np.shape(arr)
        if (
            getattr(var, "belong_to_optimizer", False)
            and "moment" in name
            and shape
            and shape[0] % ndev == 0
        ):
            moment_specs.append((name, getattr(arr, "sharding", None)))
    assert moment_specs, "no shardable optimizer moments found in scope"
    # every dp-divisible moment lives sharded over dp in HBM — the ZeRO
    # memory win (XLA propagation may additionally shard params, which is
    # FSDP-like and also fine)
    for name, sh in moment_specs:
        assert sh is not None and "dp" in str(sh.spec), (name, sh)


def test_ring_attention_long_context_exact():
    """Long-context scale: T=1024 ring-sharded over sp=8 (128 tokens per
    device) stays exact vs full attention, causal included."""
    from paddle_tpu.parallel.ring_attention import (
        full_attention, ring_attention_sharded,
    )

    b, t, h, d = 1, 1024, 2, 16
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.rand(b, t, h, d).astype("float32"))
    k = jnp.asarray(rng.rand(b, t, h, d).astype("float32"))
    v = jnp.asarray(rng.rand(b, t, h, d).astype("float32"))
    mesh = build_mesh({"sp": 8})
    for causal in (False, True):
        ref = full_attention(q, k, v, causal=causal)
        out = ring_attention_sharded(q, k, v, mesh, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-5,
        )


def test_fused_attention_rides_ring_under_sp_mesh():
    """fused_multihead_attention through a dp x sp DistributedProgram
    must route to ring attention (exact) — output matches the
    single-device run bit-for-tolerance."""
    import paddle_tpu.fluid.framework as fw
    from paddle_tpu.fluid import unique_name

    b, hds, t, d = 2, 2, 16, 8
    rng = np.random.RandomState(0)
    qv = rng.rand(b, hds, t, d).astype("float32")
    kv = rng.rand(b, hds, t, d).astype("float32")
    vv = rng.rand(b, hds, t, d).astype("float32")

    def build():
        fw.switch_main_program(fw.Program())
        fw.switch_startup_program(fw.Program())
        unique_name.switch()
        q = fluid.data("aq", [b, hds, t, d], dtype="float32")
        k = fluid.data("ak", [b, hds, t, d], dtype="float32")
        v = fluid.data("av", [b, hds, t, d], dtype="float32")
        out = fluid.layers.fused_multihead_attention(q, k, v, causal=True)
        return out

    out = build()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"aq": qv, "ak": kv, "av": vv}
    single = exe.run(feed=feed, fetch_list=[out])[0]

    out2 = build()
    mesh = build_mesh({"dp": 2, "sp": 4})
    dist = DistributedProgram(
        fluid.default_main_program(), mesh,
        feed_specs={"aq": P("dp", None, "sp", None),
                    "ak": P("dp", None, "sp", None),
                    "av": P("dp", None, "sp", None)},
    )
    # prove the RING path engaged (the test would pass via plain GSPMD
    # einsum too): count ring_attention trace-time invocations
    from paddle_tpu.parallel import ring_attention as ra_mod

    calls = []
    orig = ra_mod.ring_attention

    def spy(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    ra_mod.ring_attention = spy
    try:
        ringed = exe.run(dist, feed=feed, fetch_list=[out2])[0]
    finally:
        ra_mod.ring_attention = orig
    assert calls, "sp-sharded fused attention did not route to ring"
    np.testing.assert_allclose(ringed, single, rtol=2e-4, atol=2e-5)


def test_zero_merges_with_tp_layout():
    """Moments of tp-sharded params keep tp AND gain the dp axis."""
    from jax.sharding import PartitionSpec as P2

    mesh = build_mesh({"dp": 2, "tp": 4})
    import paddle_tpu.fluid.framework as fw

    prog = fw.Program()
    blk = prog.global_block()
    blk.create_var(name="w", shape=(16, 8), dtype="float32")
    mvar = blk.create_var(name="w_moment1_0", shape=(16, 8),
                          dtype="float32")
    mvar.belong_to_optimizer = True
    dist = DistributedProgram(
        prog, mesh,
        param_rules=[ShardingRule(r"^w", P2(None, "tp"))],
        opt_state_rules=[ShardingRule(r".*", P2("dp"))],
    )
    msh = dist.param_sharding("w_moment1_0", (16, 8))
    assert str(msh.spec) in (
        "PartitionSpec('dp', 'tp')", "PartitionSpec('dp', 'tp',)",
    ), msh
    # the param itself keeps its plain tp layout
    wsh = dist.param_sharding("w", (16, 8))
    assert "dp" not in str(wsh.spec) and "tp" in str(wsh.spec)


def test_pipeline_parallel_forward_matches_sequential():
    from paddle_tpu.parallel.pipeline import gpipe_sharded

    mesh = build_mesh({"pp": 4}, devices=jax.devices()[:4])
    rng = np.random.default_rng(5)
    ws = np.stack([rng.standard_normal((8, 8)).astype("float32") * 0.3
                   for _ in range(4)])
    x = rng.standard_normal((16, 8)).astype("float32")

    def stage(w, h):
        return jnp.tanh(h @ w)

    ref = jnp.array(x)
    for w in ws:
        ref = stage(jnp.array(w), ref)

    out = gpipe_sharded(stage, jnp.array(ws), jnp.array(x), mesh,
                        axis="pp", n_microbatches=4)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
