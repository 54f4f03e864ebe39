"""Regression tests for review findings: prune w/ control-flow sub-blocks,
sharding-rule anchoring, density priors, nms_top_k, box_clip rank, stable
endpoint hashing, NMT pad/eos separation."""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers


def test_prune_keeps_params_used_inside_while_body():
    i = layers.fill_constant([1], "float32", 0.0)
    n = layers.fill_constant([1], "float32", 3.0)
    x = fluid.data("x", [None, 4], dtype="float32")
    acc = layers.fill_constant_batch_size_like(x, [-1, 4], "float32", 0.0)

    def body(it, a):
        h = layers.fc(a, size=4,
                      param_attr=fluid.ParamAttr(name="loop_w"),
                      bias_attr=False)
        return layers.increment(it, in_place=False), h

    _, out = layers.while_loop(
        lambda it, a: layers.less_than(it, n), body, [i, acc])
    pruned = fluid.default_main_program()._prune([out])
    kept = {v.name for v in pruned.list_vars()}
    assert "loop_w" in kept, "param used only in while body must survive prune"
    # and the pruned program still runs
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (o,) = exe.run(pruned, feed={"x": np.ones((2, 4), "float32")},
                   fetch_list=[out])
    assert np.asarray(o).shape == (2, 4)


def test_prune_keeps_producer_of_var_read_only_in_sub_block():
    """A var produced OUTSIDE the loop but read only INSIDE the body must
    keep its producing op through _prune."""
    x = fluid.data("x", [None, 4], dtype="float32")
    bias = layers.scale(x, scale=3.0)  # producer outside the loop
    i = layers.fill_constant([1], "float32", 0.0)
    n = layers.fill_constant([1], "float32", 2.0)
    acc = layers.fill_constant_batch_size_like(x, [-1, 4], "float32", 0.0)

    def body(it, a):
        return (layers.increment(it, in_place=False),
                layers.elementwise_add(a, bias))

    _, out = layers.while_loop(
        lambda it, a: layers.less_than(it, n), body, [i, acc])
    pruned = fluid.default_main_program()._prune([out])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (o,) = exe.run(pruned, feed={"x": np.ones((2, 4), "float32")},
                   fetch_list=[out])
    np.testing.assert_allclose(np.asarray(o), np.full((2, 4), 6.0))


def test_sharding_rule_annotation_is_exact_match():
    from paddle_tpu.parallel.sharding import DistributedProgram
    from paddle_tpu.parallel.mesh import build_mesh
    from jax.sharding import PartitionSpec as P
    import jax

    if len(jax.devices()) < 8:
        return
    mesh = build_mesh({"tp": 8})
    prog = fluid.default_main_program()
    prog._sharding_spec = [("emb", P("tp", None))]
    dist = DistributedProgram(prog, mesh, feed_axis=None)
    sharded = dist.param_sharding("emb", (16, 4))
    other = dist.param_sharding("src_emb", (16, 4))
    assert sharded.spec == P("tp", None)
    assert other.spec == P()  # suffix name must NOT inherit the rule


def test_density_prior_box_subgrid_offsets():
    feat = fluid.data("feat", [1, 8, 2, 2])
    img = fluid.data("img", [1, 3, 64, 64])
    box, var = layers.density_prior_box(
        feat, img, densities=[2], fixed_sizes=[16.0], fixed_ratios=[1.0])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (b,) = exe.run(
        feed={"feat": np.zeros((1, 8, 2, 2), "float32"),
              "img": np.zeros((1, 3, 64, 64), "float32")},
        fetch_list=[box])
    b = np.asarray(b)  # (H, W, 4 priors, 4)
    assert b.shape == (2, 2, 4, 4)
    cell = b[0, 0]  # 4 priors of one cell
    # density 2 => the 4 priors sit on a 2x2 sub-grid, NOT stacked identical
    assert len({tuple(np.round(p, 5)) for p in cell}) == 4
    # sub-grid shift = step/d = 32/2 = 16px => 0.25 normalized
    centers_x = (cell[:, 0] + cell[:, 2]) / 2
    assert np.isclose(sorted(set(np.round(centers_x, 4)))[1]
                      - sorted(set(np.round(centers_x, 4)))[0], 0.25)


def test_multiclass_nms_respects_nms_top_k():
    # two far-apart boxes, same class, both above threshold
    boxes = np.array([[[0, 0, 10, 10], [50, 50, 60, 60]]], "float32")
    scores = np.array([[[0.0, 0.0], [0.9, 0.8]]], "float32")  # class1 scores
    b = fluid.data("b", [1, 2, 4])
    s = fluid.data("s", [1, 2, 2])
    out = layers.multiclass_nms(b, s, score_threshold=0.1, nms_top_k=1,
                                keep_top_k=5, background_label=0)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (o,) = exe.run(feed={"b": boxes, "s": scores}, fetch_list=[out])
    o = np.asarray(o)[0]
    n_detected = int((o[:, 0] >= 0).sum())
    assert n_detected == 1, "nms_top_k=1 must keep only the best candidate"


def test_box_clip_preserves_2d_rank():
    b = fluid.data("b", [5, 4])
    info = fluid.data("im", [1, 3])
    out = layers.box_clip(b, info)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    (o,) = exe.run(
        feed={"b": np.array([[-5, -5, 200, 200]] * 5, "float32"),
              "im": np.array([[100, 100, 1.0]], "float32")},
        fetch_list=[out])
    assert np.asarray(o).shape == (5, 4)
    assert np.asarray(o).max() <= 99.0


def test_hashname_dispatch_is_stable_digest():
    import zlib
    from paddle_tpu.fluid.transpiler import HashName

    eps = ["ep0", "ep1", "ep2"]

    class V:
        def __init__(self, name):
            self.name = name

    vs = [V("fc_0.w_0"), V("emb"), V("fc_1.b_0")]
    got = HashName(eps).dispatch(vs)
    expect = [eps[zlib.crc32(v.name.encode()) % 3] for v in vs]
    assert got == expect


def test_nmt_trains_eos_but_masks_pad():
    from paddle_tpu.models.transformer_nmt import (
        NMTConfig, synthetic_pair_batch)

    cfg = NMTConfig(src_vocab=50, tgt_vocab=50, hidden=16, heads=2, ffn=32,
                    enc_layers=1, dec_layers=1)
    src, tgt, labels = synthetic_pair_batch(cfg, 4, 8, 8)
    assert (labels == cfg.eos_id).any(), "labels must contain real EOS"
    assert not (labels == cfg.pad_id).any()
    assert src.min() > cfg.pad_id


def test_prune_keeps_cond_branch_params():
    """_prune must follow true_block/false_block attrs: params used only
    inside a cond branch survive pruning (save_inference_model path)."""
    import numpy as np
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, layers, unique_name
    from paddle_tpu.fluid.param_attr import ParamAttr

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 2

    x = fluid.data(name="x", shape=[None, 4], dtype="float32")
    pred = layers.greater_than(
        layers.reduce_sum(x), layers.fill_constant([1], "float32", 0.0)
    )
    out = layers.cond(
        pred,
        lambda: layers.fc(x, 4, param_attr=ParamAttr(name="w_cond")),
        lambda: layers.scale(x, 2.0),
    )
    prog = fluid.default_main_program()
    pruned = prog._prune([out])
    assert "w_cond" in pruned.global_block().vars

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    res = exe.run(
        pruned,
        feed={"x": np.ones((2, 4), np.float32)},
        fetch_list=[out.name],
    )[0]
    assert res.shape == (2, 4)


def test_dropout_rbg_mask_consistent_between_fwd_and_grad():
    """The rbg dropout path (ops/nn_ops.py _dropout_keep_mask) must
    reproduce the SAME mask in the vjp replay as in the forward pass:
    grad(mean(dropout(x)*w)) w.r.t. x is nonzero exactly where the
    forward output kept elements."""
    import numpy as np
    import paddle_tpu.fluid as fluid

    prog = fluid.Program()
    startup = fluid.Program()
    prog.random_seed = 5
    with fluid.program_guard(prog, startup):
        x = fluid.data("drx", (None, 64,), "float32")
        y = fluid.layers.dropout(
            x, dropout_prob=0.5, dropout_implementation="upscale_in_train")
        loss = fluid.layers.reduce_mean(y)
        grads = fluid.backward.gradients([loss], [x])
    exe = fluid.Executor()
    exe.run(startup)
    xv = np.random.default_rng(3).standard_normal((8, 64)).astype("float32")
    xv[xv == 0] = 1.0
    y_v, g_v = exe.run(prog, feed={"drx": xv}, fetch_list=[y, grads[0]])
    y_v, g_v = np.asarray(y_v), np.asarray(g_v)
    kept_fwd = y_v != 0
    kept_bwd = g_v != 0
    np.testing.assert_array_equal(kept_fwd, kept_bwd)
    # masks advance with the step counter (fresh randomness each run)
    y2 = np.asarray(exe.run(prog, feed={"drx": xv}, fetch_list=[y])[0])
    assert (y_v != y2).any()
    # keep rate plausible for p=0.5
    assert 0.3 < kept_fwd.mean() < 0.7


def test_dropout_masks_unbiased():
    """The keep rate is 1-p and upscale_in_train divides by exactly
    that, so E[dropout(x)] == x."""
    import numpy as np
    import paddle_tpu.fluid as fluid

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 11
    with fluid.program_guard(prog, startup):
        x = fluid.data("d8x", (None, 1024), "float32")
        y = fluid.layers.dropout(
            x, dropout_prob=0.1,
            dropout_implementation="upscale_in_train")
    exe = fluid.Executor()
    exe.run(startup)
    xv = np.ones((64, 1024), np.float32)
    yv = np.asarray(exe.run(prog, feed={"d8x": xv}, fetch_list=[y])[0])
    kept = yv != 0
    assert abs(kept.mean() - 0.9) < 0.01
    np.testing.assert_allclose(yv[kept], 1.0 / 0.9, rtol=1e-6)
    assert abs(yv.mean() - 1.0) < 0.02


def test_dropout_tiny_rate_is_honoured_exactly():
    """A drop rate of 0.002 is drawn at 0.002 (not rounded to a coarser
    threshold such as 1/256) and kept values are scaled by exactly
    1/(1-0.002)."""
    import numpy as np
    import paddle_tpu.fluid as fluid

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = 13
    with fluid.program_guard(prog, startup):
        x = fluid.data("dqx", (None, 4096), "float32")
        y = fluid.layers.dropout(
            x, dropout_prob=0.002,
            dropout_implementation="upscale_in_train")
    exe = fluid.Executor()
    exe.run(startup)
    xv = np.ones((64, 4096), np.float32)
    yv = np.asarray(exe.run(prog, feed={"dqx": xv}, fetch_list=[y])[0])
    drop_rate = (yv == 0).mean()
    assert abs(drop_rate - 0.002) < 0.0008, drop_rate
    np.testing.assert_allclose(yv[yv != 0], 1.0 / 0.998, rtol=1e-6)


def _dropout_program(shape, p, seed, name):
    """A program of one `upscale_in_train` dropout over a fed tensor of
    `shape`, with the gradient of its sum; returns (prog, startup, y, grad)."""
    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = seed
    with fluid.program_guard(prog, startup):
        x = fluid.data(name, shape, "float32")
        x.stop_gradient = False
        y = layers.dropout(x, dropout_prob=p,
                           dropout_implementation="upscale_in_train")
        grad = fluid.backward.gradients([layers.reduce_sum(y)], [x])[0]
    return prog, startup, y, grad


def _word_mates(keep):
    """The two halves of a mask whose elements share a generator word, by
    `_dropout_keep_mask`'s rule: the halves of the first axis of even size,
    else of the flat mask (whose last word serves one element alone)."""
    axis = next((i for i, s in enumerate(keep.shape) if s % 2 == 0), None)
    if axis is None:
        flat = keep.reshape(-1)
        half = (flat.size + 1) // 2
        return flat[:flat.size - half], flat[half:]
    lo, hi = np.split(keep, 2, axis=axis)
    return lo.reshape(-1), hi.reshape(-1)


@pytest.mark.parametrize("p", [0.0, 0.1, 0.5, 0.9, 1.0])
@pytest.mark.parametrize("shape,steps", [
    ((1024, 1024), 2),       # even: 2**20 elements a step
    ((1023, 1025), 2),       # no even axis: the flat draw, cut by one
    ((1, 2048), 2),          # the leading axis is odd, the next one is split
    ((1,), 512),             # one element a step: the word's other field is cut
])
def test_dropout_sixteen_bit_fields(p, shape, steps):
    """`_dropout_keep_mask` gives each element a 16-bit field of a word that
    it shares with one other element: (a) the keep rate is 1-p within four
    standard errors, exact at 0 and 1; (b) two elements of one word are
    kept together at the product of their rates; (c) the gradient's mask is
    the forward pass's; (d) a step's mask is not the last step's."""
    prog, startup, y, grad = _dropout_program(shape, p, seed=41, name="d16x")
    exe = fluid.Executor()
    exe.run(startup)
    xv = np.ones(shape, np.float32)
    keeps = []
    for _ in range(steps):
        yv, gv = (np.asarray(v) for v in exe.run(
            prog, feed={"d16x": xv}, fetch_list=[y, grad]))
        assert yv.shape == shape
        keep = yv != 0
        np.testing.assert_array_equal(keep, gv != 0)                  # (c)
        if keep.any():
            np.testing.assert_allclose(yv[keep], 1.0 / (1.0 - p), rtol=1e-6)
        keeps.append(keep)
    keeps = np.stack(keeps)
    q = 1.0 - p
    if p in (0.0, 1.0):
        assert keeps.all() if p == 0.0 else not keeps.any()           # (a)
        return
    n = keeps.size
    assert abs(keeps.mean() - q) <= 4 * np.sqrt(q * (1 - q) / n)      # (a)
    if keeps[0].size > 1:
        assert (keeps[0] != keeps[1]).any()                           # (d)
    else:
        assert keeps.any() and not keeps.all()                        # (d)
    lo, hi = _word_mates(keeps[0])
    if lo.size:
        both = q * q
        assert abs((lo & hi).mean() - both) <= 4 * np.sqrt(
            both * (1 - both) / lo.size)                              # (b)


def test_dropout_draws_half_a_word_an_element():
    """The jitted step of a [64, 768] dropout holds one generator
    operation, of at most ceil(n / 2) uint32 words."""
    import re

    import jax
    import jax.numpy as jnp
    from paddle_tpu.fluid.lowering import build_step_fn

    prog, _, y, _ = _dropout_program((64, 768), 0.1, seed=7, name="d16_hlo")
    step = build_step_fn(prog, ["d16_hlo"], [y.name])
    hlo = jax.jit(step).lower(
        {}, {"d16_hlo": jax.ShapeDtypeStruct((64, 768), jnp.float32)},
        jax.ShapeDtypeStruct((2,), jnp.uint32),
    ).compiler_ir(dialect="hlo").as_hlo_text()
    draws = re.findall(r"u32\[([\d,]*)\][^=]*\) rng-bit-generator\(", hlo)
    assert len(draws) == 1, hlo
    words = int(np.prod([int(d) for d in draws[0].split(",") if d]))
    assert words <= (64 * 768 + 1) // 2, draws
