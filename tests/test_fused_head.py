"""The fused vocabulary head (`linear_softmax_with_cross_entropy`): the
head's product and its loss over the labelled rows only, a chunk of rows
at a time. Every test compares with the pair it replaces in BERT's
training graph, `softmax_with_cross_entropy(matmul(x, w^T))`.

The chunk size is a function of shapes (2,048 rows at BERT's vocabulary);
where a test needs several chunks at a tiny size it replaces that one
function, nothing else."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import executor as executor_mod
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import bert
from paddle_tpu.ops import loss_ops
from paddle_tpu.ops.registry import LOWERINGS, LowerContext

R = 8            # rows of a chunk where a test sets it
N, H, V = 40, 16, 50


@pytest.fixture
def small_chunks(monkeypatch):
    monkeypatch.setattr(loss_ops, "head_chunk_rows",
                        lambda n_rows, vocab: min(R, -(-n_rows // 8) * 8))


@pytest.fixture
def fresh_programs():
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    fluid.default_startup_program().random_seed = 7
    yield


def _fused(x, w, label, ignore=-1):
    return LOWERINGS["linear_softmax_with_cross_entropy"](
        LowerContext(), {"X": [x], "W": [w], "Label": [label]},
        {"ignore_index": ignore})


def _unfused(x, w, label, ignore=-1):
    ctx = LowerContext()
    logits = LOWERINGS["matmul"](
        ctx, {"X": [x], "Y": [w]}, {"transpose_Y": True})["Out"][0]
    return LOWERINGS["softmax_with_cross_entropy"](
        ctx, {"Logits": [logits], "Label": [label[..., None]]},
        {"ignore_index": ignore})["Loss"][0]


def _case(n_labelled, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(4, N // 4, H)).astype(np.float32)
    w = rng.normal(size=(V, H)).astype(np.float32)
    label = np.full(N, -1, np.int64)
    at = rng.permutation(N)[:n_labelled]
    label[at] = rng.integers(0, V, n_labelled)
    cot = rng.normal(size=(4, N // 4, 1)).astype(np.float32)
    return x, w, label.reshape(4, N // 4), cot


def test_chunk_rows_come_from_shapes():
    assert loss_ops.head_chunk_rows(256 * 128, 30522) == 2048
    assert loss_ops.head_chunk_rows(48 * 128, 30522) == 2048
    assert loss_ops.head_chunk_rows(64 * 128, 30522) == 2048
    # never more than the rows there are, in whole sublanes
    assert loss_ops.head_chunk_rows(256, 1024) == 256
    assert loss_ops.head_chunk_rows(100, 1024) == 104
    # a quarter of a GB of float32 logits at most, 256 rows at least
    for vocab in (1024, 30522, 131072, 1 << 20):
        r = loss_ops.head_chunk_rows(1 << 20, vocab)
        assert r % 256 == 0 and r >= 256
        assert r == 256 or r * vocab * 4 <= 1 << 28


@pytest.mark.parametrize("n_labelled", [0, 1, R, R + 1, N],
                         ids=["none", "one", "R", "R+1", "all"])
def test_op_equals_unfused_pair_float32(small_chunks, n_labelled):
    """Loss, dX and dW under a cotangent that differs row by row."""
    x, w, label, cot = _case(n_labelled)

    def scalar(fn):
        return lambda x_, w_: jnp.sum(fn(x_, w_) * cot)

    fused = jax.jit(lambda x_, w_: _fused(x_, w_, label))(x, w)
    want = _unfused(x, w, label)
    assert fused["Loss"][0].shape == (4, N // 4, 1)
    assert fused["Loss"][0].dtype == jnp.float32
    np.testing.assert_allclose(fused["Loss"][0], want, rtol=2e-6, atol=2e-6)
    assert int(fused["Rows"][0]) == n_labelled
    assert int(fused["Chunks"][0]) == -(-n_labelled // R)
    got = jax.jit(jax.grad(
        scalar(lambda a, b: _fused(a, b, label)["Loss"][0]), (0, 1)))(x, w)
    ref = jax.grad(scalar(lambda a, b: _unfused(a, b, label)), (0, 1))(x, w)
    for g, r_ in zip(got, ref):
        np.testing.assert_allclose(g, r_, rtol=1e-5, atol=1e-5)
    if n_labelled == 0:
        assert not np.asarray(got[0]).any() and not np.asarray(got[1]).any()


def test_op_takes_label_with_trailing_one_and_two_d_rows(small_chunks):
    x, w, label, _ = _case(11, seed=3)
    a = _fused(x, w, label[..., None])["Loss"][0]
    b = _fused(x, w, label)["Loss"][0]
    np.testing.assert_array_equal(a, b)
    flat = _fused(x.reshape(N, H), w, label.reshape(N))["Loss"][0]
    assert flat.shape == (N, 1)
    np.testing.assert_allclose(flat, np.asarray(b).reshape(N, 1),
                               rtol=1e-6, atol=1e-6)


def test_all_rows_labelled_runs_every_chunk_without_the_logits(small_chunks):
    """Every position labelled: N / R trips, the same numbers, and still
    no array of rows x vocabulary in the compiled gradient."""
    x, w, label, cot = _case(N)

    def loss(x_, w_):
        out = _fused(x_, w_, label)
        return jnp.sum(out["Loss"][0] * cot), out["Chunks"][0]

    step = jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))
    (_, chunks), _ = step(x, w)
    assert int(chunks) == N // R
    assert _largest_array(step.lower(x, w).compile().as_text()) < N * V


_SHAPE = re.compile(r"\b(?:pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)"
                    r"\[([0-9,]+)\]")


def _largest_array(hlo):
    sizes = [int(np.prod([int(d) for d in m.group(1).split(",")]))
             for m in _SHAPE.finditer(hlo)]
    return max(sizes)


def _largest_compiled(exe):
    """Over every program the executor compiled (start-up and step)."""
    return max(_largest_array(e.as_text()) for e in exe._cache.values())


def _amp_losses(fused, steps=3):
    from paddle_tpu.fluid.contrib.mixed_precision import decorate

    prog, startup = fluid.Program(), fluid.Program()
    prog.random_seed = startup.random_seed = 3
    with fluid.program_guard(prog, startup), unique_name.guard():
        x = fluid.data("hx", shape=[None, 12, 24], dtype="float32")
        lab = fluid.data("hl", shape=[None, 12], dtype="int64")
        h = fluid.layers.fc(x, 32, num_flatten_dims=2, act="tanh")
        w = fluid.layers.create_parameter([96, 32], "float32", name="hw")
        if fused:
            loss = fluid.layers.linear_softmax_with_cross_entropy(
                h, w, lab, ignore_index=-1)
        else:
            loss = fluid.layers.softmax_with_cross_entropy(
                fluid.layers.matmul(h, w, transpose_y=True),
                fluid.layers.unsqueeze(lab, [2]), ignore_index=-1)
        loss = fluid.layers.mean(loss)
        opt = fluid.optimizer.Adam(1e-2)
        if fused != "float32":
            opt = decorate(opt, use_bf16=True)
        opt.minimize(loss)
    rng = np.random.default_rng(5)
    lab_v = rng.integers(0, 96, (6, 12))
    lab_v[rng.random((6, 12)) < 0.6] = -1
    feed = {"hx": rng.normal(size=(6, 12, 24)).astype(np.float32),
            "hl": lab_v.astype(np.int64)}
    scope = executor_mod.Scope()
    exe = fluid.Executor()
    exe.run(startup, scope=scope)
    losses = [float(exe.run(prog, feed=feed, fetch_list=[loss],
                            scope=scope)[0]) for _ in range(steps)]
    casts = [op for op in prog.global_block().ops if op.type == "cast"]
    return losses, casts, prog


def test_bf16_amp_program_casts_the_heads_operands_and_tracks_float32(
        small_chunks):
    """Under decorate(use_bf16=True) the op's X and W arrive in bfloat16 as
    matmul's do; the losses of three steps stay within the 1% the
    benchmark's rehearsal allows between bf16 AMP and float32."""
    amp, casts, prog = _amp_losses(True)
    head = [op for op in prog.global_block().ops
            if op.type == "linear_softmax_with_cross_entropy"][0]
    cast_outs = {op.output("Out")[0] for op in casts}
    assert head.input("X")[0] in cast_outs
    assert head.input("W")[0] == "hw.cast_bf16"
    pair, _, _ = _amp_losses(False)
    f32, casts32, _ = _amp_losses("float32")
    assert not casts32
    np.testing.assert_allclose(amp, f32, rtol=1e-2)
    np.testing.assert_allclose(amp, pair, rtol=1e-2)
    assert amp[-1] < amp[0]


def _bert_tiny(seq, head="fused", lr=1e-3):
    cfg = bert.bert_tiny(seq=seq)
    vs = bert.build_bert_pretrain(cfg, seq)
    if head == "parent":
        # the head of the parent's graph, from the encoder's output
        word_emb = fluid.default_main_program().global_block().var(
            "word_emb")
        logits = fluid.layers.matmul(vs["encoder_out"], word_emb,
                                     transpose_y=True)
        vs["loss"] = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                logits, fluid.layers.unsqueeze(vs["mlm_labels"], [2]),
                ignore_index=-1))
    fluid.optimizer.Adam(learning_rate=lr).minimize(vs["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    return cfg, vs, exe


def _params(scope):
    return {v.name: np.asarray(scope.find_value(v.name))
            for v in fluid.default_main_program().list_vars()
            if isinstance(v, framework.Parameter)}


def _three_steps(head, batch=8, seq=32):
    cfg, vs, exe = _bert_tiny(seq, head)
    ids, labels = bert.synthetic_batch(cfg, batch, seq)
    feed = {"input_ids": ids, "mlm_labels": labels}
    scope = fluid.global_scope()
    before = _params(scope)
    losses = [float(exe.run(feed=feed, fetch_list=[vs["loss"]])[0])
              for _ in range(3)]
    after = _params(scope)
    return losses, {n: after[n] - before[n] for n in before}, exe, vs, feed


def test_bert_tiny_trains_as_the_parents_graph(fresh_programs, small_chunks):
    """Three Adam steps: the losses and every parameter's change are those
    of matmul + softmax_with_cross_entropy on the same encoder."""
    losses, change, _, _, _ = _three_steps("fused")
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    fluid.default_startup_program().random_seed = 7
    want_losses, want_change, _, _, _ = _three_steps("parent")
    np.testing.assert_allclose(losses, want_losses, rtol=1e-5)
    assert set(change) == set(want_change) and "word_emb" in change
    for name, delta in change.items():
        assert np.abs(want_change[name]).max() > 0, name
        np.testing.assert_allclose(delta, want_change[name], rtol=2e-3,
                                   atol=2e-5, err_msg=name)


def test_bert_training_step_holds_no_rows_by_vocabulary_array(
        fresh_programs, small_chunks):
    """The optimised HLO of the training step has no array of B*T*V
    elements; the parent's head, compiled the same way, has."""
    batch, seq = 8, 32
    _, _, exe, vs, _ = _three_steps("fused", batch, seq)
    full = batch * seq * bert.bert_tiny().vocab_size
    assert _largest_compiled(exe) < full
    assert "logits" not in vs
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    _, _, exe, _, _ = _three_steps("parent", batch, seq)
    assert _largest_compiled(exe) >= full


def test_is_test_graph_still_builds_the_logits(fresh_programs):
    cfg = bert.bert_tiny(seq=16)
    vs = bert.build_bert_pretrain(cfg, 16, is_test=True)
    assert tuple(vs["logits"].shape)[1:] == (16, cfg.vocab_size)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ids, labels = bert.synthetic_batch(cfg, 2, 16)
    logits, loss = exe.run(
        fluid.default_main_program().clone(for_test=True),
        feed={"input_ids": ids, "mlm_labels": labels},
        fetch_list=[vs["logits"], vs["loss"]])
    logp = jax.nn.log_softmax(jnp.asarray(logits), -1)
    picked = jnp.take_along_axis(
        logp, jnp.maximum(labels, 0)[..., None], -1)[..., 0]
    want = -jnp.sum(jnp.where(labels >= 0, picked, 0.0)) / labels.size
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)


@pytest.mark.parametrize("rate", [0.0, 0.15, 1.0])
def test_head_counters_count_labels_and_chunks(fresh_programs, small_chunks,
                                               rate):
    cfg, vs, exe = _bert_tiny(16)
    rng = np.random.default_rng(1)
    ids = rng.integers(0, cfg.vocab_size, (4, 16), dtype=np.int64)
    labels = np.where(rng.random((4, 16)) < rate, ids, -1)
    loss, rows, chunks = exe.run(
        feed={"input_ids": ids, "mlm_labels": labels},
        fetch_list=[vs["loss"], vs["head_rows"], vs["head_chunks"]])
    n = int((labels >= 0).sum())
    assert rows.dtype == np.int32 and chunks.dtype == np.int32
    assert int(rows) == n and int(chunks) == -(-n // R)
    assert np.isfinite(loss) and (float(loss) == 0.0) == (n == 0)


# -- batch-sharded layouts: one list of labelled rows per batch shard ------

BATCH, SEQ = 32, 40     # B*T and its shards collide with no weight's rows


def _sharded_feed(cfg):
    """Labels at a rate that rises from the first row to the last, as the
    benchmark's ring has them: the shards hold different counts."""
    rng = np.random.default_rng(2)
    ids = rng.integers(0, cfg.vocab_size, (BATCH, SEQ), dtype=np.int64)
    rate = np.linspace(0.05, 0.45, BATCH)[:, None]
    labels = np.where(rng.random((BATCH, SEQ)) < rate, ids, -1)
    return {"input_ids": ids, "mlm_labels": labels}


_COLLECTIVE = re.compile(
    r" (all-gather|all-reduce|all-to-all|collective-permute)(-start)?\(")
_GROUPS = re.compile(r"replica_groups=(?:\[\d+,(\d+)\]|\{\{([0-9,]*)\})")


def _hidden_rows_moved(hlo, hidden, n_dp, n_tp=1):
    """Collectives over groups wider than one batch shard whose arrays have
    the hidden size last and as many rows as a chunk, the batch or a
    shard of it: hidden states (or their gradients) crossing between
    batch shards, whatever collective carries them."""
    rows = {R, BATCH * SEQ, BATCH * SEQ // n_dp}
    found = []
    for line in hlo.splitlines():
        if not _COLLECTIVE.search(line):
            continue
        pairs = re.search(r"source_target_pairs=\{([0-9,{}]*)\}", line)
        if pairs:   # devices are numbered batch shard major
            ends = [int(d) // n_tp for d in re.findall(r"\d+", pairs.group(1))]
            if ends[0::2] == ends[1::2]:
                continue
        g = _GROUPS.search(line)
        width = (int(g.group(1)) if g.group(1)
                 else len(g.group(2).split(","))) if g else n_dp * n_tp
        if width <= n_tp:
            continue
        for m in _SHAPE.finditer(line.split(" = ", 1)[1].split("(", 1)[0]
                                 if " = (" not in line
                                 else line.split(" = ", 1)[1].split(") ")[0]):
            dims = [int(d) for d in m.group(1).split(",")]
            if (len(dims) > 1 and dims[-1] == hidden
                    and int(np.prod(dims[:-1])) in rows):
                found.append(line.strip()[:160])
    return found


def _spy_hlo(cache):
    """Replace the one jitted entry of a program's cache by a wrapper that
    keeps the optimised HLO of the call it sees."""
    (sig, entry), = cache.items()
    seen = {}

    def spy(*args):
        seen["hlo"] = entry.lower(*args).compile().as_text()
        return entry(*args)

    cache[sig] = spy
    return seen


def _one_device_losses(feed, small):
    cfg, vs, exe = _bert_tiny(SEQ)
    return [exe.run(feed=feed, fetch_list=[vs["loss"], vs["head_rows"]])
            for _ in range(2)]


@pytest.mark.parametrize("layout", ["dp4", "dp8", "dp4_tp2"])
def test_batch_sharded_step_keeps_hidden_states_on_their_device(
        fresh_programs, small_chunks, layout):
    feed = _sharded_feed(bert.bert_tiny(seq=SEQ))
    want = _one_device_losses(feed, small_chunks)
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor_mod._scope_stack[:] = [executor_mod.Scope()]
    fluid.default_startup_program().random_seed = 7
    cfg, vs, exe = _bert_tiny(SEQ)
    fetch = [vs["loss"], vs["head_rows"], vs["head_chunks"]]
    if layout == "dp4_tp2":
        from paddle_tpu.parallel.mesh import build_mesh
        from paddle_tpu.parallel.sharding import (DistributedProgram,
                                                  ShardingRule)

        mesh = build_mesh({"dp": 4, "tp": 2}, devices=jax.devices()[:8])
        prog = DistributedProgram(
            fluid.default_main_program(), mesh,
            param_rules=[ShardingRule(p, s) for p, s in bert.tp_rules()],
            feed_axis="dp")
        n_dp, n_tp = 4, 2
    else:
        n_dp, n_tp = int(layout[2:]), 1
        prog = fluid.CompiledProgram(
            fluid.default_main_program()).with_data_parallel(
                loss_name=vs["loss"].name, places=jax.devices()[:n_dp])
    first = exe.run(prog, feed=feed, fetch_list=fetch)
    seen = _spy_hlo(prog._cache)
    second = exe.run(prog, feed=feed, fetch_list=fetch)
    for got, ref in zip((first, second), want):
        np.testing.assert_allclose(float(got[0]), float(ref[0]), rtol=2e-6)
        assert int(got[1]) == int(ref[1]) == int(
            (feed["mlm_labels"] >= 0).sum())
    # the trips are those of the fullest shard
    per_shard = (feed["mlm_labels"] >= 0).reshape(n_dp, -1).sum(1)
    assert int(first[2]) == -(-int(per_shard.max()) // R)
    assert not _hidden_rows_moved(seen["hlo"], cfg.hidden, n_dp, n_tp)
    assert _largest_array(seen["hlo"]) < BATCH * SEQ * cfg.vocab_size // n_dp


def test_the_search_sees_rows_moved_when_the_rows_are_one_list(
        fresh_programs, small_chunks, monkeypatch):
    """The control of the test above: with the per-shard lists turned off
    the partitioner sums every chunk's rows over all devices, and the
    search finds it."""
    monkeypatch.setattr(loss_ops, "_head_spmd", lambda ctx, x, w: None)
    feed = _sharded_feed(bert.bert_tiny(seq=SEQ))
    cfg, vs, exe = _bert_tiny(SEQ)
    prog = fluid.CompiledProgram(
        fluid.default_main_program()).with_data_parallel(
            loss_name=vs["loss"].name, places=jax.devices()[:4])
    exe.run(prog, feed=feed, fetch_list=[vs["loss"]])
    seen = _spy_hlo(prog._cache)
    exe.run(prog, feed=feed, fetch_list=[vs["loss"]])
    assert _hidden_rows_moved(seen["hlo"], cfg.hidden, 4)


# -- the analyzer's rules for the op ---------------------------------------

def _head_program(rows=(4, 16), hidden=32, vocab=96, train=True):
    x = fluid.data("ax", shape=list(rows) + [hidden], dtype="float32")
    lab = fluid.data("al", shape=list(rows), dtype="int64")
    w = fluid.layers.create_parameter([vocab, hidden], "float32", name="aw")
    loss = fluid.layers.mean(fluid.layers.linear_softmax_with_cross_entropy(
        x, w, lab, ignore_index=-1))
    if train:
        fluid.optimizer.SGD(0.1).minimize(loss)
    return loss


def test_analyzer_infers_the_ops_shapes_and_is_clean(fresh_programs):
    from paddle_tpu import analysis
    from paddle_tpu.analysis import shapes

    loss = _head_program()
    prog = fluid.default_main_program()
    report = analysis.analyze(prog, feed_names=["ax", "al"],
                              fetch_names=[loss.name], platform="cpu")
    assert not report.errors, [str(d) for d in report.errors]
    env, rep = shapes.propagate(prog)
    assert not rep.errors
    head = [op for op in prog.global_block().ops
            if op.type == "linear_softmax_with_cross_entropy"][0]
    assert tuple(env[head.output("Loss")[0]].shape) == (4, 16, 1)
    assert env[head.output("Loss")[0]].dtype == np.float32
    for slot in ("Rows", "Chunks"):
        spec = env[head.output(slot)[0]]
        assert tuple(spec.shape) == () and spec.dtype == np.int32


def test_cost_rule_counts_every_row_as_labelled(fresh_programs):
    """The labelled rows are unknown before the labels are fed: the op is
    costed as the pair it replaces, all B*T rows, not as one trip of its
    loop."""
    from paddle_tpu.analysis import costs

    loss = _head_program(rows=(64, 128), hidden=32, vocab=16384,
                         train=False)
    rep = costs.analyze_cost(fluid.default_main_program(),
                             feed_names=["ax", "al"],
                             fetch_names=[loss.name])
    (head,) = [c for c in rep.per_op
               if c.op_type == "linear_softmax_with_cross_entropy"]
    rows = 64 * 128
    assert loss_ops.head_chunk_rows(rows, 16384) < rows  # several trips
    assert head.flops == 2.0 * rows * 32 * 16384 + 6.0 * rows * 16384
    # bytes: X, W and the labels in, the per-row loss and two counters out
    assert head.bytes == (rows * 32 + 16384 * 32 + rows) * 4 + rows * 4 + 8


def test_memory_rule_holds_a_chunk_not_the_logits(fresh_programs):
    from paddle_tpu.analysis import memory

    loss = _head_program(rows=(256, 128), hidden=768, vocab=30522)
    prog = fluid.default_main_program()
    rep = memory.estimate(prog, fetch_names=[loss.name])
    chunk = 4 * (2 * 2048 * 30522 + 30522 * 768)
    x_bytes, w_bytes = 256 * 128 * 768 * 4, 30522 * 768 * 4
    assert rep.peak_op_type in ("linear_softmax_with_cross_entropy",
                                "backward")
    assert rep.peak_bytes >= w_bytes + x_bytes + chunk
    # far from the [256, 128, 30522] float32 array the pair would hold
    assert rep.peak_bytes < w_bytes * 3 + x_bytes * 2 + chunk + (1 << 20)
    assert rep.peak_bytes < 256 * 128 * 30522 * 4 // 4
    # batch shards divide the chunk's rows as they divide the activations
    rep4 = memory.estimate(prog, fetch_names=[loss.name], act_shards=4)
    assert rep4.act_bytes_at_peak < rep.act_bytes_at_peak
