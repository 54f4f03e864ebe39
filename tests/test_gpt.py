"""GPT decoder-only LM + KV-cache generation (models/gpt.py).

Exactness bar mirrors tests/test_transformer_decode.py: the incremental
KV-cache greedy decode must reproduce, token for token, a full-context
recompute (run the TRAINING graph on the growing prefix and argmax the
last position) using the same trained weights.
"""
import numpy as np
import pytest

import paddle_tpu.fluid as fluid
from paddle_tpu.models import gpt

PLEN, NEW = 6, 8


def _train_tiny(steps=60):
    cfg = gpt.gpt_tiny(vocab=97, max_len=32)
    seq = 16
    vs = gpt.build_gpt_lm(cfg, seq)
    # pruned inference clone BEFORE minimize: running the training
    # program to "just read logits" would also run the Adam update
    infer_prog = fluid.default_main_program().clone(
        for_test=True)._prune([vs["logits"]])
    fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    ids, labels = gpt.synthetic_lm_batch(cfg, 32, seq)
    losses = []
    for _ in range(steps):
        out = exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                      fetch_list=[vs["loss"]])
        losses.append(float(np.asarray(out[0])))
    return cfg, seq, vs, exe, losses, infer_prog


def test_gpt_lm_trains():
    _, _, _, _, losses, _ = _train_tiny(steps=25)
    assert all(np.isfinite(v) for v in losses)
    assert losses[-1] < losses[0] * 0.7, losses


def test_gpt_greedy_incremental_matches_full_recompute():
    cfg, seq, vs, exe, _, infer_prog = _train_tiny()
    gen_prog, gen_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_prog, gen_startup):
        gen = gpt.build_gpt_generate(cfg, PLEN, NEW, mode="greedy")
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab, size=(4, PLEN)).astype("int64")
    got = np.asarray(exe.run(gen_prog, feed={"gpt_prompt": prompt},
                             fetch_list=[gen["ids"]])[0])
    assert got.shape == (4, PLEN + NEW - 1)
    # teacher-forced region must echo the prompt
    np.testing.assert_array_equal(got[:, :PLEN - 1], prompt[:, 1:])

    # full-context reference: extend the prefix one token at a time by
    # argmaxing the TRAINING graph's logits at the last real position
    # (causal mask -> trailing pad can't affect it)
    ref = prompt.copy()
    while ref.shape[1] < PLEN + NEW:
        cur = np.zeros((4, seq), "int64")
        cur[:, :ref.shape[1]] = ref
        logits = np.asarray(exe.run(
            infer_prog, feed={"gpt_ids": cur},
            fetch_list=[vs["logits"]])[0])
        nxt = np.argmax(logits[:, ref.shape[1] - 1], axis=-1)
        ref = np.concatenate([ref, nxt[:, None].astype("int64")], 1)
    np.testing.assert_array_equal(got[:, PLEN - 1:], ref[:, PLEN:])


def test_gpt_topk_sampling_valid_and_varied():
    cfg, _, _, exe, _, _ = _train_tiny(steps=10)
    gen_prog, gen_startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_prog, gen_startup):
        gen = gpt.build_gpt_generate(cfg, PLEN, NEW, mode="topk",
                                     topk=5, temperature=1.0)
    rng = np.random.default_rng(5)
    prompt = rng.integers(1, cfg.vocab, size=(8, PLEN)).astype("int64")
    got = np.asarray(exe.run(gen_prog, feed={"gpt_prompt": prompt},
                             fetch_list=[gen["ids"]])[0])
    assert got.shape == (8, PLEN + NEW - 1)
    assert got.min() >= 0 and got.max() < cfg.vocab
    np.testing.assert_array_equal(got[:, :PLEN - 1], prompt[:, 1:])
    sampled = got[:, PLEN - 1:]
    # per-step RNG must vary across steps/rows: a degenerate constant
    # output would mean the scan reused one key
    assert len(np.unique(sampled)) > 1


def test_gpt_generate_rejects_overlong():
    cfg = gpt.gpt_tiny(vocab=50, max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        gpt.build_gpt_generate(cfg, 6, 6)


def test_gpt_generate_inference_model_roundtrip(tmp_path):
    """Deploying generation: save_inference_model on the generate
    program (StaticRNN sub-blocks + caches serialize), reload, run with
    ONLY the prompt feed — outputs must be bit-identical."""
    cfg, _, _, exe, _, _ = _train_tiny(steps=20)
    gen_prog, gs = fluid.Program(), fluid.Program()
    with fluid.program_guard(gen_prog, gs):
        gen = gpt.build_gpt_generate(cfg, PLEN, NEW, mode="greedy")
    rng = np.random.default_rng(11)
    prompt = rng.integers(1, cfg.vocab, size=(2, PLEN)).astype("int64")
    want = np.asarray(exe.run(gen_prog, feed={"gpt_prompt": prompt},
                              fetch_list=[gen["ids"]])[0])
    d = str(tmp_path)
    fluid.io.save_inference_model(d, ["gpt_prompt"], [gen["ids"]], exe,
                                  main_program=gen_prog)
    prog2, feeds, fetches = fluid.io.load_inference_model(d, exe)
    got = np.asarray(exe.run(prog2, feed={feeds[0]: prompt},
                             fetch_list=fetches)[0])
    np.testing.assert_array_equal(got, want)


def test_gpt_prefill_step_bit_identical_to_generate():
    """The factored two-program decode path (ISSUE 9): bucketed prefill
    writes a slot's cache + first token, the per-slot step program
    decodes the rest — and the tokens must be BIT-identical to the
    single-scan build_gpt_generate greedy output on the same prompt,
    with the batch dim acting as a slot dim (mixed prompt lengths at
    mixed per-row positions in one batch)."""
    cfg, _, _, exe, _, _ = _train_tiny(steps=30)
    cache_len, bucket = 24, 8

    pf_prog, pf_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(pf_prog, pf_st):
        pf = gpt.build_gpt_prefill(cfg, bucket, cache_len)
    st_prog, st_st = fluid.Program(), fluid.Program()
    with fluid.program_guard(st_prog, st_st):
        st = gpt.build_gpt_decode_step(cfg, cache_len)

    rng = np.random.default_rng(17)
    lens = [3, 6, 8]  # mixed lengths sharing one slot batch
    prompts = [rng.integers(1, cfg.vocab, n).astype("int64")
               for n in lens]
    n_new = 7
    ids = np.zeros((len(lens), bucket), "int64")
    for i, p in enumerate(prompts):
        ids[i, :lens[i]] = p
    plen = np.asarray(lens, "int64").reshape(-1, 1)
    tok, k, v = map(np.asarray, exe.run(
        pf_prog, feed={"gpt_prefill_ids": ids, "gpt_prefill_len": plen},
        fetch_list=[pf["next"], pf["k"], pf["v"]]))
    assert k.shape == (len(lens), cfg.num_layers, cache_len, cfg.hidden)
    # the step takes the slot cache per layer: (S, T, H) K then V
    # buffers under st["cache_feed_names"], fetched in the same order
    cache = [k[:, i] for i in range(cfg.num_layers)] + [
        v[:, i] for i in range(cfg.num_layers)]
    assert len(st["cache_feed_names"]) == 2 * cfg.num_layers
    toks, pos = [tok], plen.copy()
    for _ in range(n_new - 1):
        feed = dict(zip(st["cache_feed_names"], cache),
                    gpt_step_tok=tok, gpt_step_pos=pos)
        tok, *cache = map(np.asarray, exe.run(
            st_prog, feed=feed,
            fetch_list=[st["next"]] + st["k"] + st["v"]))
        toks.append(tok)
        pos = pos + 1
    got = np.concatenate(toks, axis=1)

    for i, (p, n) in enumerate(zip(prompts, lens)):
        g_prog, g_st = fluid.Program(), fluid.Program()
        with fluid.program_guard(g_prog, g_st):
            gen = gpt.build_gpt_generate(cfg, n, n_new, mode="greedy")
        want = np.asarray(exe.run(
            g_prog, feed={"gpt_prompt": p.reshape(1, -1)},
            fetch_list=[gen["ids"]])[0])
        np.testing.assert_array_equal(got[i], want[0, n - 1:])


def test_gpt_prefill_rejects_bad_lengths():
    cfg = gpt.gpt_tiny(vocab=50, max_len=16)
    with pytest.raises(ValueError, match="prompt_len"):
        gpt.build_gpt_prefill(cfg, 12, 8)
    with pytest.raises(ValueError, match="max_len"):
        gpt.build_gpt_prefill(cfg, 8, 32)
    with pytest.raises(ValueError, match="max_len"):
        gpt.build_gpt_decode_step(cfg, 32)


def test_gpt_trains_sharded_dp_tp():
    """GPT under GSPMD dp x tp via DistributedProgram + tp_rules: loss
    decreases and matches the unsharded run (sharding is a layout)."""
    import jax

    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid import executor as exmod
    from paddle_tpu.parallel.mesh import build_mesh
    from paddle_tpu.parallel.sharding import (
        DistributedProgram, ShardingRule)

    def run(sharded):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        exmod._scope_stack[:] = [exmod.Scope()]
        fluid.default_startup_program().random_seed = 9
        cfg = gpt.gpt_tiny(vocab=96, max_len=32)
        vs = gpt.build_gpt_lm(cfg, 16)
        fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
        feed = {"gpt_ids": ids, "gpt_labels": labels}
        if sharded:
            mesh = build_mesh({"dp": 4, "tp": 2})
            dist = DistributedProgram(
                fluid.default_main_program(), mesh,
                param_rules=[ShardingRule(p, s)
                             for p, s in gpt.tp_rules()],
                feed_axis="dp")
            target = dist
        else:
            target = fluid.default_main_program()
        losses = [float(np.asarray(exe.run(
            target, feed=feed, fetch_list=[vs["loss"]])[0]))
            for _ in range(6)]
        return losses

    plain = run(False)
    shard = run(True)
    assert shard[-1] < shard[0]
    np.testing.assert_allclose(plain, shard, rtol=2e-4, atol=2e-5)
