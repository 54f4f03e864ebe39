"""int8 inference throughput on the real chip: does the MXU's native
int8 path (2x bf16 peak on v5e: 394 vs 197 TOPS) show up through the
framework's real-int8 quantized ops (slim freeze/convert ->
quantized_mul: int8xint8 -> int32 dot_general)?

Three levels, each banked separately (self-exiting):
1. primitive — raw dot_general at BERT shapes, bf16 vs int8
2. end-to-end BERT-base ENCODER inference: bf16-AMP baseline vs the
   quantized program (every fc weight int8; attention act-act matmuls
   stay high precision, as the transform pass defines)
3. tiny-MLP PTQ accuracy sanity (the int8 program must still be right
   on chip, not just fast)

Writes bench_experiments/int8_infer.json.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


def measure_primitive(m=4096, k=768, n=3072, iters=50):
    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    a8 = jax.device_put(rng.integers(-127, 127, (m, k), dtype=np.int8))
    b8 = jax.device_put(rng.integers(-127, 127, (k, n), dtype=np.int8))
    abf = jax.device_put(rng.standard_normal((m, k)).astype(
        jnp.bfloat16))
    bbf = jax.device_put(rng.standard_normal((k, n)).astype(
        jnp.bfloat16))

    @jax.jit
    def dot_i8(a, b):
        return jax.lax.dot_general(
            a, b, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)

    @jax.jit
    def dot_bf(a, b):
        return a @ b

    out = {}
    for tag, fn, x, y in (("int8", dot_i8, a8, b8),
                          ("bf16", dot_bf, abf, bbf)):
        fn(x, y).block_until_ready()
        t0 = time.time()
        for _ in range(iters):
            r = fn(x, y)
        r.block_until_ready()
        dt = time.time() - t0
        tops = 2 * m * k * n * iters / dt / 1e12
        out[tag] = {"tops": round(tops, 2),
                    "us_per_matmul": round(1e6 * dt / iters, 1)}
    out["tag"] = "primitive_%dx%dx%d" % (m, k, n)
    out["speedup_int8_vs_bf16"] = round(
        out["int8"]["tops"] / out["bf16"]["tops"], 3)
    return out


def _fresh():
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    return fluid


def measure_bert_encoder(batch=32, seq=128, n_iters=20):
    """bf16-infer baseline vs frozen-int8 program, tokens/sec."""
    import numpy as np

    import jax as _jax

    fluid = _fresh()
    from paddle_tpu.models import bert

    cfg = bert.bert_base()
    cfg.dropout = 0.0
    vs = bert.build_bert_pretrain(cfg, seq, is_test=True)
    infer_prog = fluid.default_main_program()._prune([vs["encoder_out"]])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, _ = bert.synthetic_batch(cfg, batch, seq)
    ids = _jax.device_put(ids)

    def timed(prog, tag):
        t0 = time.time()
        exe.run(prog, feed={"input_ids": ids},
                fetch_list=[vs["encoder_out"]])
        compile_s = time.time() - t0
        t0 = time.time()
        for _ in range(n_iters):
            out = exe.run(prog, feed={"input_ids": ids},
                          fetch_list=[vs["encoder_out"]],
                          return_numpy=False)
        np.asarray(out[0])
        dt = time.time() - t0
        return {"tag": tag,
                "tokens_per_sec": round(n_iters * batch * seq / dt, 1),
                "step_ms": round(1000 * dt / n_iters, 2),
                "compile_s": round(compile_s, 1)}

    from paddle_tpu.fluid.contrib.mixed_precision import (
        AutoMixedPrecisionLists, _rewrite_program_bf16)

    bf16_prog = infer_prog.clone()
    _rewrite_program_bf16(bf16_prog, AutoMixedPrecisionLists())
    base = timed(bf16_prog, "bert_enc_infer_bf16")

    # post-training quantization in memory (abs_max: fast calibration)
    from paddle_tpu.fluid.contrib.slim.quantization import (
        PostTrainingQuantization)

    ids_host, _ = bert.synthetic_batch(cfg, 64, seq, seed=1)
    ptq = PostTrainingQuantization(
        executor=exe,
        sample_generator=lambda: ((row,) for row in ids_host),
        program=infer_prog.clone(), feed_list=["input_ids"],
        fetch_list=[vs["encoder_out"]], batch_size=8, batch_nums=4,
        algo="abs_max", quantizable_op_type=["mul", "matmul"])
    qprog = ptq.quantize()
    q = timed(qprog, "bert_enc_infer_int8")
    q["speedup_vs_bf16"] = round(
        q["tokens_per_sec"] / base["tokens_per_sec"], 3)
    return [base, q]


def measure_mlp_accuracy():
    """PTQ accuracy sanity on chip (int8 program must stay correct)."""
    import numpy as np

    fluid = _fresh()

    rng = np.random.default_rng(0)
    xs = rng.standard_normal((1024, 16)).astype("float32")
    ys = np.argmax(xs[:, :4], axis=1).astype("int64")[:, None]
    x = fluid.data("qx", shape=[None, 16], dtype="float32")
    y = fluid.data("qy", shape=[None, 1], dtype="int64")
    h = fluid.layers.fc(x, 32, act="relu")
    logits = fluid.layers.fc(h, 4)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    test_prog = fluid.default_main_program().clone(
        for_test=True)._prune([logits])
    fluid.optimizer.Adam(5e-3).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    for _ in range(4):
        for i in range(0, 1024, 128):
            exe.run(feed={"qx": xs[i:i + 128], "qy": ys[i:i + 128]},
                    fetch_list=[loss])

    def acc(prog):
        (lv,) = exe.run(prog, feed={"qx": xs}, fetch_list=[logits])
        return float((np.argmax(np.asarray(lv), 1) == ys[:, 0]).mean())

    fp32 = acc(test_prog)
    from paddle_tpu.fluid.contrib.slim.quantization import (
        PostTrainingQuantization)

    ptq = PostTrainingQuantization(
        executor=exe,
        sample_generator=lambda: ((xs[i],) for i in range(256)),
        program=test_prog.clone(), feed_list=["qx"],
        fetch_list=[logits], batch_size=32, batch_nums=8,
        algo="abs_max")
    qprog = ptq.quantize()
    int8 = acc(qprog)
    return {"tag": "mlp_ptq_accuracy", "fp32_acc": round(fp32, 4),
            "int8_acc": round(int8, 4)}


def main():
    bank = Bank(__file__)
    bank.run("primitive_ffn", lambda: measure_primitive(4096, 768, 3072))
    bank.run("primitive_qkv", lambda: measure_primitive(4096, 768, 768))
    bank.run("mlp_accuracy", measure_mlp_accuracy)
    bank.run("bert_encoder", measure_bert_encoder)
    bank.done()


if __name__ == "__main__":
    enable_compile_cache()
    main()
