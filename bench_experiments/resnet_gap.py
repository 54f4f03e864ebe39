"""Chase the ResNet-50 8% framework-vs-pure-jax gap (VERDICT r3 #6):
57ms framework vs 53ms pure-jax control at b128/224 bf16.

Targeted ablations, one suspect at a time (env knobs live in
ops/nn_ops.py _batch_norm, marked experiment-only):
- baseline          — framework Momentum + bf16 AMP (re-measure)
- bn_bf16_apply     — BN normalize in bf16 (per-channel scalars f32)
- bn_freeze_stats   — moving-stat update ablated (bounds its cost)
- both              — the two BN knobs together
- sgd               — Momentum -> SGD (bounds optimizer state traffic)

Self-exiting; banks to bench_experiments/resnet_gap.json after every
variant. Ship whichever knob wins as the default;
document whichever doesn't in BENCHMARKS.md.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


def measure(tag, env=(), sgd=False):
    import bench

    for k in ("PADDLE_TPU_BN_BF16_APPLY", "PADDLE_TPU_BN_FREEZE_STATS"):
        os.environ.pop(k, None)
    for k in env:
        os.environ[k] = "1"
    try:
        if sgd:
            import paddle_tpu.fluid as fluid

            orig = fluid.optimizer.Momentum

            def as_sgd(lr, mu, **kw):
                return fluid.optimizer.SGD(lr, **kw)

            fluid.optimizer.Momentum = as_sgd
            try:
                out = bench._measure_resnet(n_steps=20)
            finally:
                fluid.optimizer.Momentum = orig
        else:
            out = bench._measure_resnet(n_steps=20)
    finally:
        for k in env:
            os.environ.pop(k, None)
    out["tag"] = tag
    return out


def main():
    bank = Bank(__file__)
    plan = [
        ("baseline", (), False),
        ("bn_bf16_apply", ("PADDLE_TPU_BN_BF16_APPLY",), False),
        ("bn_freeze_stats", ("PADDLE_TPU_BN_FREEZE_STATS",), False),
        ("both", ("PADDLE_TPU_BN_BF16_APPLY",
                  "PADDLE_TPU_BN_FREEZE_STATS"), False),
        ("sgd", (), True),
    ]
    for tag, env, sgd in plan:
        bank.run(tag, lambda tag=tag, env=env, sgd=sgd: measure(
            tag, env, sgd))
    bank.done()


if __name__ == "__main__":
    enable_compile_cache()
    main()
