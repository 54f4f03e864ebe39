"""Clean A/B: 8-bit quantized dropout masks (PADDLE_TPU_DROPOUT_BITS=8)
vs 32-bit float thresholds, at the two headline shapes (b48/s128 and
b16/s512). Decides whether 8-bit ships as the default: the s512
ablation showed dropout is ~18% of the step there, but the first mixed
readings were contended — this run is back-to-back on an idle host.

Self-exiting; banks to dropout_bits_ab.json per variant.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


def measure(tag, bits, batch, seq, n_steps):
    import bench

    os.environ["PADDLE_TPU_DROPOUT_BITS"] = bits
    try:
        variant, cfg = bench._measure(tag, True, False, batch, seq,
                                      n_steps)
    finally:
        os.environ.pop("PADDLE_TPU_DROPOUT_BITS", None)
    variant["dropout_bits"] = bits
    variant["mfu"] = round(
        variant["tokens_per_sec"]
        * bench._flops_per_token_train(cfg, seq) / 197e12, 4)
    return variant


def main():
    bank = Bank(__file__)
    plan = [
        ("s128_b48_bits8", "8", 48, 128, 30),
        ("s128_b48_bits32", "32", 48, 128, 30),
        ("s512_b16_bits8", "8", 16, 512, 12),
        ("s512_b16_bits32", "32", 16, 512, 12),
        # repeat pass to separate signal from run-to-run noise
        ("s128_b48_bits8_r2", "8", 48, 128, 30),
        ("s128_b48_bits32_r2", "32", 48, 128, 30),
        ("s512_b16_bits8_r2", "8", 16, 512, 12),
        ("s512_b16_bits32_r2", "32", 16, 512, 12),
    ]
    for tag, bits, batch, seq, n in plan:
        bank.run(tag, lambda t=tag, b=bits, ba=batch, s=seq, ns=n:
                 measure(t, b, ba, s, ns))
    bank.done()


if __name__ == "__main__":
    enable_compile_cache()
    main()
