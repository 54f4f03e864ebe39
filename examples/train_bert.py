"""BERT-base MLM pretraining through the fluid API.

CPU smoke:   JAX_PLATFORMS=cpu python examples/train_bert.py --tiny --steps 5
TPU:         python examples/train_bert.py --steps 100
"""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import argparse
import time

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid.contrib.mixed_precision import decorate
from paddle_tpu.models import bert


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--bf16", action="store_true")
    args = ap.parse_args()

    fluid.default_startup_program().random_seed = 7
    cfg = bert.bert_tiny() if args.tiny else bert.bert_base()
    seq = min(args.seq, cfg.max_seq)
    vs = bert.build_bert_pretrain(cfg, seq)
    opt = fluid.optimizer.Adam(learning_rate=1e-4)
    if args.bf16:
        opt = decorate(opt, use_bf16=True)
    opt.minimize(vs["loss"])

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, labels = bert.synthetic_batch(cfg, args.batch, seq)
    feed = {"input_ids": ids, "mlm_labels": labels}
    t0 = time.time()
    for step in range(args.steps):
        # head_rows / head_chunks: the labelled positions of the step and
        # the chunks of them the fused vocabulary head ran, counted on the
        # device
        loss, rows, chunks = exe.run(
            feed=feed,
            fetch_list=[vs["loss"], vs["head_rows"], vs["head_chunks"]])
        if step % 10 == 0 or step == args.steps - 1:
            print("step %d loss %.4f head_rows %d head_chunks %d"
                  % (step, float(np.asarray(loss)), int(rows), int(chunks)))
    dt = time.time() - t0
    print("%.0f tokens/sec" % (args.steps * args.batch * seq / dt))


if __name__ == "__main__":
    main()
