"""Post-training quantization to a real-int8 inference model.

Train fp32 -> save inference model -> calibrate with sample batches ->
int8 program (int8 MXU matmuls, int32 accumulation) -> save -> reload
and compare accuracy. (ref workflow: slim PostTrainingQuantization.)

Run: python examples/quantize_int8.py
"""
import os
import sys
import tempfile

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import paddle_tpu.fluid as fluid  # noqa: E402
from paddle_tpu.fluid.contrib.slim.quantization import (  # noqa: E402
    PostTrainingQuantization,
)

D, H, C = 20, 64, 5


def main():
    rng = np.random.default_rng(0)
    xs = rng.standard_normal((2048, D)).astype("float32")
    ys = np.argmax(xs[:, :C], axis=1).astype("int64")[:, None]

    x = fluid.data("x", shape=[None, D], dtype="float32")
    y = fluid.data("y", shape=[None, 1], dtype="int64")
    h = fluid.layers.fc(x, H, act="relu")
    logits = fluid.layers.fc(h, C)
    loss = fluid.layers.mean(
        fluid.layers.softmax_with_cross_entropy(logits, y))
    test_prog = fluid.default_main_program().clone(for_test=True)
    fluid.optimizer.Adam(5e-3).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(fluid.default_startup_program())
    for i in range(0, 2048, 128):
        exe.run(feed={"x": xs[i:i + 128], "y": ys[i:i + 128]},
                fetch_list=[loss])

    def accuracy(prog, fetches):
        (lv,) = exe.run(prog, feed={"x": xs, "y": ys},
                        fetch_list=fetches)
        return float((np.argmax(lv, 1) == ys[:, 0]).mean())

    fp32_acc = accuracy(test_prog, [logits])
    tmp = tempfile.mkdtemp(prefix="int8_")
    fp32_dir = os.path.join(tmp, "fp32")
    fluid.io.save_inference_model(
        fp32_dir, ["x"], [logits], exe, main_program=test_prog)

    ptq = PostTrainingQuantization(
        executor=exe,
        sample_generator=lambda: ((xs[i],) for i in range(256)),
        model_dir=fp32_dir, batch_size=32, batch_nums=8, algo="KL")
    ptq.quantize()
    int8_dir = os.path.join(tmp, "int8")
    ptq.save_quantized_model(int8_dir)

    prog, feeds, fetches = fluid.io.load_inference_model(int8_dir, exe)
    (lv,) = exe.run(prog, feed={"x": xs}, fetch_list=fetches)
    int8_acc = float((np.argmax(lv, 1) == ys[:, 0]).mean())
    ops = [op.type for op in prog.global_block().ops]
    print("fp32 accuracy: %.4f" % fp32_acc)
    print("int8 accuracy: %.4f (ops: %s)" % (int8_acc, ops))
    assert int8_acc > fp32_acc - 0.01


if __name__ == "__main__":
    main()
