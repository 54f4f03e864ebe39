"""Train -> save_inference_model -> AnalysisConfig deployment round trip."""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import tempfile

import numpy as np

import paddle_tpu.fluid as fluid


def main():
    x = fluid.data(name="x", shape=[None, 16], dtype="float32")
    h = fluid.layers.fc(x, 32, act="relu")
    out = fluid.layers.fc(h, 4, act="softmax")
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    model_dir = tempfile.mkdtemp(prefix="paddle_tpu_model_")
    fluid.io.save_inference_model(model_dir, ["x"], [out], exe)
    print("saved to", model_dir)

    cfg = fluid.core.AnalysisConfig(model_dir)
    predictor = fluid.core.create_paddle_predictor(cfg)
    probs = predictor.run({"x": np.random.rand(2, 16).astype("float32")})[0]
    print("probs:", np.round(probs, 3), "sum:", probs.sum(axis=-1))


if __name__ == "__main__":
    main()
