"""SSD detector training + NMS inference (examples of the detection
suite). Runs on CPU in ~a minute."""
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import framework, unique_name
from paddle_tpu.models import ssd


def main():
    fluid.default_startup_program().random_seed = 3
    vs = ssd.build_ssd_train(num_classes=4, image_size=64)
    fluid.optimizer.Adam(learning_rate=2e-3).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(0)
    for step in range(10):
        img, boxes, labels = ssd.synthetic_batch(rng)
        loss = exe.run(
            feed={"image": img, "gt_box": boxes, "gt_label": labels},
            fetch_list=[vs["loss"]],
        )[0]
        print("step %d loss %.4f" % (step, float(np.asarray(loss))))

    # fresh program for the NMS inference head
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    iv = ssd.build_ssd_infer(num_classes=4, image_size=64, keep_top_k=10)
    exe2 = fluid.Executor()
    exe2.run(fluid.default_startup_program())
    img, _, _ = ssd.synthetic_batch(rng)
    det = exe2.run(feed={"image": img}, fetch_list=[iv["detections"]])[0]
    kept = det[0][det[0, :, 0] >= 0]
    print("detections (label, score, x1, y1, x2, y2):")
    print(np.round(kept, 3))


if __name__ == "__main__":
    main()
