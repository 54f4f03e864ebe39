"""Decompose the Wide&Deep CTR step (first TPU numbers this round:
9,899 -> 18,265 ex/s after columnar feeds + device double-buffer;
112ms/step remains at batch 2048 where the jitted step itself should be
~1ms). Measures, on chip:

  step_only      — one batch pre-staged on device, tight exe.run loop
                   (no fetch): jitted step + executor dispatch only.
  step_fetch     — same loop fetching the loss as numpy every step:
                   adds the device->host sync each step.
  pipeline       — the full train_from_dataset path (parse done at
                   load; columnar batches -> loader -> device prefetch
                   -> step): what bench.py reports.
  pipeline_b8192 — same, batch 8192: does the sparse path scale?

Self-exiting; banks to ctr_breakdown.json per variant.
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


def _build(batch_hint=2048):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import wide_deep

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    vs = wide_deep.build_wide_deep()
    fluid.optimizer.Adam(1e-3).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    return fluid, vs, exe


def step_loop(fetch, batch=2048, n_steps=100):
    import jax
    import numpy as np

    fluid, vs, exe = _build()
    from paddle_tpu.models import wide_deep

    dense, sparse, label = wide_deep.synthetic_ctr_batch(batch)
    feed = {"dense": jax.device_put(dense),
            "sparse": jax.device_put(sparse),
            "ctr_label": jax.device_put(label)}
    fl = [vs["loss"]]
    t0 = time.time()
    exe.run(feed=feed, fetch_list=fl)
    compile_s = time.time() - t0
    exe.run(feed=feed, fetch_list=fl)
    t0 = time.time()
    for _ in range(n_steps):
        out = exe.run(feed=feed, fetch_list=fl,
                      return_numpy=fetch)
    if not fetch:
        float(np.asarray(out[0]))
    dt = time.time() - t0
    return {
        "examples_per_sec": round(n_steps * batch / dt, 1),
        "step_ms": round(1000 * dt / n_steps, 3),
        "batch": batch, "steps": n_steps, "fetch_numpy": fetch,
        "compile_s": round(compile_s, 1),
    }


def pipeline(batch=2048, rows=49152, epochs=2):
    import bench

    return bench._measure_ctr(batch=batch, rows=rows, epochs=epochs)


def main():
    bank = Bank(__file__)
    plan = [
        ("step_only", lambda: step_loop(fetch=False)),
        ("step_fetch", lambda: step_loop(fetch=True)),
        ("pipeline", lambda: pipeline()),
        ("pipeline_b8192", lambda: pipeline(batch=8192)),
        ("step_only_b8192",
         lambda: step_loop(fetch=False, batch=8192, n_steps=50)),
    ]
    for tag, fn in plan:
        bank.run(tag, fn)
    bank.done()


if __name__ == "__main__":
    enable_compile_cache()
    main()
