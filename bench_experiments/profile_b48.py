"""Capture a jax.profiler trace of the b48 BERT headline step and
distill the top time sinks (VERDICT r4 next-step #7).

Runs the exact bench.py b48 configuration (framework path, bf16 AMP,
XLA attention), traces a handful of steady-state steps, then parses the
chrome-trace events from the profile dir and aggregates device-track
op durations into a top-N table. Banks to profile_b48.json; the trace
dir itself is left under .bench_runs/profile_b48/ for tensorboard.

Self-exiting.
"""
import glob
import gzip
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from _bank import Bank, enable_compile_cache  # noqa: E402


def _aggregate_trace(trace_dir, top_n=25):
    """Sum 'X' (complete) event durations by event name across the
    device tracks of the newest .trace.json.gz under trace_dir."""
    paths = glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True)
    if not paths:
        return None, "no trace.json.gz under %s" % trace_dir
    path = max(paths, key=os.path.getmtime)
    with gzip.open(path, "rt") as f:
        trace = json.load(f)
    events = trace.get("traceEvents", [])
    # pid -> process name; device tracks are the TPU/accelerator pids
    pid_names = {}
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "process_name":
            pid_names[ev.get("pid")] = \
                ev.get("args", {}).get("name", "")
    device_pids = {
        pid for pid, name in pid_names.items()
        if any(k in name.lower() for k in ("tpu", "device", "/device",
                                           "xla"))
        and "host" not in name.lower()
    }
    if not device_pids:
        # CPU runs expose only '/host:CPU'; aggregate everything rather
        # than return an empty table
        device_pids = set(pid_names)
    sums = {}
    total = 0.0
    for ev in events:
        if ev.get("ph") != "X" or ev.get("pid") not in device_pids:
            continue
        dur = float(ev.get("dur", 0.0))   # microseconds
        name = ev.get("name", "?")
        sums[name] = sums.get(name, 0.0) + dur
        total += dur
    top = sorted(sums.items(), key=lambda kv: -kv[1])[:top_n]
    return {
        "trace_file": os.path.relpath(path, trace_dir),
        "device_tracks": sorted(pid_names[p] for p in device_pids),
        "total_device_us": round(total, 1),
        "top": [
            {"name": n, "us": round(us, 1),
             "pct": round(100.0 * us / total, 2) if total else 0.0}
            for n, us in top
        ],
    }, None


def run_profile(batch=48, seq=128, warm_steps=4, traced_steps=10):
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import bert

    os.environ["PADDLE_TPU_DISABLE_PALLAS"] = "1"
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    fluid.default_main_program().random_seed = 7
    cfg = bert.bert_base()
    vs = bert.build_bert_pretrain(cfg, seq)
    from paddle_tpu.fluid.contrib.mixed_precision import decorate

    opt = decorate(fluid.optimizer.Adam(learning_rate=1e-4),
                   use_bf16=True)
    opt.minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, labels = bert.synthetic_batch(cfg, batch, seq)
    feed = {"input_ids": ids, "mlm_labels": labels}
    fetch = [vs["loss"]]

    import jax

    for _ in range(warm_steps):
        out = exe.run(feed=feed, fetch_list=fetch, return_numpy=False)
    float(np.asarray(out[0]))

    trace_dir = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        ".bench_runs", "profile_b48")
    os.makedirs(trace_dir, exist_ok=True)
    t0 = time.time()
    with jax.profiler.trace(trace_dir):
        for _ in range(traced_steps):
            out = exe.run(feed=feed, fetch_list=fetch,
                          return_numpy=False)
        float(np.asarray(out[0]))
    wall = time.time() - t0
    table, err = _aggregate_trace(trace_dir)
    res = {
        "batch": batch, "seq": seq, "traced_steps": traced_steps,
        "traced_wall_s": round(wall, 2),
        "step_ms": round(1000 * wall / traced_steps, 2),
        "tokens_per_sec": round(traced_steps * batch * seq / wall, 1),
    }
    if err:
        res["trace_error"] = err
    else:
        res.update(table)
    return res


if __name__ == "__main__":
    enable_compile_cache()
    bank = Bank(__file__)
    bank.run("profile_b48", run_profile)
    bank.done()
