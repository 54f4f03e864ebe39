"""Headline benchmark: BERT-base MLM pretraining throughput, tokens/sec/chip
(matches BASELINE.json: "BERT-base tokens/sec/chip").

Runs the full framework path — fluid Program -> single-XLA-module train step
(vjp backward + Adam) in bf16 compute — in ONE process that holds the chip.
The accelerator plan needs a TPU: without one the run refuses (exit 2). With
``JAX_PLATFORMS=cpu`` set explicitly it runs the tiny ``_cpu``-named plan the
CPU lanes under bench_experiments/ use — a choice made from outside, never a
fallback. The first thing to run on a chip is ``chip_smoke.py``, not this.

Prints the device first, then ONE JSON line:
  {"metric": ..., "value": N, "unit": "tokens/sec/chip", "vs_baseline": N,
   "detail": {"backend", "device_kind", "n_devices", "variants", "errors",
              ...}}
A phase that raises is recorded under ``detail.errors`` with its traceback
on stderr, the remaining phases still run, and the exit code is 1.

vs_baseline denominator: the reference stack's published-era BERT-base
single-GPU training throughput on V100 (fp32/amp mixed era) ~= 5300
tokens/sec (batch 32 x seq 128 at ~1.3 steps/s). BASELINE.json carries no
published number, so this documented constant is the comparison point.
"""
import json
import os
import sys
import time
import traceback

V100_BASELINE_TOKENS_PER_SEC = 5300.0


def _atomic_write_json(path, obj):
    with open(path + ".tmp", "w") as f:
        json.dump(obj, f)
    os.replace(path + ".tmp", path)


def _peak_flops(device_kind):
    """bf16 peak FLOPs/s by device_kind from the one device table
    (analysis/costs.py shares it with the roofline model). An unknown
    device raises: a utilisation is never dropped silently."""
    from paddle_tpu.analysis.costs import require_device_profile

    return require_device_profile(device_kind).peak_flops


def _flops_per_token_train(cfg, seq):
    """Analytic matmul FLOPs per trained token — shared with the static
    cost model (analysis/costs.py)."""
    from paddle_tpu.analysis.costs import bert_train_flops_per_token

    return bert_train_flops_per_token(cfg, seq)


def _measure(tag, on_accel, use_flash, batch, seq, n_steps,
             vocab_pad=None):
    """Build the program fresh and measure steady-state throughput."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid import compile_cache
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import bert

    if use_flash:
        os.environ.pop("PADDLE_TPU_DISABLE_PALLAS", None)
        # auto-engage is off by default; a flash variant must opt in or
        # it would silently measure the XLA path under a flash label
        os.environ["PADDLE_TPU_FLASH_MIN_SEQ"] = "1"
    else:
        os.environ["PADDLE_TPU_DISABLE_PALLAS"] = "1"
        os.environ.pop("PADDLE_TPU_FLASH_MIN_SEQ", None)

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    fluid.default_main_program().random_seed = 7

    cfg = bert.bert_base() if on_accel else bert.bert_tiny()
    if seq > cfg.max_seq:
        cfg.max_seq = seq          # position table must cover the seq len
    if vocab_pad:
        # Megatron-style vocab padding to an MXU-friendly multiple; ids
        # and labels stay < the true vocab so the task is unchanged
        cfg.vocab_size = vocab_pad
    cfg.use_fused_attention = use_flash
    vs = bert.build_bert_pretrain(cfg, seq)
    opt = fluid.optimizer.Adam(learning_rate=1e-4)
    if on_accel:
        from paddle_tpu.fluid.contrib.mixed_precision import decorate

        opt = decorate(opt, use_bf16=True)
    opt.minimize(vs["loss"])

    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())

    ids, labels = bert.synthetic_batch(cfg, batch, seq)
    if vocab_pad:
        ids = np.clip(ids, 0, 30521)
        labels = np.clip(labels, 0, 30521)
    feed = {"input_ids": ids, "mlm_labels": labels}
    fetch = [vs["loss"]]

    # warmup: step 1 compiles; step 2 settles donated-buffer layouts so the
    # timed loop measures steady state only. With the persistent AOT
    # cache active (PADDLE_TPU_COMPILE_CACHE_DIR) a warm process
    # resolves the compile from disk — the disk_hit/disk_miss deltas
    # below say which kind of compile_s this was.
    cc_hit0 = obs.counter("compile_cache.disk_hit")
    cc_miss0 = obs.counter("compile_cache.disk_miss")
    t0 = time.time()
    loss0 = float(exe.run(feed=feed, fetch_list=fetch)[0])
    compile_s = time.time() - t0
    exe.run(feed=feed, fetch_list=fetch)

    # timed steps; keep fetches on device so the loop isn't serialized on
    # per-step host readbacks (sync once at the end). The goodput
    # account decomposes the same window: productive step time vs any
    # in-loop compiles/retries (a warm steady-state loop should report
    # goodput ~1.0 — a sag here means the cache is churning)
    from paddle_tpu.observability import runhealth as _rh

    seed_slowdown = os.environ.get("PADDLE_TPU_BENCH_SEED_SLOWDOWN")
    acct = obs.GoodputAccount()
    prev_acct = _rh.set_active_goodput(acct)
    acct.start()
    t0 = time.time()
    try:
        for _ in range(n_steps):
            if seed_slowdown:
                # deliberate regression for perf_lane.sh: dropping the
                # executable LRU forces a cache lookup + AOT reload every
                # step, which --check-regressions must flag
                exe._cache.clear()
            with acct.step():
                out = exe.run(feed=feed, fetch_list=fetch,
                              return_numpy=False)
        last = float(np.asarray(out[0]))
        dt = time.time() - t0
    finally:
        acct.stop()
        _rh.set_active_goodput(prev_acct)
    tokens_per_sec = n_steps * batch * seq / dt

    variant = {
        "tag": tag,
        "tokens_per_sec": round(tokens_per_sec, 1),
        "batch": batch,
        "seq_len": seq,
        "flash_attention": use_flash,
        "steps": n_steps,
        "step_ms": round(1000 * dt / n_steps, 2),
        "compile_s": round(compile_s, 1),
        "loss_first": round(loss0, 4),
        "loss_last": round(last, 4),
        "goodput_fraction": round(acct.goodput_fraction(), 4),
    }
    # static roofline prediction next to the measurement: the
    # predicted-vs-measured column continuously validates the analyzer's
    # cost model against this lane (never sink the bench on a model bug)
    pred = None
    try:
        import jax as _jax

        from paddle_tpu.analysis import costs as _costs

        pred = _costs.predict_program(
            fluid.default_main_program(), feed_specs=feed,
            fetch_names=[vs["loss"].name],
            device_kind=getattr(_jax.devices()[0], "device_kind", None))
        if pred.get("predicted_step_seconds"):
            variant["predicted_step_ms"] = round(
                1000 * pred["predicted_step_seconds"], 2)
        if pred.get("predicted_mfu") is not None:
            variant["predicted_mfu"] = round(pred["predicted_mfu"], 4)
        if pred.get("predicted_peak_hbm_bytes") is not None:
            variant["predicted_peak_hbm_gb"] = round(
                pred["predicted_peak_hbm_bytes"] / 1e9, 3)
    except Exception as e:  # noqa: BLE001 — prediction is advisory
        variant["predicted_error"] = "%s: %s" % (type(e).__name__, e)
    # pair the prediction + measured step with the program's ledger
    # entry: the perf CLI's drift table and DeviceProfile.calibrated_from
    # both read these
    try:
        fp = compile_cache.fingerprint_or_none(
            fluid.default_main_program())
        led = obs.get_ledger()
        if pred is not None:
            led.note_prediction(fp, pred)
        led.note_measured(fp, dt / n_steps, kind="executor")
    except Exception:  # noqa: BLE001 — ledger is observability only
        pass
    if compile_cache.enabled():
        hits = obs.counter("compile_cache.disk_hit") - cc_hit0
        variant["compile_cache"] = {
            "disk_hit": hits,
            "disk_miss": obs.counter("compile_cache.disk_miss") - cc_miss0,
            "warm_start": bool(hits),
        }
    if os.environ.get("PADDLE_TPU_BENCH_ASYNC"):
        # pipelined dispatch lane: same program/feeds through
        # run_pipelined, reporting the staging/compute overlap
        runner = exe.run_pipelined(
            feeds=(feed for _ in range(n_steps)), fetch_list=fetch,
            return_numpy=False)
        t0 = time.time()
        for out in runner:
            pass
        float(np.asarray(out[0]))
        dt_async = time.time() - t0
        variant["async_step_ms"] = round(1000 * dt_async / n_steps, 2)
        variant["overlap_ratio"] = round(runner.overlap_ratio(), 3)
    return variant, cfg


def _measure_resnet(batch=128, image_size=224, n_steps=20):
    """ResNet-50 ImageNet-config training throughput, imgs/sec/chip
    (SURVEY §6's second headline)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.contrib.mixed_precision import decorate
    from paddle_tpu.models import resnet

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7
    vs = resnet.build_resnet_train(depth=50, class_num=1000,
                                   image_size=image_size)
    opt = decorate(fluid.optimizer.Momentum(0.1, 0.9), use_bf16=True)
    opt.minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(0)
    imgs = rng.standard_normal(
        (batch, 3, image_size, image_size), dtype=np.float32)
    labels = rng.integers(0, 1000, size=(batch, 1), dtype=np.int64)
    # stage the (38MB at b64/224) batch on device ONCE: the timed loop
    # measures training throughput, not the host->device
    # bandwidth (a real input pipeline double-buffers this transfer)
    import jax as _jax

    feed = {"image": _jax.device_put(imgs),
            "label": _jax.device_put(labels)}
    t0 = time.time()
    exe.run(feed=feed, fetch_list=[vs["loss"]])
    compile_s = time.time() - t0
    exe.run(feed=feed, fetch_list=[vs["loss"]])
    t0 = time.time()
    for _ in range(n_steps):
        out = exe.run(feed=feed, fetch_list=[vs["loss"]],
                      return_numpy=False)
    last = float(np.asarray(out[0]))
    dt = time.time() - t0
    imgs_per_sec = n_steps * batch / dt
    # ResNet-50 fwd ~= 3.86 GFLOPs/img at 224; train ~= 3x fwd. MFU here
    # is the CHIP ceiling for this workload, not framework overhead: a
    # minimal pure-jax ResNet-50 (bf16, NCHW and NHWC) measures the same
    # ~0.14 on v5e (bench_experiments/resnet_ablate.py, BENCHMARKS.md) —
    # ResNet's conv stack is HBM-bandwidth-bound at batch 128-256.
    train_flops_per_img = 3 * 3.86e9
    out = {
        "imgs_per_sec": round(imgs_per_sec, 1),
        "batch": batch,
        "image_size": image_size,
        "step_ms": round(1000 * dt / n_steps, 2),
        "compile_s": round(compile_s, 1),
        "loss_last": round(last, 4),
        "train_flops_per_img": train_flops_per_img,
    }
    dk = getattr(_jax.devices()[0], "device_kind", "")
    out["mfu"] = round(
        imgs_per_sec * train_flops_per_img / _peak_flops(dk), 4)
    return out


def _measure_ctr(batch=2048, rows=49152, epochs=2):
    """Wide&Deep CTR examples/sec through the FULL dataset trainer path
    (BASELINE config: lookup_table sparse embedding + train_from_dataset;
    the InMemoryDataset parse -> native ring -> jitted step pipeline)."""
    import tempfile

    import numpy as np

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

    # synthetic Criteo-shaped MultiSlot shards (26 sparse + 13 dense)
    tmpdir = tempfile.mkdtemp(prefix="bench_ctr_")
    rng = np.random.default_rng(0)
    w = np.random.default_rng(1).standard_normal(13)
    files = []
    per_shard = rows // 4
    for s in range(4):
        path = os.path.join(tmpdir, "part_%d.txt" % s)
        with open(path, "w") as f:
            for _ in range(per_shard):
                sparse = rng.integers(0, 100000, size=26)
                dense = rng.standard_normal(13)
                label = int(dense @ w > 0)
                # slot order mirrors set_use_var: dense, sparse, label
                f.write("13 %s 26 %s 1 %d\n" % (
                    " ".join("%.4f" % x for x in dense),
                    " ".join(map(str, sparse)), label))
        files.append(path)

    dataset = fluid.DatasetFactory().create_dataset("InMemoryDataset")
    dataset.set_batch_size(batch)
    dataset.set_thread(2)
    dataset.set_filelist(files)
    dataset.set_use_var([vs["dense"], vs["sparse"], vs["label"]])
    dataset.load_into_memory()

    dense_ev, sparse_ev, label_ev = wide_deep.synthetic_ctr_batch(batch)
    eval_feed = {"dense": dense_ev, "sparse": sparse_ev,
                 "ctr_label": label_ev}
    loss_first = float(exe.run(feed=eval_feed,
                               fetch_list=[vs["loss"]])[0])
    # warmup epoch compiles the step; timed epochs measure the pipeline
    exe.train_from_dataset(program=fluid.default_main_program(),
                           dataset=dataset)
    t0 = time.time()
    for _ in range(epochs):
        exe.train_from_dataset(program=fluid.default_main_program(),
                               dataset=dataset)
    dt = time.time() - t0
    loss_last = float(exe.run(feed=eval_feed,
                              fetch_list=[vs["loss"]])[0])
    dataset.release_memory()
    n_batches = rows // batch
    return {
        "examples_per_sec": round(epochs * n_batches * batch / dt, 1),
        "batch": batch,
        "rows": rows,
        "epochs_timed": epochs,
        "loss_first": round(loss_first, 4),
        "loss_last": round(loss_last, 4),
    }


def _measure_nmt_decode(batch=32, src_len=32, max_out_len=48, beam=4,
                        n_iters=8):
    """Transformer NMT beam-search decode throughput, generated
    tokens/sec (BASELINE config: beam_search ops). Runs the KV-cache
    incremental decoder (models/transformer_nmt.py) — one lax.scan,
    static beam."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import transformer_nmt as tnmt

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 7

    cfg = tnmt.NMTConfig(src_vocab=32000, tgt_vocab=32000, hidden=512,
                         heads=8, ffn=2048, enc_layers=4, dec_layers=4,
                         max_len=max(64, max_out_len), dropout=0.0)
    vs = tnmt.build_transformer_beam_decode(cfg, src_len, max_out_len,
                                            beam)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    rng = np.random.default_rng(0)
    import jax as _jax

    src = _jax.device_put(rng.integers(
        3, cfg.src_vocab, size=(batch, src_len)).astype("int64"))
    feed = {"src_ids": src}
    fetch = [vs["ids"], vs["scores"]]
    t0 = time.time()
    out = exe.run(feed=feed, fetch_list=fetch)
    compile_s = time.time() - t0
    scores0 = np.asarray(out[1])
    t0 = time.time()
    for _ in range(n_iters):
        out = exe.run(feed=feed, fetch_list=fetch, return_numpy=False)
    np.asarray(out[0])  # sync
    dt = time.time() - t0
    toks = n_iters * batch * max_out_len
    return {
        "tokens_per_sec": round(toks / dt, 1),
        "batch": batch,
        "src_len": src_len,
        "max_out_len": max_out_len,
        "beam_size": beam,
        "decode_ms_per_batch": round(1000 * dt / n_iters, 2),
        "compile_s": round(compile_s, 1),
        "scores_finite": bool(np.isfinite(scores0).all()),
    }


def _measure_serving(n_clients=8, n_requests=160):
    """Serving-engine throughput smoke (ISSUE 5): a tiny fc predictor
    behind the micro-batching ServingEngine, mixed-shape concurrent
    clients; reports requests/sec, latency p50/p99, and how much the
    batcher actually coalesced (gated by PADDLE_TPU_BENCH_SERVING=1)."""
    import tempfile
    import threading

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.inference import Predictor

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 9
    x = fluid.data(name="x", shape=[None, 32], dtype="float32")
    h = fluid.layers.fc(x, size=64, act="relu")
    out = fluid.layers.fc(h, size=8, act="softmax")
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    with tempfile.TemporaryDirectory() as d:
        fluid.io.save_inference_model(d, ["x"], [out], exe)
        pred = Predictor.from_model(d)
    engine = serving.ServingEngine(
        pred, buckets=[serving.BucketSpec(
            {"x": (32,)}, batch_sizes=(1, 2, 4, 8, 16))],
        max_batch_size=16, max_wait_ms=1.0, queue_capacity=256,
        name="bench")
    engine.warmup()
    rng = np.random.default_rng(0)
    shapes = (1, 2, 3, 4)
    feeds = [rng.standard_normal((r, 32)).astype("float32")
             for r in shapes]
    lat = []
    lat_lock = threading.Lock()
    per_client = max(1, n_requests // n_clients)

    def client(i):
        for k in range(per_client):
            fv = feeds[(i + k) % len(feeds)]
            t0 = time.monotonic()
            engine.predict({"x": fv})
            dt = time.monotonic() - t0
            with lat_lock:
                lat.append(dt)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.monotonic() - t0
    engine.stop(drain=True)
    lat.sort()
    stats = engine.stats()
    waste = obs.histogram("serving.padding_waste") or {}
    return {
        "clients": n_clients,
        "requests": len(lat),
        "requests_per_sec": round(len(lat) / dt, 1),
        "rows_per_sec": round(stats["rows"] / dt, 1),
        "p50_ms": round(1000 * lat[len(lat) // 2], 3),
        "p99_ms": round(
            1000 * lat[min(len(lat) - 1, int(len(lat) * 0.99))], 3),
        "batches": stats["batches"],
        "coalesced_batches": stats["coalesced"],
        "mean_rows_per_batch": round(
            stats["rows"] / max(1, stats["batches"]), 2),
        "padding_waste_mean": round(waste.get("mean", 0.0) or 0.0, 4),
    }


def _measure_serving_fleet(n_replicas=4, n_clients=8, n_requests=240):
    """Serving-fleet lane (ISSUE 7): the same predictor behind a
    health-aware ServingRouter over N per-device replicas, versus the
    raw engines driven round-robin — the spread between the two is the
    router's dispatch overhead, which must stay a thin slice (gated by
    PADDLE_TPU_BENCH_SERVING=1)."""
    import tempfile
    import threading

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 9
    x = fluid.data(name="x", shape=[None, 32], dtype="float32")
    h = fluid.layers.fc(x, size=64, act="relu")
    out = fluid.layers.fc(h, size=8, act="softmax")
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_bench_fleet_")
    fluid.io.save_inference_model(tmp, ["x"], [out], exe)
    router = serving.local_fleet(
        tmp, n_replicas=n_replicas, per_device=True,
        buckets=[serving.BucketSpec(
            {"x": (32,)}, batch_sizes=(1, 2, 4, 8, 16))],
        name="fleet-bench", max_batch_size=16, max_wait_ms=1.0,
        queue_capacity=256)
    engines = [router._live[rid].engine for rid in sorted(router._live)]
    rng = np.random.default_rng(0)
    feeds = [rng.standard_normal((r, 32)).astype("float32")
             for r in (1, 2, 3, 4)]
    per_client = max(1, n_requests // n_clients)

    def drive(predict):
        def client(i):
            for k in range(per_client):
                predict(i, k, {"x": feeds[(i + k) % len(feeds)]})

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(n_clients)]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return (n_clients * per_client) / (time.monotonic() - t0)

    # same warmed engines, same load, two dispatch paths
    direct_rps = drive(
        lambda i, k, f: engines[(i + k) % len(engines)].predict(f))
    router_rps = drive(lambda i, k, f: router.predict(f))
    stats = router.stats()
    router.stop(drain=True)
    overhead = (100.0 * (direct_rps - router_rps) / direct_rps
                if direct_rps else 0.0)
    return {
        "replicas": n_replicas,
        "clients": n_clients,
        "requests_per_path": n_clients * per_client,
        "router_requests_per_sec": round(router_rps, 1),
        "direct_requests_per_sec": round(direct_rps, 1),
        "router_overhead_pct": round(overhead, 2),
        "failovers": int(stats.get("failovers", 0)),
        "replicas_live": int(stats.get("replicas_live", 0)),
    }


def _measure_decode_serving(n_clients=8, requests_per_client=3,
                            max_new=16):
    """Decode-serving lane (ISSUE 9): a tiny trained GPT behind the
    continuous-batching DecodeEngine and the HTTP chunked ``:generate``
    endpoint, >= 8 concurrent mixed-length clients. Reports aggregate
    tokens/s and per-token + TTFT latency p50/p99, the peak
    slot-utilization gauge, the spread vs the full-batch-barrier
    baseline (same programs, admission only when every slot is free), a
    per-length bit-identity check against solo build_gpt_generate, and
    the warm-restart compile count (gated by PADDLE_TPU_BENCH_DECODE=1)."""
    import json as _json
    import threading
    import urllib.request

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu import serving
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import gpt

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 9
    cfg = gpt.gpt_tiny(vocab=97, max_len=64)
    vs = gpt.build_gpt_lm(cfg, 16)
    fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
    for _ in range(10):
        exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                fetch_list=[vs["loss"]])

    lens_cycle = (3, 6, 10, 14)
    rng = np.random.default_rng(0)
    prompts = {n: rng.integers(1, cfg.vocab, n).astype("int64")
               for n in lens_cycle}

    def make_engine(barrier=False):
        # deterministic program names per build: an engine constructed
        # after a process restart fingerprints identically, so the
        # compile-cache disk tier makes its warmup zero-compile
        unique_name.switch()
        return serving.DecodeEngine(
            cfg, fluid.global_scope(), slots=4, cache_len=48,
            prompt_buckets=(8, 16), queue_capacity=256,
            name="decode-bench", barrier=barrier)

    eng = make_engine()
    eng.warmup()
    reg = serving.ModelRegistry()
    reg.publish("gpt", eng)
    srv = serving.ServingServer(reg).start()

    # sample the live-slot gauge while the load runs (its end-state is
    # always 0.0 once everything retires)
    util_peak = [0.0]
    sampling = threading.Event()

    def sampler():
        while not sampling.is_set():
            g = obs.gauge("serving.decode.slot_utilization.decode-bench")
            if g is not None:
                util_peak[0] = max(util_peak[0], g)
            time.sleep(0.002)

    ttfts, gaps, errors = [], [], []
    lock = threading.Lock()
    streamed = {}

    def client(cid):
        for k in range(requests_per_client):
            plen = lens_cycle[(cid + k) % len(lens_cycle)]
            body = _json.dumps({
                "prompt": prompts[plen].tolist(),
                "max_new_tokens": max_new}).encode()
            req = urllib.request.Request(
                srv.url + "/v1/models/gpt:generate", data=body,
                headers={"Content-Type": "application/json"})
            t0 = time.monotonic()
            try:
                toks, times = [], []
                with urllib.request.urlopen(req, timeout=120) as resp:
                    for line in resp:
                        doc = _json.loads(line)
                        if "token" in doc:
                            toks.append(doc["token"])
                            times.append(time.monotonic())
                        elif doc.get("done") and doc.get(
                                "finish_reason") != "length":
                            errors.append((cid, k, doc))
                with lock:
                    ttfts.append(times[0] - t0)
                    gaps.extend(b - a for a, b in zip(times, times[1:]))
                    streamed.setdefault(plen, toks)
            except Exception as e:  # noqa: BLE001 — bank it, keep driving
                errors.append((cid, k, repr(e)))

    sampler_t = threading.Thread(target=sampler, daemon=True)
    sampler_t.start()
    threads = [threading.Thread(target=client, args=(c,))
               for c in range(n_clients)]
    t0 = time.monotonic()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.monotonic() - t0
    sampling.set()
    sampler_t.join(timeout=2)
    srv.stop(close_registry=False)
    if errors:
        raise RuntimeError("decode clients failed: %r" % errors[:3])

    # bit-identity: every streamed sequence must match a SOLO
    # build_gpt_generate greedy run of its prompt, token for token
    for plen, toks in sorted(streamed.items()):
        g_prog, g_st = fluid.Program(), fluid.Program()
        with fluid.program_guard(g_prog, g_st):
            gen = gpt.build_gpt_generate(cfg, plen, max_new, mode="greedy")
        want = np.asarray(exe.run(
            g_prog, feed={"gpt_prompt": prompts[plen].reshape(1, -1)},
            fetch_list=[gen["ids"]])[0])[0, plen - 1:]
        if list(want) != toks:
            raise RuntimeError(
                "decode stream diverged from solo generate at prompt "
                "len %d" % plen)

    def pct(sorted_vals, q):
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1, int(len(sorted_vals) * q))
        return round(1000 * sorted_vals[i], 3)

    ttfts.sort()
    gaps.sort()
    n_requests = n_clients * requests_per_client
    stats = eng.stats()

    # ablation: identical programs, but admission only when EVERY slot
    # is free — the classic full-batch generation schedule
    def drive_direct(engine):
        # prime first-dispatch costs (write-jit trace, executable
        # first-run) out of the timed window so the two schedules
        # compare scheduling, not warmup order
        for plen in lens_cycle:
            engine.generate(prompts[plen], max_new=2, timeout=120)
        done = []

        def d_client(cid):
            for k in range(requests_per_client):
                plen = lens_cycle[(cid + k) % len(lens_cycle)]
                out = engine.generate(prompts[plen], max_new=max_new,
                                      timeout=120)
                with lock:
                    done.append(len(out))

        ths = [threading.Thread(target=d_client, args=(c,))
               for c in range(n_clients)]
        w0 = time.monotonic()
        for t in ths:
            t.start()
        for t in ths:
            t.join()
        return sum(done) / (time.monotonic() - w0)

    continuous_tps = drive_direct(eng)
    eng.stop(drain=True)
    barrier_eng = make_engine(barrier=True)
    barrier_eng.warmup(check_hbm=False)
    barrier_tps = drive_direct(barrier_eng)
    barrier_eng.stop(drain=True)

    # warm restart: a rebuilt engine resolves every program through the
    # compile cache — with the disk tier on, zero XLA compiles
    restart = make_engine()
    warm2 = restart.warmup(check_hbm=False)
    restart.stop(drain=True)
    sources = {}
    for r in warm2:
        sources[r["source"]] = sources.get(r["source"], 0) + 1
    reg.close()

    return {
        "clients": n_clients,
        "requests": n_requests,
        "tokens_total": stats["tokens"],
        "tokens_per_sec": round(n_requests * max_new / wall, 1),
        "ttft_ms_p50": pct(ttfts, 0.50),
        "ttft_ms_p99": pct(ttfts, 0.99),
        "per_token_ms_p50": pct(gaps, 0.50),
        "per_token_ms_p99": pct(gaps, 0.99),
        "slot_utilization_peak": round(util_peak[0], 3),
        "prefills": stats["prefills"],
        "steps": stats["steps"],
        "continuous_tokens_per_sec": round(continuous_tps, 1),
        "barrier_tokens_per_sec": round(barrier_tps, 1),
        "continuous_vs_barrier_speedup": round(
            continuous_tps / barrier_tps, 3) if barrier_tps else None,
        "bit_identical_to_solo_generate": True,
        "warm_restart_sources": sources,
    }


def _measure_disagg_serving(latency_clients=6, long_clients=2,
                            requests_per_client=3, max_new=16):
    """Disaggregated-serving lane (ISSUE 12): the same mixed-tenant
    load against a colocated DecodeEngine (prefill and step share one
    dispatch loop) and a 2-prefill + 2-decode disagg fleet over the
    int8 KV wire — recording the latency tenant's per-token p50/p99
    for both (the disagg legs must hold that tenant's per-token SLO
    with long bulk prompts in the mix AND through a replica kill),
    aggregate tokens/s, the int8-resident slot economics at an equal
    HBM budget, and a mid-run decode-replica kill that every live
    stream must survive via re-prefill migration with zero failures
    (gated by PADDLE_TPU_BENCH_DISAGG=1)."""
    import threading

    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import gpt
    from paddle_tpu.serving.decode import kv_slot_bytes
    from paddle_tpu.serving.disagg import (
        TenantSpec, TenantTable, disagg_fleet, handoff_compression,
    )

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    fluid.default_startup_program().random_seed = 9
    cfg = gpt.gpt_tiny(vocab=97, max_len=128)
    vs = gpt.build_gpt_lm(cfg, 16)
    fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
    for _ in range(10):
        exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                fetch_list=[vs["loss"]])

    cache_len, buckets = 96, (8, 96)
    long_len, long_new = 90, 6   # 90 + 6 - 1 <= 96: bucket-96 prefills
    latency_lens = (3, 5, 6)
    rng = np.random.default_rng(0)
    prompts = {n: rng.integers(1, cfg.vocab, n).astype("int64")
               for n in latency_lens + (long_len,)}

    def drive(submit, chaos=None, expect_tokens=1):
        """Run the mixed-tenant load against one `submit` callable;
        `chaos` (if given) fires once ~50% of the expected tokens have
        streamed. Returns (per-tenant inter-token gaps, errors, wall,
        tokens)."""
        gaps = {"latency": [], "bulk": []}
        errors, lock = [], threading.Lock()
        done_tokens = [0]

        def client(tenant, plen, n_new, rounds):
            for _ in range(rounds):
                try:
                    h = submit(prompts[plen], n_new, tenant)
                    times = [time.monotonic()]
                    n = 0
                    for _tok in h.tokens(timeout=180):
                        times.append(time.monotonic())
                        n += 1
                    if n != n_new:
                        raise RuntimeError(
                            "stream delivered %d/%d tokens" % (n, n_new))
                    with lock:
                        gaps[tenant].extend(
                            b - a for a, b in zip(times[1:], times[2:]))
                        done_tokens[0] += n
                except Exception as e:  # noqa: BLE001 — bank it, keep driving
                    errors.append((tenant, plen, repr(e)))

        threads = [threading.Thread(
            target=client,
            args=("latency", latency_lens[c % len(latency_lens)],
                  max_new, requests_per_client))
            for c in range(latency_clients)]
        threads += [threading.Thread(
            target=client, args=("bulk", long_len, long_new,
                                 requests_per_client))
            for _ in range(long_clients)]
        stop_watch = threading.Event()

        def watcher():
            while not stop_watch.wait(0.01):
                if done_tokens[0] >= expect_tokens // 2:
                    chaos()
                    return

        w = (threading.Thread(target=watcher, daemon=True)
             if chaos else None)
        t0 = time.monotonic()
        for t in threads:
            t.start()
        if w:
            w.start()
        for t in threads:
            t.join()
        wall = time.monotonic() - t0
        stop_watch.set()
        if w:
            w.join(timeout=1)
        return gaps, errors, wall, done_tokens[0]

    expect = (latency_clients * requests_per_client * max_new
              + long_clients * requests_per_client * long_new)

    def pct(vals, q):
        vals = sorted(vals)
        if not vals:
            return None
        return round(
            1000 * vals[min(len(vals) - 1, int(len(vals) * q))], 3)

    # -- leg 1: colocated baseline (one engine, prefill stalls steps) --
    unique_name.switch()
    base = serving.DecodeEngine(
        cfg, fluid.global_scope(), slots=4, cache_len=cache_len,
        prompt_buckets=buckets, queue_capacity=256, name="disagg-base")
    base.warmup(check_hbm=False)
    base_gaps, base_errors, base_wall, base_tokens = drive(
        lambda p, n, t: base.submit(p, max_new=n, tenant=t),
        expect_tokens=expect)
    base.stop(drain=True)
    if base_errors:
        raise RuntimeError(
            "colocated baseline failed: %r" % base_errors[:3])

    # -- leg 2: the disagg fleet, steady state ------------------------
    unique_name.switch()
    tenants = TenantTable(specs=[
        TenantSpec("latency", priority="interactive",
                   per_token_slo_ms=250.0),
        TenantSpec("bulk", priority="batch")])
    router = disagg_fleet(
        cfg, fluid.global_scope(), n_prefill=2, n_decode=2, slots=2,
        cache_len=cache_len, prompt_buckets=buckets, kv_dtype="fp32",
        wire_dtype="int8", tenants=tenants, name="disagg-bench",
        queue_capacity=256, request_timeout_s=180.0)
    router.warmup(check_hbm=False)
    # clean mixed-tenant drive first: the latency numbers must not mix
    # steady-state inter-token gaps with migration stalls from the kill.
    # This leg runs traced (ISSUE 14) so the lane banks the per-phase
    # queue/prefill/handoff/adopt/decode split, not just end-to-end.
    from paddle_tpu import observability as obs

    trace_root = tempfile.mkdtemp(prefix="paddle_tpu_disagg_trace_")
    prev_trace = os.environ.get(obs.TRACE_DIR_ENV)
    os.environ[obs.TRACE_DIR_ENV] = trace_root
    try:
        dis_gaps, dis_errors, dis_wall, dis_tokens = drive(
            lambda p, n, t: router.submit(
                p, max_new=n, tenant=t,
                trace_ctx=obs.TraceContext.new()),
            expect_tokens=expect)
    finally:
        if prev_trace is None:
            os.environ.pop(obs.TRACE_DIR_ENV, None)
        else:
            os.environ[obs.TRACE_DIR_ENV] = prev_trace
    if dis_errors:
        raise RuntimeError("disagg clean leg failed: %r" % dis_errors[:3])
    phase_ms = {
        phase: {"count": st_["count"],
                "mean_ms": round(st_["mean_s"] * 1e3, 3),
                "max_ms": round(st_["max_s"] * 1e3, 3)}
        for phase, st_ in obs.phase_breakdown(
            obs.read_spans(trace_root)).items()}

    # -- leg 3: same fleet, mid-run decode-replica kill ----------------
    # a long-lived canary guarantees the kill catches a live stream
    canary = router.submit(prompts[5], max_new=80, tenant="latency")
    deadline = time.monotonic() + 60
    while len(canary.so_far()) < 3 and time.monotonic() < deadline:
        time.sleep(0.002)
    with router._lock:
        victim = next(r for r, s in router._sessions.items()
                      if canary in s)
    killed = []

    def chaos():
        router.kill_replica(victim)
        killed.append(victim)

    chaos_gaps, dis_errors, _chaos_wall, _chaos_tokens = drive(
        lambda p, n, t: router.submit(p, max_new=n, tenant=t),
        chaos=chaos, expect_tokens=expect)
    canary_toks = canary.result(180.0)
    if not killed:
        chaos()  # load outran the watcher; still record a clean kill
    st = router.stats()
    router.stop(drain=True, timeout=30.0)
    if dis_errors:
        raise RuntimeError("disagg fleet failed: %r" % dis_errors[:3])
    if len(canary_toks) != 80:
        raise RuntimeError(
            "canary stream lost tokens across the kill: %d/80"
            % len(canary_toks))
    if st["failed_streams"]:
        raise RuntimeError(
            "%d streams failed through the chaos leg"
            % st["failed_streams"])

    # -- slot economics: int8 residency at an equal HBM budget ---------
    fp32_slot = kv_slot_bytes(cfg, cache_len, "fp32")
    int8_slot = kv_slot_bytes(cfg, cache_len, "int8")
    budget = 4 * fp32_slot

    return {
        "clients": latency_clients + long_clients,
        "long_prompt_len": long_len,
        "baseline_tokens_per_sec": round(base_tokens / base_wall, 1),
        "disagg_tokens_per_sec": round(dis_tokens / dis_wall, 1),
        "baseline_latency_per_token_ms_p99": pct(
            base_gaps["latency"], 0.99),
        "disagg_latency_per_token_ms_p99": pct(
            dis_gaps["latency"], 0.99),
        "baseline_latency_per_token_ms_p50": pct(
            base_gaps["latency"], 0.50),
        "disagg_latency_per_token_ms_p50": pct(
            dis_gaps["latency"], 0.50),
        "chaos_latency_per_token_ms_p99": pct(
            chaos_gaps["latency"], 0.99),
        "killed_decode_replica": killed[0] if killed else None,
        "phase_latency_ms": phase_ms,
        "migrations": int(st["migrations"]),
        "failed_streams": int(st["failed_streams"]),
        "replica_dead": int(st["replica_dead"]),
        "handoff_compression_int8": round(
            handoff_compression(cfg.num_layers, cache_len, cfg.hidden,
                                "int8"), 3),
        "slot_bytes_fp32": fp32_slot,
        "slot_bytes_int8": int8_slot,
        "slots_at_equal_budget_fp32": int(budget // fp32_slot),
        "slots_at_equal_budget_int8": int(budget // int8_slot),
    }


def _measure_spec_serving(clients=12, max_new=12):
    """Speculative-decoding + prefix-cache KV reuse lane (ISSUE 19):
    shared-prefix traffic (one 24-token system prompt, unique 4..8
    token tails) against a plain DecodeEngine vs one with a PrefixPool
    + draft model attached — recording tokens/s both ways, the draft
    acceptance rate, and the redundant-prefill FLOPs ledger (the lane
    FAILS unless >50%% of prefill rows are adopted instead of computed
    and every reuse-path token stream is bit-identical to the plain
    engine's) — plus a session-tiering leg where hibernate/resume
    serves more concurrent conversations than the engine has slots
    (gated by PADDLE_TPU_BENCH_SPEC=1)."""
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.fluid.executor import Scope
    from paddle_tpu.models import gpt

    def train(cfg, seed, steps=30):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        fluid.default_startup_program().random_seed = seed
        vs = gpt.build_gpt_lm(cfg, 16)
        fluid.optimizer.Adam(5e-3).minimize(vs["loss"])
        scope = Scope()
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program(), scope=scope)
        ids, labels = gpt.synthetic_lm_batch(cfg, 16, 16)
        for _ in range(steps):
            exe.run(feed={"gpt_ids": ids, "gpt_labels": labels},
                    fetch_list=[vs["loss"]], scope=scope)
        return scope

    cfg = gpt.gpt_tiny(vocab=97, max_len=128)
    tscope = train(cfg, seed=9)
    # the draft trains on the SAME synthetic task (that alignment, not
    # size, is what buys acceptance): 1 layer, half the width
    dcfg = gpt.GPTConfig(vocab=97, hidden=16, num_layers=1, heads=2,
                         ffn=32, max_len=128, dropout=0.0)
    dscope = train(dcfg, seed=13)

    cache_len, buckets = 64, (8, 32)
    shared_len = 24
    rng = np.random.default_rng(0)
    shared = rng.integers(1, cfg.vocab, shared_len).astype("int64")
    prompts = [np.concatenate([shared, rng.integers(
        1, cfg.vocab, 4 + (c % 5)).astype("int64")])
        for c in range(clients)]

    def drive(eng):
        handles = [eng.submit(p, max_new=max_new) for p in prompts]
        t0 = time.monotonic()
        toks = [h.result(180.0) for h in handles]
        return toks, time.monotonic() - t0

    # -- leg 1: plain engine (every prompt cold-prefills in full) ------
    base = serving.DecodeEngine(
        cfg, tscope, slots=2, cache_len=cache_len,
        prompt_buckets=buckets, queue_capacity=256, name="spec-base")
    base.warmup(check_hbm=False)
    drive(base)  # warm the dispatch path once
    ref_toks, base_wall = drive(base)
    base_rows = base.stats()["prefill_rows_computed"]
    base.stop(drain=True)

    # -- leg 2: prefix pool + draft (k=4) ------------------------------
    unique_name.switch()
    reuse = serving.DecodeEngine(
        cfg, tscope, slots=2, cache_len=cache_len,
        prompt_buckets=buckets, queue_capacity=256, name="spec-reuse",
        draft=serving.DraftModel(dcfg, dscope, k=4, name="spec-draft"),
        prefix_pool=serving.PrefixPool(prefix_lens=(shared_len,),
                                       name="spec-bench"))
    reuse.warmup(check_hbm=False)
    drive(reuse)  # seed the pool + warm; correctness scored on run 2
    got_toks, reuse_wall = drive(reuse)
    if got_toks != ref_toks:
        raise RuntimeError(
            "reuse-path tokens diverged from the plain engine "
            "(speculation/prefix adoption must be bit-exact)")
    info = reuse.reuse_info()
    st = reuse.stats()
    reuse.stop(drain=True)
    saved_pct = info["prefill_rows_saved_pct"]
    if saved_pct is None or saved_pct <= 50.0:
        raise RuntimeError(
            "prefix reuse saved only %r%% of prefill rows (need >50%%)"
            % (saved_pct,))

    # -- leg 3: session tiering — conversations > slots ----------------
    unique_name.switch()
    n_sessions, slots = 6, 2
    # fp32 wire: the lane gates on bit-exact resume-vs-replay (int8
    # wire is the capacity choice; its parity is argmax-stable, not
    # bitwise, on an fp32-resident engine)
    tier = serving.SessionTier(wire_dtype="fp32", name="spec-bench")
    sess = serving.DecodeEngine(
        cfg, tscope, slots=slots, cache_len=cache_len,
        prompt_buckets=buckets, queue_capacity=256, name="spec-sess",
        session_tier=tier)
    sess.warmup(check_hbm=False)
    turn1 = {c: prompts[c][:6 + (c % 3)] for c in range(n_sessions)}
    turn2 = {c: rng.integers(1, cfg.vocab, 4).astype("int64")
             for c in range(n_sessions)}
    t0 = time.monotonic()
    first = {c: sess.submit(turn1[c], max_new=6,
                            session="conv%d" % c).result(180.0)
             for c in range(n_sessions)}
    second = {c: sess.submit(turn2[c], max_new=6,
                             session="conv%d" % c).result(180.0)
              for c in range(n_sessions)}
    sess_wall = time.monotonic() - t0
    sess_st = sess.stats()
    tier_st = tier.stats()
    sess.stop(drain=True)
    if sess_st["resumed"] != n_sessions:
        raise RuntimeError(
            "only %d/%d sessions resumed from the tier"
            % (sess_st["resumed"], n_sessions))
    # tiering-off comparison: turn 2 replays the full transcript cold
    unique_name.switch()
    cold = serving.DecodeEngine(
        cfg, tscope, slots=slots, cache_len=cache_len,
        prompt_buckets=buckets, queue_capacity=256, name="spec-cold")
    cold.warmup(check_hbm=False)
    for c in range(n_sessions):
        transcript = np.concatenate(
            [turn1[c], np.asarray(first[c], np.int64), turn2[c]])
        toks = cold.generate(transcript, max_new=6, timeout=180.0)
        if toks != second[c]:
            raise RuntimeError(
                "session resume diverged from the cold transcript "
                "replay (delta adoption must be bit-exact)")
    cold_rows = cold.stats()["prefill_rows_computed"]
    cold.stop(drain=True)

    return {
        "clients": clients,
        "shared_prefix_len": shared_len,
        "baseline_tokens_per_sec": round(
            clients * max_new / base_wall, 1),
        "reuse_tokens_per_sec": round(
            clients * max_new / reuse_wall, 1),
        "spec_accept_rate": round(st["spec_accept_rate"], 4),
        "spec_rounds": int(st["spec_rounds"]),
        "spec_fallback_steps": int(st["spec_fallback_steps"]),
        "prefix_full_hits": int(st["prefix_full_hits"]),
        "delta_prefills": int(st["delta_prefills"]),
        "prefill_rows_computed_plain": int(base_rows),
        "prefill_rows_computed_reuse": int(
            info["prefill_rows_computed"]),
        "prefill_rows_saved": int(info["prefill_rows_saved"]),
        "prefill_flops_saved_pct": round(saved_pct, 1),
        "bit_exact": True,
        "sessions": n_sessions,
        "session_slots": slots,
        "sessions_per_chip_tiered": n_sessions,
        "session_resumes": int(sess_st["resumed"]),
        "session_hibernates": int(sess_st["hibernated"]),
        "session_rows_computed_tiered": int(
            sess_st["prefill_rows_computed"]),
        "session_rows_computed_untiered": int(cold_rows),
        "session_wall_s": round(sess_wall, 3),
        "tier_bytes": int(tier_st["bytes"]),
        "tier_wire_dtype": tier_st["wire_dtype"],
    }


def _measure_retrieval(vocab=20000, dim=64, n_queries=256, k=10,
                       iters=5):
    """Embedding & retrieval lane (ISSUE 20): an ep-sharded embedding
    table over every local device — (1) an N-way dryrun parity gate
    proving the sharded batched-gather lookup BIT-IDENTICAL to the
    single-device ``table[ids]`` and the chunked brute-force top-k
    exact (recall@k == 1.0) vs the full score matrix, (2) lookup ex/s
    and top-k queries/s with predicted-vs-measured MFU on the scoring
    matmul, and (3) the distributed-linalg leg: blocked matmul and
    power iteration priced in fraction-of-roofline terms (gated by
    PADDLE_TPU_BENCH_RETRIEVAL=1)."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    from paddle_tpu import retrieval
    from paddle_tpu.analysis import costs
    from paddle_tpu.fluid.executor import _device_kind

    n_dev = len(jax.devices())
    mesh = retrieval.ep_mesh(n_dev)
    tbl = retrieval.ShardedEmbeddingTable(
        vocab, dim, mesh=mesh, seed=7, name="bench_items")
    host = tbl.host_rows()
    rng = np.random.default_rng(0)

    # -- parity gate: the lane FAILS unless the distributed paths match
    # the single-device reference
    ids = rng.integers(0, vocab, size=4096).astype(np.int32)
    emb = tbl.lookup(ids)
    if not (emb.view(np.uint8) == host[ids].view(np.uint8)).all():
        raise RuntimeError(
            "ep-sharded lookup diverged BITWISE from the single-device "
            "gather (%d-way mesh)" % n_dev)
    q = rng.normal(size=(n_queries, dim)).astype(np.float32)
    topk_fn = retrieval.build_sharded_topk(
        mesh, tbl.rows_per_shard, dim, vocab, k)
    scores, got_ids = (np.asarray(a) for a in topk_fn(
        tbl.device_table, jnp.asarray(q)))
    full = q @ host.T
    ref_ids = np.argsort(-full, axis=1)[:, :k]
    recall = float(np.mean([
        len(set(got_ids[i]) & set(ref_ids[i])) / k
        for i in range(n_queries)]))
    if recall < 1.0:
        raise RuntimeError(
            "sharded top-k recall@%d = %.4f vs exact brute force "
            "(want 1.0)" % (k, recall))

    # -- device profile: real roofline constants when the device table
    # knows the chip; on CPU CI, calibrate an alpha-beta model of the
    # same search program from two sub-batch probes — a fixed
    # per-dispatch latency c0 plus an effective peak (memory traffic
    # folded in, cost_lane.sh-style) — then predict the full batch
    # from it. A single small probe would fold the dispatch overhead
    # into the peak and systematically under-predict the full batch.
    def _best_of(fn, *args):
        best = float("inf")
        for _ in range(iters):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            best = min(best, time.perf_counter() - t0)
        return best

    search_flops = retrieval.matmul_flops(
        n_queries, tbl.padded_vocab, dim)
    prof = costs.device_profile(_device_kind())
    calibrated = False
    dispatch_s = 0.0
    if prof is None or not prof.peak_flops:
        probes = []
        for frac in (8, 2):
            q_cal = q[: max(1, n_queries // frac)]
            qc = jnp.asarray(q_cal)
            topk_fn(tbl.device_table, qc)  # compile
            probes.append((
                retrieval.matmul_flops(
                    q_cal.shape[0], tbl.padded_vocab, dim),
                _best_of(topk_fn, tbl.device_table, qc)))
        (f1, t1), (f2, t2) = probes
        if t2 > t1:
            peak_eff = (f2 - f1) / (t2 - t1)
            dispatch_s = max(0.0, t1 - f1 / peak_eff)
        else:  # timer noise swamped the probe gap: single-point model
            peak_eff = f2 / t2
        os.environ[costs.PEAK_FLOPS_ENV] = repr(peak_eff / n_dev)
        os.environ[costs.HBM_BW_ENV] = "1e18"  # folded into the peak
        prof = costs.device_profile(_device_kind())
        calibrated = True
    # analytic roofline prediction for one full-batch search dispatch:
    # each device scores its vocab shard (flops/n_dev) and streams its
    # table block once; the calibrated dispatch latency rides on top
    flops_per_dev = search_flops / n_dev
    bytes_per_dev = (tbl.resident_bytes(per_shard=True)
                     + q.nbytes + n_queries * k * 8)
    t_pred = dispatch_s + max(
        flops_per_dev / prof.peak_flops,
        bytes_per_dev / prof.hbm_bw if prof.hbm_bw else 0.0)
    predicted_mfu = flops_per_dev / (t_pred * prof.peak_flops)

    # -- throughput: lookup ex/s and search queries/s ------------------
    tbl.lookup(ids)  # warm
    lookup_wall = _best_of(lambda i: jnp.asarray(tbl.lookup(i)), ids)
    qj = jnp.asarray(q)
    jax.block_until_ready(topk_fn(tbl.device_table, qj))  # warm
    search_wall = _best_of(topk_fn, tbl.device_table, qj)
    measured_mfu = retrieval.fraction_of_roofline(
        search_flops, search_wall, prof, n_devices=n_dev)
    mfu_err_pct = (
        round(100.0 * (predicted_mfu - measured_mfu) / measured_mfu, 1)
        if measured_mfu else None)

    # -- linalg leg: blocked matmul + power iteration ------------------
    m = n = kk = 512
    a = rng.normal(size=(m, kk)).astype(np.float32)
    b = rng.normal(size=(kk, n)).astype(np.float32)
    c = retrieval.blocked_matmul(a, b, mesh=mesh)
    if not np.allclose(c, a @ b, rtol=2e-4, atol=2e-4):
        raise RuntimeError("blocked matmul diverged from np reference")
    mm_wall = _best_of(
        lambda: retrieval.blocked_matmul(a, b, mesh=mesh))
    mm_roofline = retrieval.fraction_of_roofline(
        retrieval.matmul_flops(m, n, kk), mm_wall, prof, n_devices=n_dev)
    # PSD operand: the dominant eigenpair is well-separated, so 60
    # matvecs converge tightly (a symmetric-indefinite seed can have
    # |λ1| ≈ |λ2| and stall — that's spectrum, not code)
    g = rng.normal(size=(256, 256)).astype(np.float32)
    psd = (g @ g.T) / 256.0
    t0 = time.perf_counter()
    eig, vec, residual = retrieval.power_iteration(psd, iters=60,
                                                   mesh=mesh)
    pi_wall = time.perf_counter() - t0
    ref_eig = float(np.linalg.eigvalsh(psd)[-1])
    if abs(eig - ref_eig) > 1e-2 * abs(ref_eig):
        raise RuntimeError(
            "power iteration eig %.6g vs reference %.6g" % (eig, ref_eig))
    pi_roofline = retrieval.fraction_of_roofline(
        61 * retrieval.matmul_flops(256, 1, 256), pi_wall, prof,
        n_devices=n_dev)

    return {
        "ep": n_dev,
        "vocab": vocab,
        "dim": dim,
        "k": k,
        "lookup_bit_identical": True,
        "recall_at_k": recall,
        "lookup_ex_per_sec": round(ids.size / lookup_wall, 1),
        "search_queries_per_sec": round(n_queries / search_wall, 1),
        "search_wall_ms": round(1000 * search_wall, 3),
        "table_resident_bytes": tbl.resident_bytes(),
        "mfu_calibrated_peak": calibrated,
        "predicted_mfu": round(predicted_mfu, 4),
        "measured_mfu": (round(measured_mfu, 4)
                         if measured_mfu is not None else None),
        "mfu_model_err_pct": mfu_err_pct,
        "blocked_matmul_roofline": (round(mm_roofline, 4)
                                    if mm_roofline is not None else None),
        "blocked_matmul_gflops": round(
            retrieval.matmul_flops(m, n, kk) / mm_wall / 1e9, 2),
        "power_iteration_roofline": (
            round(pi_roofline, 6) if pi_roofline is not None else None),
        "power_iteration_residual": round(residual, 6),
        "power_iteration_eig_rel_err": round(
            abs(eig - ref_eig) / abs(ref_eig), 6),
    }


def _measure_comms(steps=10, batch=64, hidden=256, n_layers=3):
    """Gradient-communication lane (ISSUE 10): the same dp training step
    three ways — GSPMD fp32 baseline, explicit bucketed comms fp32, and
    block-scaled int8 with error feedback — recording loss parity, the
    deterministic wire accounting (compression/overlap ratios, bytes),
    and measured step seconds (gated by PADDLE_TPU_BENCH_COMMS=1)."""
    import numpy as np

    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.fluid import executor as executor_mod
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.parallel import fleet as fleet_mod
    from paddle_tpu.parallel.fleet import DistributedStrategy

    if len(jax.devices()) < 2:
        return {"skipped": "needs >= 2 devices for a dp group"}

    rng = np.random.default_rng(7)
    x = rng.standard_normal((batch, hidden)).astype("float32")
    y = (x @ rng.standard_normal((hidden, 1)) / hidden).astype("float32")

    def run_variant(mutate):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        executor_mod._scope_stack[:] = [executor_mod.Scope()]
        obs.reset()
        fluid.default_startup_program().random_seed = 17
        fluid.default_main_program().random_seed = 17
        xv = fluid.data("bx", shape=[None, hidden], dtype="float32")
        yv = fluid.data("by", shape=[None, 1], dtype="float32")
        h = xv
        for _ in range(n_layers):
            h = fluid.layers.fc(h, hidden, act="tanh")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square_error_cost(pred, yv))
        strategy = DistributedStrategy()
        mutate(strategy)
        fl = fleet_mod.Fleet().init()
        opt = fl.distributed_optimizer(
            fluid.optimizer.SGD(0.05), strategy=strategy)
        opt.minimize(loss)
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        feed = {"bx": x, "by": y}
        losses = []
        out = exe.run(fl.main_program, feed=feed, fetch_list=[loss])
        losses.append(float(np.asarray(out[0])))  # compile step
        t0 = time.time()
        for _ in range(steps - 1):
            out = exe.run(fl.main_program, feed=feed, fetch_list=[loss])
            losses.append(float(np.asarray(out[0])))
        det = {
            "losses": [round(v, 6) for v in losses],
            "step_seconds": round(
                (time.time() - t0) / max(steps - 1, 1), 6),
        }
        for key in ("comm.compression_ratio", "comm.overlap_ratio"):
            v = obs.gauge(key)
            if v is not None:
                det[key.split(".", 1)[1]] = round(float(v), 4)
        for key in ("comm.bytes_sent", "comm.bytes_saved"):
            v = obs.counter(key)
            if v:
                det[key.split(".", 1)[1]] = int(v)
        return det

    def comms(s, quantize):
        s.grad_sync_mode = "comms"
        s.grad_quantize = quantize
        # small target so the tiny model still splits into several
        # buckets and the overlap accounting is exercised
        s.grad_bucket_bytes = 256 << 10

    prev_tel = os.environ.get("PADDLE_TPU_TELEMETRY")
    os.environ["PADDLE_TPU_TELEMETRY"] = "on"
    try:
        out = {
            "n_devices": len(jax.devices()),
            "gspmd_fp32": run_variant(lambda s: None),
            "comms_fp32": run_variant(lambda s: comms(s, False)),
            "comms_int8": run_variant(lambda s: comms(s, True)),
        }
    finally:
        if prev_tel is None:
            os.environ.pop("PADDLE_TPU_TELEMETRY", None)
        else:
            os.environ["PADDLE_TPU_TELEMETRY"] = prev_tel
    out["loss_gap_int8_vs_fp32"] = round(
        abs(out["comms_int8"]["losses"][-1]
            - out["gspmd_fp32"]["losses"][-1]), 6)
    return out


def _measure_planner(steps=8, batch=16, seq=64):
    """Auto-tuned lane (ISSUE 11): run the auto-parallelism planner's
    search on the bench BERT-tiny pretrain step for the actual device
    count, then run its top fleet-runnable pick end-to-end against the
    dp-gspmd baseline, banking the ranked table and the chosen config
    (gated by PADDLE_TPU_BENCH_PLAN=1)."""
    import numpy as np

    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu.analysis.cli import _bench_bert_program
    from paddle_tpu.analysis.costs import device_profile
    from paddle_tpu.fluid import executor as executor_mod
    from paddle_tpu.fluid import framework, unique_name
    from paddle_tpu.models import bert
    from paddle_tpu.parallel import fleet as fleet_mod
    from paddle_tpu.parallel.fleet import DistributedStrategy
    from paddle_tpu.planner import plan_search

    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"skipped": "needs >= 2 devices to plan over"}
    device_kind = getattr(jax.devices()[0], "device_kind", "cpu")
    on_accel = jax.default_backend() not in ("cpu",)
    profile = device_profile(device_kind) or device_profile("v5e")

    # -- search -----------------------------------------------------------
    prog, feed_names, fetch_names = _bench_bert_program(batch=batch,
                                                        seq=seq)
    result = plan_search(
        prog, n_dev, profile=profile, feed_names=feed_names,
        fetch_names=fetch_names, default_dim=batch,
        # bf16 AMP is a TPU lever; the CPU lane measures what it runs
        amp_choices=(False, True) if on_accel else (False,))
    out = {
        "n_devices": n_dev,
        "device_profile": profile.name if profile else None,
        "n_candidates": (len(result.ranked) + len(result.rejected)
                         + len(result.unpriced)),
        "n_rejected": len(result.rejected),
        "ranked": [
            {"plan": p.plan.name,
             "predicted_step_seconds": p.predicted_step_seconds,
             "fleet_runnable": p.plan.fleet_runnable()}
            for p in result.ranked[:5]],
    }

    # -- run a config end-to-end -----------------------------------------
    def run_config(strategy):
        framework.switch_main_program(framework.Program())
        framework.switch_startup_program(framework.Program())
        unique_name.switch()
        executor_mod._scope_stack[:] = [executor_mod.Scope()]
        fluid.default_startup_program().random_seed = 17
        fluid.default_main_program().random_seed = 17
        cfg = bert.bert_tiny(seq=seq)
        vs = bert.build_bert_pretrain(cfg, seq)
        if strategy.tensor_parallel_degree > 1:
            strategy.tensor_parallel_rules = bert.tp_rules()
        fl = fleet_mod.Fleet().init()
        opt = fl.distributed_optimizer(
            fluid.optimizer.Adam(learning_rate=1e-4), strategy=strategy)
        opt.minimize(vs["loss"])
        exe = fluid.Executor()
        exe.run(fluid.default_startup_program())
        ids, labels = bert.synthetic_batch(cfg, batch, seq)
        feed = {"input_ids": ids, "mlm_labels": labels}
        losses = []
        res = exe.run(fl.main_program, feed=feed,
                      fetch_list=[vs["loss"]])
        losses.append(float(np.asarray(res[0])))  # compile step
        t0 = time.time()
        for _ in range(steps - 1):
            res = exe.run(fl.main_program, feed=feed,
                          fetch_list=[vs["loss"]])
            losses.append(float(np.asarray(res[0])))
        return {
            "losses": [round(v, 6) for v in losses],
            "step_seconds": round(
                (time.time() - t0) / max(steps - 1, 1), 6),
        }

    out["baseline"] = run_config(DistributedStrategy())
    out["baseline"]["plan"] = "dp%d (gspmd baseline)" % n_dev

    # walk the ranking until a plan runs; record anything that failed
    fallbacks = []
    chosen = None
    for priced in result.ranked:
        if not priced.plan.fleet_runnable():
            continue
        try:
            strategy = DistributedStrategy.from_plan(priced.plan)
            measured = run_config(strategy)
            chosen = priced
            out["auto"] = measured
            break
        except Exception as e:  # noqa: BLE001 — fall to the next plan
            fallbacks.append({"plan": priced.plan.name,
                              "error": "%s: %s"
                              % (type(e).__name__, str(e)[:160])})
    if fallbacks:
        out["fallbacks"] = fallbacks
    if chosen is None:
        out["error"] = "no fleet-runnable plan survived"
        return out
    out["chosen"] = chosen.plan.to_dict()
    out["chosen_predicted_step_seconds"] = chosen.predicted_step_seconds
    base_s = out["baseline"]["step_seconds"]
    auto_s = out["auto"]["step_seconds"]
    if auto_s:
        out["speedup_vs_baseline"] = round(base_s / auto_s, 4)
    out["loss_gap_auto_vs_baseline"] = round(
        abs(out["auto"]["losses"][-1]
            - out["baseline"]["losses"][-1]), 6)
    return out


class _Run:
    """What one bench process measured: variants, the best headline,
    detail sections, and the phases that raised."""

    def __init__(self, backend, device_kind, n_devices):
        self.on_accel = backend != "cpu"
        self.device_kind = device_kind
        self.best = None
        self.variants = []
        self.errors = []
        self.detail = {
            "backend": backend,
            "device_kind": device_kind,
            "n_devices": n_devices,
            "measured_unix": int(time.time()),
        }

    def phase(self, key, fn):
        """Run one phase; a raise is recorded (traceback on stderr) and
        makes the process exit non-zero, the other phases still run."""
        try:
            return fn()
        except Exception as e:  # noqa: BLE001 — reported, exit code 1
            traceback.print_exc()
            self.errors.append(
                "%s failed: %s: %s" % (key, type(e).__name__, str(e)[:300]))
            return None

    def section(self, key, fn):
        out = self.phase(key, fn)
        if out is not None:
            self.detail[key] = out

    def add_variant(self, variant, cfg):
        from paddle_tpu.analysis import costs

        # the accelerator plan errors on a device the table does not
        # know; the CPU plan reports a ratio only under explicit
        # PADDLE_TPU_PEAK_FLOPS / calibration pins (perf_lane.sh)
        peak = (_peak_flops(self.device_kind) if self.on_accel
                else costs.peak_flops(self.device_kind))
        flops = _flops_per_token_train(cfg, variant["seq_len"])
        if peak:
            variant["mfu"] = round(
                variant["tokens_per_sec"] * flops / peak, 4)
            if variant.get("predicted_mfu") and variant["mfu"]:
                # model error of the static roofline vs the measurement
                variant["mfu_model_err_pct"] = round(
                    100.0 * (variant["predicted_mfu"] - variant["mfu"])
                    / variant["mfu"], 1)
        self.variants.append(variant)
        tps = variant["tokens_per_sec"]
        if self.best is not None and self.best["value"] >= tps:
            return
        detail = {k: variant[k] for k in (
            "batch", "seq_len", "flash_attention", "step_ms", "compile_s",
            "loss_first", "loss_last")}
        detail["train_flops_per_token"] = flops
        if peak:
            detail["mfu"] = variant["mfu"]
            detail["peak_flops_assumed"] = peak
        self.best = {
            "metric": "bert_base_pretrain_throughput" if self.on_accel
            else "bert_tiny_pretrain_throughput_cpu",
            "value": tps,
            "detail": detail,
        }

    def result(self):
        detail = dict(self.detail)
        detail["errors"] = self.errors
        detail["variants"] = self.variants
        if self.best is None:
            return {"metric": "bert_pretrain_throughput", "value": 0.0,
                    "unit": "tokens/sec/chip", "vs_baseline": 0.0,
                    "detail": detail}
        detail.update(self.best["detail"])
        return {
            "metric": self.best["metric"],
            "value": self.best["value"],
            "unit": "tokens/sec/chip",
            "vs_baseline": round(
                self.best["value"] / V100_BASELINE_TOKENS_PER_SEC, 3),
            "detail": detail,
        }


# opt-in lanes: (env flag, detail key, measure function)
_OPT_IN_LANES = (
    # serving lane (ISSUE 5): micro-batched inference throughput; fleet
    # lane (ISSUE 7): router over per-device replicas vs the bare engines
    ("PADDLE_TPU_BENCH_SERVING", "serving", _measure_serving),
    ("PADDLE_TPU_BENCH_SERVING", "serving_fleet", _measure_serving_fleet),
    # decode lane (ISSUE 9): continuous-batching KV-cache decode behind
    # the HTTP :generate stream, vs the full-batch barrier
    ("PADDLE_TPU_BENCH_DECODE", "decode_serving", _measure_decode_serving),
    # disagg lane (ISSUE 12): prefill/decode phase split vs the colocated
    # engine under mixed tenants, with a mid-run decode-replica kill
    ("PADDLE_TPU_BENCH_DISAGG", "disagg_serving", _measure_disagg_serving),
    # spec lane (ISSUE 19): prefix-cache KV adoption + speculative
    # block-verify decode vs the plain engine
    ("PADDLE_TPU_BENCH_SPEC", "spec_serving", _measure_spec_serving),
    # retrieval lane (ISSUE 20): ep-sharded embedding lookup + brute-force
    # top-k vs single-device reference
    ("PADDLE_TPU_BENCH_RETRIEVAL", "retrieval", _measure_retrieval),
    # comms lane (ISSUE 10): explicit bucketed/quantized dp gradient sync
    # vs the GSPMD fp32 baseline
    ("PADDLE_TPU_BENCH_COMMS", "comms", _measure_comms),
    # auto-tuned lane (ISSUE 11): the planner's top fleet-runnable pick
    # end-to-end vs the dp-gspmd baseline
    ("PADDLE_TPU_BENCH_PLAN", "planner", _measure_planner),
)


def main(telemetry_out=None):
    import jax

    from paddle_tpu.fluid import compile_cache

    cache_dir = compile_cache.configure_xla_cache()
    devs = jax.devices()
    backend, device_kind = devs[0].platform, devs[0].device_kind
    print("bench.py: platform=%s device_kind=%r devices=%d jax=%s "
          "xla_cache=%s" % (backend, device_kind, len(devs),
                            jax.__version__, cache_dir), flush=True)
    if backend == "tpu":
        _peak_flops(device_kind)   # unknown device: error before any work
    elif not (backend == "cpu"
              and os.environ.get("JAX_PLATFORMS") == "cpu"):
        print("bench.py: jax found no TPU (platform=%s). The accelerator "
              "plan needs one; JAX_PLATFORMS=cpu set explicitly selects "
              "the tiny CPU plan instead." % backend, file=sys.stderr)
        return 2
    run = _Run(backend, device_kind, len(devs))

    if run.on_accel:
        # Measured on v5e (2026-07-31, before PR 1): XLA fused attention
        # beats the pallas kernel at T=128, batch 48 is the throughput
        # sweet spot (b32 latency-bound, b64+ flat), and vocab padding
        # to 30720 measured neutral. Dropout masks ride XLA's native
        # RngBitGenerator (see ops/nn_ops.py), worth ~35%.
        plan = [
            ("b48", False, 48, 128, 30, None),
            ("b64", False, 64, 128, 30, None),
            ("b128", False, 128, 128, 30, None),
            # phase-2 pretrain shape; MFU 0.34 here vs 0.485 at s128
            # (attention's T^2 term). XLA attention beats pallas flash
            # at s512/1024/2048 too (BENCHMARKS.md crossover table), so
            # flash stays opt-in.
            ("s512", False, 16, 512, 12, None),
        ]
    else:
        plan = [("cpu-tiny", False, 8, 64, 5, None)]

    for tag, use_flash, batch, seq, n_steps, vpad in plan:
        run.phase(tag, lambda: run.add_variant(*_measure(
            tag, run.on_accel, use_flash, batch, seq, n_steps,
            vocab_pad=vpad)))

    if run.on_accel:
        # secondary headline (SURVEY §6): ResNet-50 imgs/sec/chip, then
        # BASELINE configs 4-5: Wide&Deep CTR (dataset trainer path) and
        # Transformer-NMT beam decode (throughput peaks at b128; b32
        # stays the continuity config) — detail-only
        run.section("resnet50", _measure_resnet)
        run.section("ctr", _measure_ctr)
        run.section("nmt_decode", _measure_nmt_decode)
        run.section("nmt_decode_b128",
                    lambda: _measure_nmt_decode(batch=128, n_iters=6))

    for flag, key, fn in _OPT_IN_LANES:
        if os.environ.get(flag):
            run.section(key, fn)

    if telemetry_out:
        # --telemetry-out: the final hub snapshot (compile times, cache
        # hit/miss, span histograms) lands next to the result so a
        # regression in throughput can be cross-read against WHERE the
        # step time went
        def _write_telemetry():
            from paddle_tpu import observability as _obs

            doc = _obs.snapshot()
            # the executable ledger rides along: `python -m
            # paddle_tpu.observability perf <this file>` renders its
            # predicted-vs-XLA-vs-measured drift table, and
            # DeviceProfile.calibrated_from fits effective roofline
            # constants from it
            doc["ledger"] = _obs.get_ledger().snapshot()
            # per-variant goodput fractions ride under "runhealth" so
            # `python -m paddle_tpu.observability run <this file>`
            # reads the same doc the perf CLI does
            goodput = {v["tag"]: v["goodput_fraction"]
                       for v in run.variants if "goodput_fraction" in v}
            if goodput:
                doc["runhealth"] = {"goodput": {"per_variant": goodput}}
            _atomic_write_json(telemetry_out, doc)

        run.phase("telemetry-out", _write_telemetry)

    print(json.dumps(run.result()), flush=True)
    return 1 if run.errors else 0


def baseline_cli(argv):
    """``bench.py --update-baseline | --check-regressions`` — the
    perf-regression gate over the persistent baseline store
    (``bench_experiments/_baseline.py``). Stdlib only, never imports jax.
    Reads a bench result JSON (``--result``, default the dated pre-PR-1
    chip record ``.bench_last_good.json``), compares/banks it against
    ``bench_experiments/BASELINE.json`` (or ``--baseline``).

    Exit codes: 0 clean (or banked), 1 regression(s) beyond tolerance,
    2 unreadable result."""
    import argparse

    ap = argparse.ArgumentParser(prog="bench.py (baseline gate)")
    ap.add_argument("--check-regressions", action="store_true")
    ap.add_argument("--update-baseline", action="store_true")
    ap.add_argument("--result", default=None,
                    help="bench result JSON (default: "
                    ".bench_last_good.json)")
    ap.add_argument("--baseline", default=None,
                    help="baseline store path (default: "
                    "bench_experiments/BASELINE.json)")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "bench_experiments"))
    from _baseline import BaselineStore

    result_path = args.result or os.path.join(
        os.path.dirname(os.path.abspath(__file__)), ".bench_last_good.json")
    try:
        with open(result_path) as f:
            result = json.load(f)
    except (OSError, ValueError) as e:
        print("baseline gate: cannot read result %s (%s: %s)"
              % (result_path, type(e).__name__, e), file=sys.stderr)
        return 2
    store = BaselineStore(args.baseline)
    if args.update_baseline:
        banked = store.update(result)
        print(json.dumps({"banked": banked, "path": store.path}))
        return 0
    report = store.check(result)
    print(store.render_report(report))
    return 1 if report["regressions"] else 0


if __name__ == "__main__":
    _argv = sys.argv[1:]
    # baseline gate: pure JSON comparison, never touches the chip
    if "--check-regressions" in _argv or "--update-baseline" in _argv:
        sys.exit(baseline_cli(_argv))
    _tel_out = None
    if "--telemetry-out" in _argv:
        # --telemetry-out PATH: write the final Telemetry.snapshot()
        # JSON (plus the executable ledger) there
        try:
            _tel_out = _argv[_argv.index("--telemetry-out") + 1]
        except IndexError:
            print("bench.py: --telemetry-out requires a PATH",
                  file=sys.stderr)
            sys.exit(2)
    sys.exit(main(telemetry_out=_tel_out))
