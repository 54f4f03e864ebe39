#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof the system still starts on the chip.

One process, which holds the chip(s) itself and starts no child, drives the
main path once through the entry points a user calls, at the full width of
the models (depth as published too; weights random from a seed):

  device    names platform / device_kind / count / versions; not a TPU, or a
            device_kind missing from analysis.costs.DEVICE_TABLE -> exit 2
  trainer   BERT-base b48 x s128, bf16-AMP Adam, fluid.Executor(): loss falls,
            all state on the TPU, no compile after step 1
  server    GPT (hidden 768 x 12 layers) behind DecodeEngine -> ModelRegistry
            -> ServingServer, concurrent HTTP :generate; every emitted
            token's logit within a stated tolerance of a float32 reference
  kernels   flash attention fwd+bwd compiled by Mosaic, with and without
            dropout and a key-padding mask, against references (the
            kernels a cell runs are held to their XLA twins by that cell)
  callback  one py_func program (host callback) trains on the chip
  multichip only when several chips are visible: BERT-base data-parallel
            (CompiledProgram) and dp x tp (DistributedProgram) over all of
            them, shards on n distinct devices, dp loss ~ one-chip loss

Any phase that fails raises: the exit code is non-zero and no result line is
printed. Step times, compile seconds and peak memory are printed as smoke
observations, not metrics. The last stdout line of a passing run is

  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearse-cpu` walks every phase's code at tiny sizes on the CPU (Pallas
kernels in interpret mode) for debugging here; it is printed as a rehearsal
and never ends in the result line above.

The XLA compile cache lives where JAX_COMPILATION_CACHE_DIR says, else at
<checkout>/.jax_cache (fluid.compile_cache.configure_xla_cache); the AOT
jax.export tier (PADDLE_TPU_COMPILE_CACHE_DIR) is left off because a hit
there runs a non-donating executable, not the program a cold process runs.
"""
import argparse
import json
import sys
import threading
import time
import urllib.request

# what the chip run uses, and what the explicit CPU rehearsal shrinks it to
CHIP = dict(
    bert_batch=48, bert_seq=128, bert_steps=10,
    gpt_cache_len=128, gpt_buckets=(16, 64), gpt_slots=4,
    gpt_prompt_lens=(5, 12, 16, 30, 50, 64), gpt_max_new=16,
    # BERT-base head shapes: 12 heads of 64
    flash_shapes=((8, 12, 128, 64), (8, 12, 512, 64)), flash_block=128,
)
REHEARSAL = dict(
    bert_batch=8, bert_seq=32, bert_steps=4,
    gpt_cache_len=48, gpt_buckets=(8, 16), gpt_slots=2,
    gpt_prompt_lens=(3, 6, 10, 14), gpt_max_new=4,
    flash_shapes=((1, 2, 32, 16), (1, 2, 64, 16)), flash_block=16,
)

# Tolerances, each with its reason.
# Flash attention and the reference both get float32 operands; on a TPU the
# kernel's MXU passes round them to bf16 (8-bit mantissa, 2^-8 = 4e-3 per
# product) while the reference runs at precision "highest". Relative to the
# largest reference element the chip runs of PR 21 measured 3.6e-3 .. 9.7e-3
# over output and gradients.
FLASH_RTOL = 2e-2
# Serving: the engine's programs run float32 weights at the TPU's default
# matmul precision (bf16 passes); the teacher-forced reference runs at
# "highest". An emitted token may differ from the reference argmax only
# where the two top logits are closer than that rounding: its reference
# logit must be within this fraction of the position's logit range
# (max - min over the vocabulary) of the maximum. The chip runs of PR 21
# measured 0.0 (96/96 tokens are the reference argmax, the closest top-2
# margin being 3.5e-4 of the range).
SERVE_LOGIT_FRAC = 5e-3
# dp loss vs one-chip loss: same data (the one-chip batch tiled n times),
# same init; only the dropout masks differ with the batch shape, and the
# bf16 loss reads in steps of 0.5%. Four chips measured 0% (first) and 1.1%
# (last of 10 steps).
DP_LOSS_RTOL = 3e-2


def note(phase, **obs):
    """One line per observation; these are smoke observations, not metrics."""
    print("[%s] %s" % (phase, json.dumps(obs, sort_keys=True)), flush=True)


class XlaCacheCounter:
    """Counts jax's persistent-compilation-cache hits and misses, so each
    phase can say which tier served its compiles."""

    def __init__(self):
        import jax.monitoring

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"xla_cache_hits": self.hits, "xla_cache_misses": self.misses}
        self.hits = self.misses = 0
        return out


def fresh_programs(seed):
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid import executor, framework, unique_name

    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor._scope_stack[:] = [executor.Scope()]
    fluid.default_startup_program().random_seed = seed
    fluid.default_main_program().random_seed = seed


def assert_on_device(arrays, platform, what):
    import jax

    for name, v in arrays:
        assert isinstance(v, jax.Array), (
            "%s %r is a %s, not a jax.Array" % (what, name, type(v)))
        plats = {d.platform for d in v.devices()}
        assert plats == {platform}, (
            "%s %r lives on %s, expected %s" % (what, name, plats, platform))


def rel_err(got, want):
    import jax.numpy as jnp

    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


# ---------------------------------------------------------------------------
def phase_device(rehearse):
    import jax
    import jaxlib

    from paddle_tpu.analysis import costs
    from paddle_tpu.fluid import compile_cache
    from paddle_tpu.native import build as native_build

    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearse and dev["platform"] != "cpu":
        sys.exit("chip_smoke.py: --rehearse-cpu is for JAX_PLATFORMS=cpu; "
                 "on a %s run without it" % dev["platform"])
    if not rehearse and dev["platform"] != "tpu":
        # nothing on stdout: no line of this run can be read as a result
        print("chip_smoke.py: jax found no TPU (platform=%s, devices=%s). "
              "This script proves the system on the chip and has no CPU "
              "fallback; --rehearse-cpu walks the phases at tiny sizes."
              % (dev["platform"], devs), file=sys.stderr)
        sys.exit(2)
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None
    native_build.load_native()
    note("device", jax=jax.__version__, jaxlib=jaxlib.__version__,
         libtpu=libtpu_version, native_runtime=native_build.status(),
         xla_cache_dir=compile_cache.configure_xla_cache(),
         aot_disk_tier=compile_cache.enabled(), **dev)
    if rehearse:
        return dev
    try:
        profile = costs.require_device_profile(dev["kind"])
    except LookupError as e:
        print("chip_smoke.py: %s" % e, file=sys.stderr)
        sys.exit(2)
    note("device", table_row=profile.name, peak_bf16_flops=profile.peak_flops,
         hbm_bytes=profile.hbm_bytes)
    return dev


# ---------------------------------------------------------------------------
def build_bert_trainer(full, seq):
    """BERT pretrain program + bf16-AMP Adam."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.contrib.mixed_precision import decorate
    from paddle_tpu.models import bert

    fresh_programs(seed=7)
    cfg = bert.bert_base() if full else bert.bert_tiny()
    cfg.use_fused_attention = False
    vs = bert.build_bert_pretrain(cfg, seq)
    opt = decorate(fluid.optimizer.Adam(learning_rate=1e-4), use_bf16=True)
    opt.minimize(vs["loss"])
    return cfg, vs


def run_steps(exe, program, feed, vs, n_steps):
    """Step 1 (compile) alone, then the rest timed as one window that ends
    in a host read. Every step fetches the loss and the head's two device
    counters (one fetch list, one compile). Returns (losses first/last,
    compile_s, step_ms, {head_rows, head_chunks} of the last step)."""
    import numpy as np

    fetch = [vs["loss"], vs["head_rows"], vs["head_chunks"]]
    t0 = time.monotonic()
    first = float(np.asarray(
        exe.run(program, feed=feed, fetch_list=fetch)[0]))
    compile_s = time.monotonic() - t0
    t0 = time.monotonic()
    for _ in range(n_steps - 1):
        out = exe.run(program, feed=feed, fetch_list=fetch,
                      return_numpy=False)
    last = float(np.asarray(out[0]))
    step_ms = 1000 * (time.monotonic() - t0) / (n_steps - 1)
    head = {"head_rows": int(np.asarray(out[1])),
            "head_chunks": int(np.asarray(out[2]))}
    assert np.isfinite([first, last]).all(), (first, last)
    assert last < first, "loss did not fall: %s -> %s" % (first, last)
    labelled = int((np.asarray(feed["mlm_labels"]) >= 0).sum())
    assert head["head_rows"] == labelled and head["head_chunks"] >= 1, (
        head, labelled)
    return first, last, compile_s, step_ms, head


def phase_trainer(sz, dev, cache):
    import jax

    import paddle_tpu.fluid as fluid
    from paddle_tpu import observability as obs
    from paddle_tpu.models import bert

    full = sz is CHIP
    cfg, vs = build_bert_trainer(full, sz["bert_seq"])
    exe = fluid.Executor()
    t0 = time.monotonic()
    exe.run(fluid.default_startup_program())
    startup_s = time.monotonic() - t0
    ids, labels = bert.synthetic_batch(cfg, sz["bert_batch"], sz["bert_seq"])
    feed = {"input_ids": ids, "mlm_labels": labels}

    def compiles():
        return len(obs.get_recorder().of("compile_start"))

    before = compiles()
    first, last, compile_s, step_ms, head = run_steps(
        exe, fluid.default_main_program(), feed, vs, sz["bert_steps"])
    # run_steps compiled once, in step 1; nothing may compile after it
    assert compiles() == before + 1, (
        "%d compile_start events in %d steps, expected 1 (step 1)"
        % (compiles() - before, sz["bert_steps"]))

    scope = fluid.global_scope()
    persist = [(v.name, scope.find_value(v.name))
               for v in fluid.default_main_program().list_vars()
               if v.persistable and scope.find_value(v.name) is not None]
    assert persist
    assert_on_device(persist, dev["platform"], "persistable")
    stats = jax.devices()[0].memory_stats() or {}
    note("trainer", model="bert_base" if full else "bert_tiny",
         layers=cfg.num_layers, hidden=cfg.hidden, batch=sz["bert_batch"],
         seq=sz["bert_seq"], steps=sz["bert_steps"],
         loss_first=round(first, 4), loss_last=round(last, 4), **head,
         startup_s=round(startup_s, 1),
         step1_compile_s=round(compile_s, 1), step_ms=round(step_ms, 2),
         persistables_on_device=len(persist),
         peak_bytes_in_use=stats.get("peak_bytes_in_use"),
         compile_tier="executor in-memory LRU <- XLA persistent cache",
         **cache.take())
    return {"loss_first": first, "loss_last": last}


# ---------------------------------------------------------------------------
def http_generate(url, prompt, max_new):
    """One streaming POST :generate; returns the emitted tokens."""
    body = json.dumps({"prompt": [int(t) for t in prompt],
                       "max_new_tokens": max_new}).encode()
    req = urllib.request.Request(
        url, data=body, headers={"Content-Type": "application/json"})
    toks, done = [], None
    with urllib.request.urlopen(req, timeout=300) as resp:
        for line in resp:
            doc = json.loads(line)
            if "token" in doc:
                toks.append(doc["token"])
            elif doc.get("done"):
                done = doc
    assert done is not None and done.get("finish_reason") == "length", done
    assert len(toks) == max_new and done["tokens"] == toks, (toks, done)
    return toks


def phase_server(sz, dev, cache):
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu import serving
    from paddle_tpu.models import gpt

    full = sz is CHIP
    max_new = sz["gpt_max_new"]
    ref_len = max(sz["gpt_prompt_lens"]) + max_new
    # GPTConfig() defaults ARE the published GPT-2-small width
    cfg = gpt.GPTConfig() if full else gpt.gpt_tiny(vocab=97, max_len=64)
    fresh_programs(seed=9)
    # the reference program doubles as the weight initialiser
    ref = gpt.build_gpt_lm(cfg, ref_len, is_test=True)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ref_prog = fluid.default_main_program()

    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab, n).astype("int64")
               for n in sz["gpt_prompt_lens"]]
    t0 = time.monotonic()
    eng = serving.DecodeEngine(
        cfg, fluid.global_scope(), slots=sz["gpt_slots"],
        cache_len=sz["gpt_cache_len"], prompt_buckets=sz["gpt_buckets"],
        queue_capacity=64, name="smoke-gpt")
    reg = serving.ModelRegistry()
    srv = None
    try:
        warm = eng.warmup()
        warmup_s = time.monotonic() - t0
        reg.publish("gpt", eng)
        srv = serving.ServingServer(reg).start()
        url = srv.url + "/v1/models/gpt:generate"

        results, errors = {}, []

        def client(i):
            try:
                results[i] = http_generate(url, prompts[i], max_new)
            except Exception as e:  # noqa: BLE001 — re-raised below
                errors.append((i, e))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        t0 = time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=600)
        wall = time.monotonic() - t0
        assert not any(t.is_alive() for t in threads), "a client hung"
        if errors:
            raise errors[0][1]
        assert sorted(results) == list(range(len(prompts)))
        assert_on_device(
            [("slot_cache_%d" % i, b)
             for i, b in enumerate(eng._cache.bufs)]
            + sorted(eng._params.items()), dev["platform"], "engine state")
        stats = eng.stats()
    finally:
        if srv is not None:
            srv.stop(close_registry=False)
        reg.close()

    # correctness on logits: teacher-force prompt + emitted tokens through
    # the float32 LM on the same device at precision "highest"
    ids = np.zeros((len(prompts), ref_len), np.int64)
    for i, p in enumerate(prompts):
        seq = list(p) + results[i]
        ids[i, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        logits = exe.run(
            ref_prog, feed={"gpt_ids": ids, "gpt_labels": ids},
            fetch_list=[ref["logits"]])[0]
    worst, closest_call, agree, total = 0.0, 1.0, 0, 0
    for i, p in enumerate(prompts):
        for j, tok in enumerate(results[i]):
            row = logits[i, len(p) - 1 + j]
            top2 = np.partition(row, -2)[-2:]
            span = row.max() - row.min()
            worst = max(worst, float((row.max() - row[tok]) / span))
            closest_call = min(closest_call, float((top2[1] - top2[0]) / span))
            agree += int(row.argmax() == tok)
            total += 1
    assert np.isfinite(logits).all()
    assert worst <= SERVE_LOGIT_FRAC, (
        "an emitted token's reference logit is %.4f of the logit range "
        "below the maximum (tolerance %.4f)" % (worst, SERVE_LOGIT_FRAC))
    note("server", model="gpt2_small_width" if full else "gpt_tiny",
         layers=cfg.num_layers, hidden=cfg.hidden, vocab=cfg.vocab,
         slots=sz["gpt_slots"], cache_len=sz["gpt_cache_len"],
         prompt_buckets=sz["gpt_buckets"], requests=len(prompts),
         prompt_lens=sz["gpt_prompt_lens"], max_new=max_new,
         tokens=stats["tokens"], prefills=stats["prefills"],
         decode_steps=stats["steps"], warmup_s=round(warmup_s, 1),
         warmup_sources=[r["source"] for r in warm],
         requests_wall_s=round(wall, 2), worst_logit_gap_frac=round(worst, 5),
         logit_tolerance_frac=SERVE_LOGIT_FRAC,
         argmax_agreement="%d/%d" % (agree, total),
         distinct_tokens_emitted=len({t for r in results.values() for t in r}),
         closest_top2_margin_frac=round(closest_call, 5), **cache.take())


# ---------------------------------------------------------------------------
def flash_keep_mask(seed, bh, t, block, p):
    """The kernel's own dropout bits (a pure-jnp hash), rebuilt outside it
    as a (bh, t, t) keep mask."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops import pallas_attention as pa

    def tile(b, qi, kj):
        return pa._keep_mask(pa.fold_bh_seed(jnp.int32(seed), b), qi, kj,
                             block, block, p)

    over = jax.vmap(jax.vmap(jax.vmap(
        tile, (None, None, 0)), (None, 0, None)), (0, None, None))
    n = jnp.arange(t // block, dtype=jnp.int32)
    m = over(jnp.arange(bh, dtype=jnp.int32), n, n)   # (bh, nq, nk, bq, bk)
    return m.transpose(0, 1, 3, 2, 4).reshape(bh, t, t)


def out_and_grads(f, w, *args):
    """(f(*args), d sum(f * w) / d args...) from one jitted call."""
    import jax
    import jax.numpy as jnp

    def loss(*a):
        y = f(*a)
        return jnp.sum(y * w), y

    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=tuple(range(len(args))), has_aux=True))(*args)
    return (y,) + grads


def phase_kernels(sz, cache):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops import pallas_attention as pa

    interpret = sz is not CHIP   # Mosaic on the chip, interpreter in rehearsal
    block, seed, drop = sz["flash_block"], 11, 0.1
    rng = np.random.default_rng(0)
    cases = []

    def masked_reference(q, k, v, kpm, keep, p):
        """reference_attention with the kernel's own dropout mask."""
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
        if kpm is not None:
            s = s + kpm[:, None, None, :]
        pr = jax.nn.softmax(s, -1)
        pr = jnp.where(keep, pr, 0.0) / (1.0 - p)
        return jnp.einsum("bhqk,bhkd->bhqd", pr, v)

    for shape in sz["flash_shapes"]:
        b, h, t, d = shape
        q, k, v, w = (jnp.asarray(rng.normal(size=shape), jnp.float32)
                      for _ in range(4))
        pad = np.zeros((b, t), np.float32)
        pad[:, t - t // 4:] = -1e30          # last quarter of keys padded
        keep = flash_keep_mask(seed, b * h, t, block, drop).reshape(
            b, h, t, t)
        for kpm in (None, jnp.asarray(pad)):
            for p in (0.0, drop):
                def flash(q, k, v):
                    return pa.flash_attention(
                        q, k, v, kpm, seed=seed, dropout_p=p, block_q=block,
                        block_k=block, interpret=interpret)

                def reference(q, k, v):
                    if p:
                        return masked_reference(q, k, v, kpm, keep, p)
                    return pa.reference_attention(q, k, v, kpm)

                got = out_and_grads(flash, w, q, k, v)
                with jax.default_matmul_precision("highest"):
                    want = out_and_grads(reference, w, q, k, v)
                errs = [rel_err(a, b) for a, b in zip(got, want)]
                assert all(np.isfinite(errs)) and max(errs) <= FLASH_RTOL, (
                    "flash_attention %s kpm=%s dropout=%s: rel err "
                    "(out, dq, dk, dv) = %s > %s"
                    % (shape, kpm is not None, p, errs, FLASH_RTOL))
                cases.append(dict(
                    shape=shape, key_padding_mask=kpm is not None,
                    dropout=p,
                    rel_err_out_dq_dk_dv=[round(e, 6) for e in errs]))
    note("kernels", kernel="flash_attention",
         compiled_by="interpreter" if interpret else "mosaic",
         rtol=FLASH_RTOL, cases=cases, **cache.take())


# ---------------------------------------------------------------------------
def phase_callback():
    """py_func lowers to jax.pure_callback: a host callback in the step."""
    import numpy as np

    import paddle_tpu.fluid as fluid

    fresh_programs(seed=3)
    x = fluid.data(name="x", shape=[4, 2], dtype="float32")
    h = fluid.layers.fc(x, size=2)
    sq = fluid.default_main_program().current_block().create_var(
        name="sq_out", dtype="float32", shape=(4, 2))
    sq = fluid.layers.py_func(
        lambda a: a * a, h, sq,
        backward_func=lambda a, out, dout: 2.0 * a * dout)
    loss = fluid.layers.reduce_mean(sq)
    fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    feed = {"x": np.random.RandomState(1).rand(4, 2).astype("float32")}
    losses = [float(exe.run(feed=feed, fetch_list=[loss])[0])
              for _ in range(5)]
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    note("callback", op="py_func", place=repr(exe.place),
         loss_first=round(losses[0], 5), loss_last=round(losses[-1], 5))


# ---------------------------------------------------------------------------
def shard_devices(arr):
    return {s.device for s in arr.addressable_shards}


def phase_multichip(sz, dev, one_chip, cache):
    """BERT over every visible chip: data-parallel CompiledProgram (global
    batch = one-chip batch tiled n times, so the loss is comparable) and
    the dp x tp DistributedProgram step of __graft_entry__.dryrun_multichip
    (dp = n/2, tp = 2)."""
    import jax
    import numpy as np

    import paddle_tpu.fluid as fluid
    from paddle_tpu.models import bert
    from paddle_tpu.parallel.mesh import build_mesh
    from paddle_tpu.parallel.sharding import DistributedProgram, ShardingRule

    full = sz is CHIP
    n = dev["count"]
    devices = set(jax.devices())
    seq, steps = sz["bert_seq"], sz["bert_steps"]

    def state_devices(scope, program):
        vals = [scope.find_value(v.name) for v in program.list_vars()
                if v.persistable]
        return [shard_devices(v) for v in vals if v is not None]

    # -- data parallel -------------------------------------------------
    cfg, vs = build_bert_trainer(full, seq)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    ids, labels = bert.synthetic_batch(cfg, sz["bert_batch"], seq)
    feed = {"input_ids": np.tile(ids, (n, 1)),
            "mlm_labels": np.tile(labels, (n, 1))}
    prog = fluid.default_main_program()
    cp = fluid.CompiledProgram(prog).with_data_parallel(
        loss_name=vs["loss"].name)
    for name, arr in cp._shard_feeds(feed, cp._get_mesh(exe.place)).items():
        assert shard_devices(arr) == devices and (
            arr.addressable_shards[0].data.shape[0] * n == arr.shape[0]), (
            "dp feed %r is not split over %d devices" % (name, n))
    first, last, compile_s, step_ms, head = run_steps(
        exe, cp, feed, vs, steps)
    placed = state_devices(fluid.global_scope(), prog)
    assert placed and all(d == devices for d in placed), (
        "dp state is not on all %d devices" % n)
    for got, want in ((first, one_chip["loss_first"]),
                      (last, one_chip["loss_last"])):
        assert abs(got - want) <= DP_LOSS_RTOL * abs(want), (
            "dp loss %s vs one-chip %s (rtol %s)" % (got, want, DP_LOSS_RTOL))
    note("multichip", mode="data_parallel", devices=n,
         global_batch=n * sz["bert_batch"], loss_first=round(first, 4),
         loss_last=round(last, 4), **head,
         one_chip_loss_first=round(one_chip["loss_first"], 4),
         one_chip_loss_last=round(one_chip["loss_last"], 4),
         loss_rtol=DP_LOSS_RTOL, step1_compile_s=round(compile_s, 1),
         step_ms=round(step_ms, 2), **cache.take())

    # -- dp x tp -------------------------------------------------------
    tp = 2 if n % 2 == 0 else 1
    dp = n // tp
    cfg, vs = build_bert_trainer(full, seq)
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    prog = fluid.default_main_program()
    mesh = build_mesh({"dp": dp, "tp": tp}, devices=jax.devices())
    dist = DistributedProgram(
        prog, mesh, feed_axis="dp",
        param_rules=[ShardingRule(p, s) for p, s in bert.tp_rules()])
    ids, labels = bert.synthetic_batch(cfg, sz["bert_batch"] * dp, seq)
    feed = {"input_ids": ids, "mlm_labels": labels}
    for name, arr in feed.items():
        placed = jax.device_put(arr, dist.feed_sharding(name, arr.shape))
        assert shard_devices(placed) == devices, (
            "dp x tp feed %r is not on all %d devices" % (name, n))
    first, last, compile_s, step_ms, head = run_steps(
        exe, dist, feed, vs, steps)
    scope = fluid.global_scope()
    placed = state_devices(scope, prog)
    assert placed and all(d == devices for d in placed), (
        "dp x tp state is not on all %d devices" % n)
    if tp > 1:
        qkv = scope.find_value("enc_l0_qkv.w")
        assert qkv.addressable_shards[0].data.shape[1] * tp == qkv.shape[1], (
            "enc_l0_qkv.w is not column-sharded over tp: %s" % qkv.sharding)
    note("multichip", mode="dp_x_tp", dp=dp, tp=tp,
         global_batch=dp * sz["bert_batch"], loss_first=round(first, 4),
         loss_last=round(last, 4), **head,
         step1_compile_s=round(compile_s, 1),
         step_ms=round(step_ms, 2), **cache.take())


# ---------------------------------------------------------------------------
def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--rehearse-cpu", action="store_true",
        help="walk every phase at tiny sizes under JAX_PLATFORMS=cpu "
             "(kernels in interpret mode); proves nothing about the chip")
    args = ap.parse_args(argv)
    sz = REHEARSAL if args.rehearse_cpu else CHIP
    t0 = time.monotonic()
    if args.rehearse_cpu:
        print("REHEARSAL on the CPU at tiny sizes: not a device result",
              flush=True)
    dev = phase_device(args.rehearse_cpu)
    cache = XlaCacheCounter()
    one_chip = phase_trainer(sz, dev, cache)
    phase_server(sz, dev, cache)
    phase_kernels(sz, cache)
    phase_callback()
    phases = ["device", "trainer", "server", "kernels", "callback"]
    if dev["count"] > 1:
        phase_multichip(sz, dev, one_chip, cache)
        phases.append("multichip")
    else:
        note("multichip", skipped="one device visible")
    note("done", phases_passed=phases, wall_s=round(time.monotonic() - t0, 1))
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal_passed": True,
                          "platform": dev["platform"],
                          "devices": dev["count"]}))
    else:
        print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
