"""System under test: the Laguna decoder (`paddle_tpu/models/laguna.py`:
window and full grouped-query attention layers, a gate per head, two rotary
terms, softmax-routed SwiGLU experts plus a shared expert) served as users
reach it, streaming `POST /v1/models/laguna:generate` -> ServingServer ->
ModelRegistry -> DecodeEngine (continuous batching over slots, batch-1
bucketed prefill) -> Predictor: the same served path as
`gpt_decode_server.py`, with state of two kinds in the engine's SlotCache
(K/V `rows` of the full layers, `cache_len` long; K/V `ring`s of the window
layers, `sliding_window` long) and a chip's share of every sparse layer.

The weights are the benchmark's own, made on the device from the seed by
the reference (`benchmark/reference/laguna_lm.py`, bfloat16) and handed to
the engine as owned: 6 GB are not copied through the host.

`check()` compares three numbers with the plain reference: the served
tokens (`logit_gap_sigma`), layer by layer the held experts' part
(`routed_gap`), and layer by layer what the attention block adds, through
the prefill's banded and flash paths and through the step's rows and rings
(`window_gap`).

`counters()` adds to the engine's lifetime counters what the step program
counts on the device: `moe_assignments_held`, `moe_assignments_total`,
`moe_expert_load_max_sum`, `moe_experts_touched_sum`, `kv_rows_live`,
`kv_rows_read`. `gauges()` adds `state_bytes_rows` / `state_bytes_ring`."""
import numpy as np

from benchmark import costs_laguna
from benchmark.reference import laguna_lm
from benchmark.systems import gpt_decode_server
from benchmark.systems.gpt_decode_server import pick_sample

MODEL_NAME = "laguna"
reference_sizes = costs_laguna.sizes


def model_config(m):
    from paddle_tpu.models import laguna

    return laguna.LagunaConfig.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])


class Server(gpt_decode_server.Server):
    """The GPT server's `counters`, `live_slots` and `close` (they read the
    engine, whatever it serves), around this family's model and weights."""

    def __init__(self, run):
        from paddle_tpu import serving

        m, sv = reference_sizes(run.config), run.config["serving"]
        self.model, self.serving = m, sv
        self.cfg = cfg = model_config(m)
        weights = laguna_lm.make_weights(m, run.seed)
        run.mark("seeded weights")
        self.engine = serving.DecodeEngine(
            cfg, weights, slots=sv["slots"], cache_len=sv["cache_len"],
            prompt_buckets=run.traffic["prompt_buckets"],
            queue_capacity=sv["queue_capacity"],
            request_timeout_s=sv["request_timeout_s"], name=MODEL_NAME,
            adopt_params=True)
        del weights
        run.mark("engine built")
        self.warm_report = self.engine.warmup()
        run.mark("engine.warmup")
        self.registry = serving.ModelRegistry()
        self.registry.publish(MODEL_NAME, self.engine)
        self.server = serving.ServingServer(self.registry).start()  # port 0
        self.host, self.port = self.server.host, self.server.port
        self.path = "/v1/models/%s:generate" % MODEL_NAME

    def gauges(self):
        from paddle_tpu import observability as obs

        def g(name):
            return obs.gauge("serving.%s.%s" % (name, MODEL_NAME))

        return {"slot_utilization": g("decode.slot_utilization"),
                "queue_depth": g("queue_depth"),
                "state_bytes_rows": g("decode.state_bytes_rows"),
                "state_bytes_ring": g("decode.state_bytes_ring")}


def build(run):
    return Server(run)


class ServedLayers:
    """The SYSTEM's own programs over one sequence at a time, as the engine
    builds them (the same builders, lowering and kernels; a cache of one
    slot), with what the engine does not fetch as further fetches: per
    layer the stream before it and what its attention block adds, per
    sparse layer the held experts' part."""

    def __init__(self, sut, w, bucket):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.inference import Predictor
        from paddle_tpu.models import laguna

        self.cfg, self.bucket = sut.cfg, bucket
        cache_len = sut.serving["cache_len"]
        self.n_state = len(sut.cfg.decode_model(cache_len).state)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            pv = laguna.build_prefill(sut.cfg, bucket, cache_len)
            self.prefill = Predictor(
                fluid.default_main_program(), pv["feed_names"],
                pv["fetch_vars"] + pv["moe_routed"] + pv["attn_in"]
                + pv["attn_out"], scope=w, name="check_prefill_%d" % bucket)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            sv = laguna.build_step(sut.cfg, cache_len)
            self.step_names = sv["feed_names"]
            self.step = Predictor(
                fluid.default_main_program(), sv["feed_names"],
                sv["fetch_vars"] + sv["attn_in"] + sv["attn_out"], scope=w,
                name="check_step", donate_feeds=sv["cache_feed_names"])

    def run(self, prompt, served):
        """-> (routed: per sparse layer (plen, H); attn_in, attn_out: per
        layer (plen + len(served) - 1, H), the prompt's rows from the
        prefill program and one row from each decode step that takes
        served token j at position plen + j), device arrays."""
        import jax.numpy as jnp

        layers_n, sparse = len(self.cfg.layer_types), self.cfg.expert_layers
        plen = len(prompt)
        ids = np.zeros((1, self.bucket), np.int64)
        ids[0, :plen] = prompt
        out = self.prefill.run([ids, np.full((1, 1), plen, np.int64)],
                               return_numpy=False)
        state = list(out[1:1 + self.n_state])
        rest = out[1 + self.n_state:]
        routed = [r[:plen] for r in rest[:sparse]]
        rows_in = [[a[0, :plen]] for a in rest[sparse:sparse + layers_n]]
        rows_out = [[a[0, :plen]] for a in rest[sparse + layers_n:]]
        for j, tok in enumerate(served[:-1]):
            feeds = dict(zip(self.step_names,
                             [np.full((1, 1), tok, np.int64),
                              np.full((1, 1), plen + j, np.int64)] + state))
            out = self.step.run(feeds, return_numpy=False)
            state = list(out[1:1 + self.n_state])
            rest = out[2 + self.n_state:]
            for i in range(layers_n):
                rows_in[i].append(rest[i])
                rows_out[i].append(rest[layers_n + i])
        return (routed, [jnp.concatenate(r, 0) for r in rows_in],
                [jnp.concatenate(r, 0) for r in rows_out])


def attention_rows(plen, n_steps, window, count, seed):
    """The query rows `window_gap` reads, in two groups: `count` positions
    of the prompt spread over [window, plen) (the prefill's paths: banded
    window, flash or dense causal), and every decoded position (the step's
    paths: rotary from `pos`, rows and rings; the ring has wrapped)."""
    rng = np.random.default_rng(int(seed) + 2)
    lo = min(window, plen - 1)
    prompt = np.unique(np.concatenate([
        rng.integers(lo, plen, max(count - 1, 1)), [plen - 1]]))
    return {"prefill": prompt.astype(np.int32),
            "step": (plen + np.arange(n_steps)).astype(np.int32)}


def check(run, sut, control=None):
    """Once the window has closed and the engine's weights and state are
    freed, three numbers over a seeded sample of finished requests.

    `logit_gap_sigma`: one reference pass over each sampled prompt with its
    served tokens (teacher-forced); the widest gap, in units of the
    position's logit standard deviation, by which a served token lies below
    the reference's best. It holds the whole served path: prefill, rows and
    rings, the decode step.

    `routed_gap`: the held experts' part of every sparse layer over each
    sampled prompt, from the system's prefill program against the
    reference's own pass (`laguna_lm.routed_gap`: the median over
    positions, the largest layer and request).

    `window_gap`: what each layer's attention block adds to the stream
    (after the gate and Wo), the system's (`ServedLayers`: its prefill
    program over the prompt, then its step program over the served tokens
    through a cache of one slot) against the reference's block over the
    SAME stream (the system's own, so that only this block's arithmetic
    differs), at sampled prompt positions beyond the window and at every
    decoded position (`attention_rows`), as `laguna_lm.rms_gap`; the
    largest group, layer and request.

    `control` names a lower precision: its own first choice is judged in
    place of the served token, its own held experts' parts and attention
    blocks in place of the system's."""
    import jax.numpy as jnp

    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    m, cache_len = sut.model, sut.serving["cache_len"]
    out_len = run.traffic["max_new_tokens"]["max"]
    w = laguna_lm.make_weights(m, run.seed)
    served = ServedLayers(sut, w, max(run.traffic["prompt_buckets"]))
    gaps, routed, window, by_path = [], None, None, {}
    for r in sample:
        prompt, toks = list(r["prompt"]), list(r["tokens"])
        plen, n = len(prompt), len(toks)
        seq = np.zeros((cache_len,), np.int32)
        seq[:plen + n] = prompt + toks
        at = np.minimum(plen - 1 + np.arange(out_len),
                        cache_len - 1).astype(np.int32)
        x, _, want_routed = laguna_lm.forward(w, seq, m)
        ref = laguna_lm.head_logits(w, x, at, m)
        got_routed, rows_in, rows_out = served.run(prompt, toks)
        if control:
            xc, _, got_routed = laguna_lm.forward(w, seq, m, control)
            toks = np.asarray(laguna_lm.head_logits(
                w, xc, at, m, control))[:n].argmax(-1)
            del xc
        gaps.append(laguna_lm.token_gaps(ref, toks))
        routed = max(routed or 0.0, laguna_lm.routed_gap(
            [g[:plen] for g in got_routed], [p[:plen] for p in want_routed]))
        del x, want_routed, got_routed
        groups = attention_rows(plen, n - 1, m["sliding_window"],
                                chk["attention_positions"], run.seed)
        for i in range(len(m["layer_types"])):
            stream = jnp.zeros((cache_len, m["hidden_size"]),
                               rows_in[i].dtype).at[:plen + n - 1].set(
                                   rows_in[i])
            for group, rows in groups.items():
                if not len(rows):
                    continue
                want = laguna_lm.attention_at(w, i, stream, rows, m)
                got = (laguna_lm.attention_at(w, i, stream, rows, m, control)
                       if control else jnp.take(rows_out[i], rows, axis=0))
                path = "%s %s" % (m["layer_types"][i].split("_")[0], group)
                by_path[path] = max(by_path.get(path, 0.0),
                                    laguna_lm.rms_gap(got, want))
    window = max(by_path.values(), default=None)
    run.note("window_gap by layer type and program: %s"
             % {k: round(v, 5) for k, v in sorted(by_path.items())})
    del w, served
    n_tok = int(sum(len(g) for g in gaps))
    worst = float(max((g.max() for g in gaps), default=np.inf))
    exact = sum(int((g == 0).sum()) for g in gaps)
    run.note("compared %d requests, %d served tokens, %d of them the "
             "reference's first choice; buckets %s; longest %d"
             % (len(sample), n_tok, exact,
                sorted({r["bucket"] for r in sample}),
                max((len(r["prompt"]) + len(r["tokens"]) for r in sample),
                    default=0)))
    limits = chk["limits"]
    run.compared["logit_gap_sigma"] = {
        "value": worst if n_tok >= chk["min_tokens"] else None,
        "limit": limits["logit_gap_sigma"]}
    run.compared["routed_gap"] = {"value": routed,
                                  "limit": limits["routed_gap"]}
    run.compared["window_gap"] = {"value": window,
                                  "limit": limits["window_gap"]}
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
