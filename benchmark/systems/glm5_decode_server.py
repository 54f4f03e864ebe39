"""System under test: the GLM-5 decoder (`paddle_tpu/models/glm_moe_dsa.py`:
multi-head latent attention over a learned sparse selection of keys,
sigmoid-routed SwiGLU experts plus a shared expert) served as users reach
it, streaming `POST /v1/models/glm5:generate` -> ServingServer ->
ModelRegistry -> DecodeEngine (continuous batching over slots, batch-1
bucketed prefill) -> Predictor: the same served path as
`gpt_decode_server.py`, with two `rows` entries a layer in the engine's
SlotCache that are no K and V (a latent row and the indexer's row of every
position) and a chip's share of every sparse layer.

The weights are the benchmark's own, made on the device from the seed by
the reference (`benchmark/reference/glm5_lm.py`, bfloat16) and handed to the
engine as owned: 7.8 GB are not copied through the host.

`check()` compares five numbers with the plain reference: the served tokens
(`logit_gap_sigma`), layer by layer the held experts' part (`routed_gap`),
layer by layer what the attention block adds, through the prefill's expanded
path and through the step's absorbed path over gathered rows (`latent_gap`),
and the keys each sampled query kept against the keys the reference keeps
(`select_overlap_miss`, `select_count_off`).

In a traced run the adapter runs one fill of the longest bucket alone under
the profiler before the window (`Server.trace_one_fill`), for the readers of
a program that the window's three traced seconds rarely hold whole.

`counters()` adds to the engine's lifetime counters what the step program
counts on the device: `moe_assignments_held`, `moe_assignments_total`,
`moe_expert_load_max_sum`, `moe_experts_touched_sum`, `dsa_rows_scored`,
`dsa_rows_selected`, `latent_rows_live`, `latent_rows_read`. `gauges()`
splits `state_bytes_rows` into the latent and the indexer's entries."""
import glob
import os
import shutil

import numpy as np

from benchmark import costs_glm5, trace
from benchmark.reference import glm5_lm
from benchmark.systems import gpt_decode_server

MODEL_NAME = "glm5"
reference_sizes = costs_glm5.sizes


def model_config(m):
    from paddle_tpu.models import glm_moe_dsa

    return glm_moe_dsa.GlmMoeDsaConfig.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])


class Server(gpt_decode_server.Server):
    """The GPT server's `counters`, `live_slots` and `close` (they read the
    engine, whatever it serves), around this family's model and weights."""

    def __init__(self, run):
        from paddle_tpu import serving

        m, sv = reference_sizes(run.config), run.config["serving"]
        self.model, self.serving = m, sv
        self.cfg = cfg = model_config(m)
        weights = glm5_lm.make_weights(m, run.seed)
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
        decl = cfg.decode_model(sv["cache_len"]).state
        self.state_bytes = {
            part: sv["slots"] * sum(e.nbytes for e in decl
                                    if e.name.startswith(part + "_"))
            for part in ("lat", "idx")}
        if run.trace:
            self.trace_one_fill(run)
            run.mark("traced fill")

    def trace_one_fill(self, run):
        """One prompt that fills the longest bucket, through the engine and
        alone on the device, under the profiler: `run.obs["glm5_fill"]`
        holds the reduced trace (benchmark/trace.py) and the prompt's
        length. A fill of this program runs a second, so an edge of the
        window's three traced seconds cuts most of those they touch and
        the pooled program line then tells no whole execution's time; here
        every execution is whole. The same prompt goes through once
        before, so that the traced fill is not the program's first."""
        import jax

        plen = max(run.traffic["prompt_buckets"])
        prompt = np.random.default_rng(int(run.seed) + 3).integers(
            0, self.model["vocab_size"], plen)
        self.engine.generate(prompt, max_new=1)
        out = os.path.join(run.out_dir, "fill_trace")
        try:
            with jax.profiler.trace(out):
                self.engine.generate(prompt, max_new=1)
            rows = [row for path in glob.glob(os.path.join(
                out, "plugins", "profile", "*", "*.xplane.pb"))
                for row in trace.rows_from_xplane(path)]
        finally:
            shutil.rmtree(out, ignore_errors=True)
        run.obs["glm5_fill"] = {"plen": plen, "trace": trace.reduce(rows, 0.0)}

    def gauges(self):
        from paddle_tpu import observability as obs

        def g(name):
            return obs.gauge("serving.%s.%s" % (name, MODEL_NAME))

        return {"slot_utilization": g("decode.slot_utilization"),
                "queue_depth": g("queue_depth"),
                "state_bytes_rows": g("decode.state_bytes_rows"),
                "state_bytes_rows_latent": self.state_bytes["lat"],
                "state_bytes_rows_indexer": self.state_bytes["idx"]}


def build(run):
    return Server(run)


class ServedLayers:
    """The SYSTEM's own programs over one sequence at a time, as the engine
    builds them (the same builders, lowering and kernels; a cache of one
    slot), with what the engine does not fetch as further fetches: per
    layer the stream before it, and at `rows` sampled positions of the
    prompt (a feed of the check's own, gathered inside the program: a whole
    (T, T) mask a layer is not brought out) what its attention block adds
    and the keys its indexer kept; per sparse layer the held experts'
    part."""

    def __init__(self, sut, w, bucket, n_rows):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid import layers
        from paddle_tpu.fluid.inference import Predictor
        from paddle_tpu.models import glm_moe_dsa as glm

        self.cfg, self.bucket, self.n_rows = sut.cfg, bucket, n_rows
        self.cache_len = cache_len = sut.serving["cache_len"]
        self.n_state = len(sut.cfg.decode_model(cache_len).state)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            pv = glm.build_prefill(sut.cfg, bucket, cache_len)
            at = fluid.data("check_rows", shape=[n_rows], dtype="int64")

            def sampled(v):
                return layers.gather(
                    layers.reshape(v, [bucket, v.shape[-1]]), at)

            self.prefill = Predictor(
                fluid.default_main_program(),
                pv["feed_names"] + ["check_rows"],
                pv["fetch_vars"] + pv["moe_routed"] + pv["attn_in"]
                + [sampled(v) for v in pv["attn_out"] + pv["selected"]],
                scope=w, name="check_prefill_%d" % bucket)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            sv = glm.build_step(sut.cfg, cache_len)
            self.step_names = sv["feed_names"]
            self.step = Predictor(
                fluid.default_main_program(), sv["feed_names"],
                sv["fetch_vars"] + sv["attn_in"] + sv["attn_out"]
                + sv["selected"], scope=w, name="check_step",
                donate_feeds=sv["cache_feed_names"])

    def run(self, prompt, served, rows):
        """`rows`: `n_rows` positions of the prompt. -> (routed: per sparse
        layer (plen, H); stream: per layer (plen + len(served) - 1, H), the
        stream before the layer, the prompt's rows from the prefill program
        and one row from each decode step that takes served token j at
        position plen + j; added: per layer (n_rows + len(served) - 1, H),
        what the attention block adds at `rows` and at every step; kept:
        per layer (the same rows, cache_len) bool, the keys the indexer
        kept), device arrays."""
        import jax.numpy as jnp

        n, sparse = self.cfg.num_layers, self.cfg.expert_layers
        plen, cache_len = len(prompt), self.cache_len
        ids = np.zeros((1, self.bucket), np.int64)
        ids[0, :plen] = prompt
        out = self.prefill.run(
            [ids, np.full((1, 1), plen, np.int64),
             np.asarray(rows, np.int64)], return_numpy=False)
        state = list(out[1:1 + self.n_state])
        rest = out[1 + self.n_state:]
        routed = [r[:plen] for r in rest[:sparse]]
        rest = rest[sparse:]
        stream = [[a[0, :plen]] for a in rest[:n]]
        added = [[a] for a in rest[n:2 * n]]
        kept = [jnp.pad(a > 0, ((0, 0), (0, cache_len - self.bucket)))
                for a in rest[2 * n:]]
        cols = [[] for _ in range(n)]
        del out, rest
        for j, tok in enumerate(served[:-1]):
            feeds = dict(zip(self.step_names,
                             [np.full((1, 1), tok, np.int64),
                              np.full((1, 1), plen + j, np.int64)] + state))
            out = self.step.run(feeds, return_numpy=False)
            state = list(out[1:1 + self.n_state])
            rest = out[2 + self.n_state:]
            for i in range(n):
                stream[i].append(rest[i])
                added[i].append(rest[n + i])
                cols[i].append(rest[2 * n + i])
        for i in range(n):
            if cols[i]:
                c = jnp.concatenate(cols[i], 0)           # (steps, topk)
                steps = jnp.arange(c.shape[0])[:, None]
                kept[i] = jnp.concatenate([kept[i], jnp.zeros(
                    (c.shape[0], cache_len + 1), bool).at[
                        steps, jnp.where(c >= 0, c, cache_len)].set(True)[
                            :, :cache_len]], 0)
        return (routed, [jnp.concatenate(r, 0) for r in stream],
                [jnp.concatenate(r, 0) for r in added], kept)


def attention_rows(plen, topk, count, seed):
    """`count` query rows of a prompt for `latent_gap` and the selection's
    overlap, spread over [topk, plen) where the selection leaves keys out
    (the last position among them); over the whole prompt while it is
    shorter than that."""
    rng = np.random.default_rng(int(seed) + 2)
    lo = min(topk, plen - 1)
    rows = np.concatenate([rng.integers(lo, plen, count - 1), [plen - 1]])
    return np.sort(rows).astype(np.int32)


def pick_sample(finished, n, seed):
    """A sample of the finished requests of the LONGEST prompt bucket that
    was used, drawn from the seed, the longest request in it: the check then
    builds one prefill program, not one a bucket (each is a large entry of
    the machine's XLA cache, PERF.md section 7.7; the other bucket's program
    is the same builder at another length)."""
    longest = max((r["bucket"] for r in finished), default=None)
    return gpt_decode_server.pick_sample(
        [r for r in finished if r["bucket"] == longest], n, seed)


def check(run, sut, control=None):
    """Once the window has closed and the engine's weights and state are
    freed, five numbers over a seeded sample of finished requests (of the
    longest prompt bucket, the longest request among them).

    `logit_gap_sigma`: one reference pass over each sampled prompt with its
    served tokens (teacher-forced); the widest gap, in units of the
    position's logit standard deviation, by which a served token lies below
    the reference's best. It holds the whole served path: prefill, the two
    caches, the decode step.

    `routed_gap`: the held experts' part of every sparse layer over each
    sampled prompt, from the system's prefill program against the
    reference's own pass (`glm5_lm.routed_gap`: per layer the median over
    the positions of the whole sample that the reference routes here, the
    largest layer).

    `latent_gap`: what each layer's attention block adds to the stream
    (after Wo), the system's (`ServedLayers`: its prefill program over the
    prompt, the expanded path over a threshold mask; then its step program
    over the served tokens through a cache of one slot, the absorbed path
    over gathered rows) against the reference's block over the SAME stream
    (the system's own, so that only this block's arithmetic and selection
    differ) and the reference's OWN selection, at sampled prompt positions
    beyond `index_topk` and at every decoded position, as
    `glm5_lm.rms_gap`; the largest path, layer and request.

    `select_overlap_miss`: 1 - the share of the keys one query kept that
    the reference's selection over the same stream keeps too (over the
    larger of the two counts), the worst sampled query of any layer, path
    and request: rounding moves only the positions ranked near
    `index_topk`. `select_count_off`: the share of the sampled queries
    whose number of kept keys is not the reference's (two scores that tie
    in float32 at the threshold are both kept by the prompt's path: a
    handful of queries in ten thousand; a selection of another size moves
    every one).

    `control` names a lower precision: its own first choice is judged in
    place of the served token, its own held experts' parts, attention
    blocks and selections in place of the system's."""
    import jax.numpy as jnp

    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    m, cache_len = sut.model, sut.serving["cache_len"]
    out_len = run.traffic["max_new_tokens"]["max"]
    n_rows = chk["attention_positions"]
    w = glm5_lm.make_weights(m, run.seed)
    served = None if control or not sample else ServedLayers(
        sut, w, sample[0]["bucket"], n_rows)
    gaps, by_path, miss, off = [], {}, {}, [0, 0]
    routed = [[] for _ in range(sut.cfg.expert_layers)]
    for r in sample:
        prompt, toks = list(r["prompt"]), list(r["tokens"])
        plen, n = len(prompt), len(toks)
        seq = np.zeros((cache_len,), np.int32)
        seq[:plen + n] = prompt + toks
        at = np.minimum(plen - 1 + np.arange(out_len),
                        cache_len - 1).astype(np.int32)
        rows = attention_rows(plen, m["index_topk"], n_rows, run.seed)
        groups = {"prefill": rows,
                  "step": (plen + np.arange(n - 1)).astype(np.int32)}
        if control:
            xc, _, got_routed = glm5_lm.forward(
                w, seq, m, control,
                on_part=lambda j, p: np.asarray(
                    p[:plen].astype(jnp.bfloat16)))
            toks = np.asarray(glm5_lm.head_logits(
                w, xc, at, m, control))[:n].argmax(-1)
            del xc
        else:
            got_routed, streams, added, kept = served.run(prompt, toks,
                                                          rows)
            # 1.8 GB at the timed size: on the host while the reference's
            # pass needs the device, back a layer at a time
            got_routed = [np.asarray(g) for g in got_routed]
            streams = [np.asarray(x) for x in streams]
        # each layer's part is compared as the reference makes it, not kept
        x, ref_streams, _ = glm5_lm.forward(
            w, seq, m, keep_streams=bool(control),
            on_part=lambda j, p: routed[j].append(glm5_lm.routed_errors(
                got_routed[j][:plen], p[:plen])))
        if control:
            streams = ref_streams
        gaps.append(glm5_lm.token_gaps(
            glm5_lm.head_logits(w, x, at, m), toks))
        del x, got_routed, ref_streams
        for i in range(m["num_hidden_layers"]):
            stream = jnp.zeros((cache_len, m["hidden_size"]),
                               streams[i].dtype).at[:plen + n - 1].set(
                                   streams[i][:plen + n - 1])
            first = 0
            for path, at_rows in groups.items():
                if not len(at_rows):
                    continue
                want, want_kept = glm5_lm.attention_at(w, i, stream, at_rows,
                                                       m)
                if control:
                    got, got_kept = glm5_lm.attention_at(
                        w, i, stream, at_rows, m, control)
                else:
                    part = slice(first, first + len(at_rows))
                    got, got_kept = added[i][part], kept[i][part]
                first += len(at_rows)
                by_path[path] = max(by_path.get(path, 0.0),
                                    glm5_lm.rms_gap(got, want))
                share, differ = glm5_lm.overlap(got_kept, want_kept)
                miss[path] = max(miss.get(path, 0.0), 1.0 - share)
                off[0] += differ
                off[1] += len(at_rows)
        del streams
    run.note("latent_gap by program: %s; select_overlap_miss: %s; queries "
             "whose count of kept keys differs: %d of %d"
             % ({k: round(v, 5) for k, v in sorted(by_path.items())},
                {k: round(v, 5) for k, v in sorted(miss.items())}, *off))
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
    for name, value in (
            ("logit_gap_sigma", worst if n_tok >= chk["min_tokens"] else None),
            ("routed_gap", glm5_lm.routed_gap(routed)),
            ("latent_gap", max(by_path.values(), default=None)),
            ("select_overlap_miss", max(miss.values(), default=None)),
            ("select_count_off", off[0] / off[1] if off[1] else None)):
        run.compared[name] = {"value": value, "limit": limits[name]}
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
