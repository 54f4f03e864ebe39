"""System under test: the Solar-Open2 decoder (`paddle_tpu/models/
solar_open2.py`: a softmax grouped-query attention layer without a position
term under an elementwise gate, then three gated delta-rule linear-attention
layers with a decay a channel; sigmoid-routed SwiGLU experts plus a shared
expert in every layer) served as users reach it, streaming `POST
/v1/models/solar:generate` -> ServingServer -> ModelRegistry -> DecodeEngine
(continuous batching over slots, batch-1 bucketed prefill) -> Predictor: the
same served path as `gpt_decode_server.py`, with state of two kinds in the
engine's SlotCache (K/V `rows` of one layer in four, `cache_len` long; three
convolution windows and a float32 delta-rule state of every other layer,
`fixed`) and a chip's share of every layer's experts.

The weights are the benchmark's own, made on the device from the seed by the
reference (`benchmark/reference/solar_open2_lm.py`, bfloat16) and handed to
the engine as owned: 6.6 GB are not copied through the host.

`check()` compares five numbers with the plain reference: the served tokens
(`logit_gap_sigma`), layer by layer the held experts' part (`routed_gap`),
what each delta-rule block adds through the fill's chunked scan and through
the step's path on the slot's state (`kda_gap`), the state a fill hands its
slot (`state_gap`), and what the gated softmax attention adds (`gqa_gap`).
`CONTROLS` names what must fail them: a lower precision, or one departure
planted in the reference and judged in the system's place.

In a traced run the adapter runs one fill of the longest bucket alone under
the profiler before the window (`Server.trace_one_fill`), for the readers of
a program that the window's three traced seconds rarely hold whole.

`counters()` adds to the engine's lifetime counters what the step program
counts on the device: `moe_assignments_held`, `moe_assignments_total`,
`moe_expert_load_max_sum`, `moe_experts_touched_sum`, `kda_states_live`,
`kda_states_updated`, `kv_rows_live`, `kv_rows_read`. `gauges()` adds
`state_bytes_rows` / `state_bytes_fixed`."""
import glob
import os
import shutil

import numpy as np

from benchmark import costs_solar, trace
from benchmark.reference import blocks
from benchmark.reference import solar_open2_lm as ref
from benchmark.systems import gpt_decode_server
from benchmark.systems.glm5_decode_server import pick_sample

MODEL_NAME = "solar"
reference_sizes = costs_solar.sizes
FAULTS = ("beta_not_doubled", "decay_head_mean", "alpha_one",
          "conv_window_shifted", "k_norm_dropped", "held_shifted",
          "gqa_gate_dropped")
CONTROLS = ("float8",) + FAULTS


def model_config(m):
    from paddle_tpu.models import solar_open2

    return solar_open2.SolarOpen2Config.from_hf(
        m, router_experts=m["router_experts"], first_expert=m["first_expert"])


class Server(gpt_decode_server.Server):
    """The GPT server's `counters`, `live_slots` and `close` (they read the
    engine, whatever it serves), around this family's model and weights."""

    def __init__(self, run):
        from paddle_tpu import serving

        m, sv = reference_sizes(run.config), run.config["serving"]
        self.model, self.serving = m, sv
        self.cfg = cfg = model_config(m)
        weights = ref.make_weights(m, run.seed)
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
        if run.trace:
            self.trace_one_fill(run)
            run.mark("traced fill")

    def trace_one_fill(self, run):
        """One prompt that fills the longest bucket, through the engine and
        alone on the device, under the profiler: `run.obs["solar_fill"]`
        holds the reduced trace (benchmark/trace.py) and the prompt's
        length. A fill of this program runs a good part of a second, so an
        edge of the window's three traced seconds cuts most of those they
        touch; here every execution is whole. The same prompt goes through
        once before, so that the traced fill is not the program's first."""
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
        run.obs["solar_fill"] = {"plen": plen,
                                 "trace": trace.reduce(rows, 0.0)}

    def gauges(self):
        from paddle_tpu import observability as obs

        def g(name):
            return obs.gauge("serving.%s.%s" % (name, MODEL_NAME))

        return {"slot_utilization": g("decode.slot_utilization"),
                "queue_depth": g("queue_depth"),
                "state_bytes_rows": g("decode.state_bytes_rows"),
                "state_bytes_fixed": g("decode.state_bytes_fixed")}


def build(run):
    return Server(run)


class ServedLayers:
    """The SYSTEM's own programs over one sequence at a time, as the engine
    builds them (the same builders, lowering and kernels; a cache of one
    slot), with what the engine does not fetch as further fetches: per
    layer the stream before it, what its mixer adds and the held experts'
    part."""

    def __init__(self, sut, w, bucket):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.inference import Predictor
        from paddle_tpu.models import solar_open2 as solar

        self.cfg, self.bucket = sut.cfg, bucket
        cache_len = sut.serving["cache_len"]
        self.decl = sut.cfg.decode_model(cache_len).state
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            pv = solar.build_prefill(sut.cfg, bucket, cache_len)
            self.prefill = Predictor(
                fluid.default_main_program(), pv["feed_names"],
                pv["fetch_vars"] + pv["moe_routed"] + pv["attn_in"]
                + pv["attn_out"], scope=w, name="check_prefill_%d" % bucket)
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            sv = solar.build_step(sut.cfg, cache_len)
            self.step_names = sv["feed_names"]
            self.step = Predictor(
                fluid.default_main_program(), sv["feed_names"],
                sv["fetch_vars"] + sv["attn_in"] + sv["attn_out"], scope=w,
                name="check_step", donate_feeds=sv["cache_feed_names"])

    def run(self, prompt, served):
        """-> (routed: per layer (plen, H); stream, added: per layer (plen +
        len(served) - 1, H), the stream before the layer and what its mixer
        adds, the prompt's rows from the prefill program and one row from
        each decode step that takes served token j at position plen + j;
        handed: {layer: the delta-rule state (heads, D, D) the fill hands
        its slot}), host arrays."""
        n, n_state = self.cfg.num_layers, len(self.decl)
        plen = len(prompt)
        ids = np.zeros((1, self.bucket), np.int64)
        ids[0, :plen] = prompt
        out = self.prefill.run([ids, np.full((1, 1), plen, np.int64)],
                               return_numpy=False)
        state = list(out[1:1 + n_state])
        handed = {int(e.name.split("_")[1]): np.asarray(s)[0]
                  for e, s in zip(self.decl, state)
                  if e.name.startswith("kda_")}
        rest = out[1 + n_state:]
        routed = [np.asarray(r[:plen]) for r in rest[:n]]
        stream = [[np.asarray(a[0, :plen])] for a in rest[n:2 * n]]
        added = [[np.asarray(a[0, :plen])] for a in rest[2 * n:]]
        del out, rest
        for j, tok in enumerate(served[:-1]):
            feeds = dict(zip(self.step_names,
                             [np.full((1, 1), tok, np.int64),
                              np.full((1, 1), plen + j, np.int64)] + state))
            out = self.step.run(feeds, return_numpy=False)
            state = list(out[1:1 + n_state])
            rest = out[2 + n_state:]
            for i in range(n):
                stream[i].append(np.asarray(rest[i]))
                added[i].append(np.asarray(rest[n + i]))
        return (routed, [np.concatenate(r, 0) for r in stream],
                [np.concatenate(r, 0) for r in added], handed)


def compared_rows(plen, n_steps, count, seed):
    """The rows the mixers are compared at, in two groups: `count`
    positions of the prompt, the last among them (the fill's paths: the
    chunked scan, flash or dense attention), and every decoded position
    (the step's paths: the slot's state, windows and rows)."""
    rng = np.random.default_rng(int(seed) + 2)
    fill = np.unique(np.concatenate([
        rng.integers(0, plen, max(count - 1, 1)), [plen - 1]]))
    return {"fill": fill.astype(np.int32),
            "step": (plen + np.arange(n_steps)).astype(np.int32)}


def widest(values):
    """The largest of `values`; NaN if any is (a recurrence that blew up
    is no small gap: `max` would drop it); None of none."""
    if not values:
        return None
    return float("nan") if np.isnan(values).any() else float(max(values))


def check(run, sut, control=None):
    """Once the window has closed and the engine's weights and state are
    freed, five numbers over a seeded sample of finished requests (of the
    longest prompt bucket that was used, the longest request among them).

    `logit_gap_sigma`: one reference pass over each sampled prompt with its
    served tokens (teacher-forced); the widest gap, in units of the
    position's logit standard deviation, by which a served token lies below
    the reference's best. It holds the whole served path: the fill, the
    state and the rows it hands over, the decode step.

    `routed_gap`: the held experts' part of every layer over each sampled
    prompt, from the system's prefill program against the reference's own
    pass (`routed_gap` of the reference: per layer the median over the
    positions of the whole sample that the reference routes here, the
    largest layer).

    `kda_gap`: what each delta-rule layer's block adds to the stream (after
    the gate and Wo), the system's (`ServedLayers`: its prefill program
    over the prompt, the chunked scan from run to run; then its step
    program over the served tokens through the state and the windows of one
    slot) against the reference's recurrence, position by position, over
    the SAME stream (the system's own, so that only this block's arithmetic
    differs), at sampled prompt positions and at every decoded position, as
    `rms_gap`; the largest path, layer and request.

    `state_gap`: the float32 state the fill hands its slot against the
    reference's at the prompt's real end, as `rms_gap`; the largest layer
    and request.

    `gqa_gap`: what the softmax layer's gated attention adds, the same way
    (the fill's flash path, the step over the slot's rows).

    `control` names a lower precision or one planted departure (`FAULTS`,
    the reference's `m["fault"]`): the reference so computed is judged in
    the system's place: its own first choice for the served token, its own
    held experts' parts, blocks and states for the system's."""
    import jax.numpy as jnp

    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    m, cache_len = sut.model, sut.serving["cache_len"]
    out_len = run.traffic["max_new_tokens"]["max"]
    faulty = dict(m, fault=control) if control in FAULTS else m
    low = control if control in blocks.PRECISIONS else "float32"
    kinds = ref.layer_kinds(m)
    w = ref.make_weights(m, run.seed)
    served = None if control or not sample else ServedLayers(
        sut, w, sample[0]["bucket"])
    gaps, by_path, states = [], {}, {}
    routed = [[] for _ in kinds]
    for r in sample:
        prompt, toks = list(r["prompt"]), list(r["tokens"])
        plen, n = len(prompt), len(toks)
        seq = np.zeros((cache_len,), np.int32)
        seq[:plen + n] = prompt + toks
        at = np.minimum(plen - 1 + np.arange(out_len),
                        cache_len - 1).astype(np.int32)
        if control:
            xc, _, got_routed = ref.forward(
                w, seq, faulty, low, on_part=lambda j, p: np.asarray(
                    p[:plen].astype(jnp.bfloat16)))
            toks = np.asarray(ref.head_logits(
                w, xc, at, faulty, low))[:n].argmax(-1)
            del xc
        else:
            got_routed, streams, added, handed = served.run(prompt, toks)
        # each layer's part is compared as the reference makes it, not kept
        x, ref_streams, _ = ref.forward(
            w, seq, m, keep_streams=bool(control),
            on_part=lambda j, p: routed[j].append(ref.routed_errors(
                got_routed[j][:plen], p[:plen])))
        if control:
            streams = ref_streams
        gaps.append(ref.token_gaps(ref.head_logits(w, x, at, m), toks))
        del x, got_routed, ref_streams
        groups = compared_rows(plen, n - 1, chk["attention_positions"],
                               run.seed)
        rows = np.concatenate(list(groups.values()))
        for i, kind in enumerate(kinds):
            stream = jnp.zeros((cache_len, m["hidden_size"]),
                               jnp.bfloat16).at[:plen + n - 1].set(
                                   jnp.asarray(streams[i][:plen + n - 1]))
            want, want_state = ref.mixer_at(w, i, stream, rows, m, stop=plen)
            if control:
                got, got_state = ref.mixer_at(w, i, stream, rows, faulty,
                                              low, stop=plen)
            else:
                got = jnp.take(jnp.asarray(added[i]), rows, axis=0)
                got_state = handed.get(i)
            first = 0
            for path, at_rows in groups.items():
                part = slice(first, first + len(at_rows))
                first += len(at_rows)
                if len(at_rows):
                    by_path.setdefault("%s %s" % (kind, path), []).append(
                        ref.rms_gap(got[part], want[part]))
            if want_state is not None:
                width = want_state.shape[-1]
                states.setdefault(i, []).append(ref.rms_gap(
                    jnp.asarray(got_state).reshape(-1, width),
                    want_state.reshape(-1, width)))
        del streams
    by_path = {k: widest(v) for k, v in by_path.items()}
    states = {k: widest(v) for k, v in states.items()}
    run.note("kda_gap / gqa_gap by layer kind and program: %s; state_gap "
             "by layer: %s" % ({k: round(v, 5) for k, v in
                                sorted(by_path.items())},
                               {k: round(v, 5) for k, v in
                                sorted(states.items())}))
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

    def worst_of(kind):
        return widest([v for k, v in by_path.items() if k.startswith(kind)])

    limits = chk["limits"]
    for name, value in (
            ("logit_gap_sigma", worst if n_tok >= chk["min_tokens"] else None),
            ("routed_gap", ref.routed_gap(routed)),
            ("kda_gap", worst_of("kda")),
            ("state_gap", widest(list(states.values()))),
            ("gqa_gap", worst_of("gqa"))):
        run.compared[name] = {"value": value, "limit": limits[name]}
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
