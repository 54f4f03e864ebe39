"""System under test: the hybrid state-space / attention / sparse-expert
decoder (`paddle_tpu/models/nemotron_h.py`) served as users reach it,
streaming `POST /v1/models/nemotron_h:generate` -> ServingServer ->
ModelRegistry -> DecodeEngine (continuous batching over slots, batch-1
bucketed prefill) -> Predictor: the same served path as
`gpt_decode_server.py`, with state of two kinds in the engine's SlotCache
(K/V rows of the attention block; a convolution window and a state-space
state per Mamba-2 block) and a chip's share of every expert layer.

The weights are the benchmark's own, made on the device from the seed by
the reference (`benchmark/reference/nemotron_h_lm.py`, bfloat16) and handed
to the engine as owned: 9.3 GB are not copied through the host.

`check()` compares two numbers with the plain reference: the served tokens
(`logit_gap_sigma`) and, layer by layer, the held experts' part
(`routed_gap`).

`counters()` adds to the engine's lifetime counters what the step program
counts on the device: `moe_assignments_held`, `moe_assignments_total`,
`moe_expert_load_max_sum`. `gauges()` adds `state_bytes_rows` /
`state_bytes_fixed`, the two kinds of slot state in bytes."""
import numpy as np

from benchmark.reference import nemotron_h_lm
from benchmark.systems import gpt_decode_server
from benchmark.systems.gpt_decode_server import pick_sample

MODEL_NAME = "nemotron_h"


def reference_sizes(config):
    """The configuration file's published keys plus the share this chip
    holds, as the reference takes them."""
    return dict(config["model"],
                router_experts=config["reduced_from"]["n_routed_experts"],
                first_expert=config["share"]["first_expert"])


class Server(gpt_decode_server.Server):
    """The GPT server's `counters`, `live_slots` and `close` (they read the
    engine, whatever it serves), around this family's model and weights."""

    def __init__(self, run):
        from paddle_tpu import serving
        from paddle_tpu.models import nemotron_h

        m, sv = reference_sizes(run.config), run.config["serving"]
        self.model, self.serving = m, sv
        self.cfg = cfg = nemotron_h.NemotronHConfig.from_hf(
            m, router_experts=m["router_experts"],
            first_expert=m["first_expert"])
        weights = nemotron_h_lm.make_weights(m, run.seed)
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
                "state_bytes_fixed": g("decode.state_bytes_fixed")}


def build(run):
    return Server(run)


def served_routed_parts(sut, w, bucket, prompts):
    """The held experts' part of every expert layer as the SYSTEM computes
    it over each prompt: the model's own prefill program for `bucket`
    (built as the engine builds it, the same lowering and kernels) with
    `build_prefill`'s `moe_routed` as its fetches, over the weights `w`.
    -> per prompt, per expert layer, float32 (len(prompt), latent)."""
    import paddle_tpu.fluid as fluid
    from paddle_tpu.fluid.inference import Predictor
    from paddle_tpu.models import nemotron_h

    with fluid.program_guard(fluid.Program(), fluid.Program()):
        pv = nemotron_h.build_prefill(sut.cfg, bucket,
                                      sut.serving["cache_len"])
        pred = Predictor(fluid.default_main_program(), pv["feed_names"],
                         pv["moe_routed"], scope=w,
                         name="routed_parts_%d" % bucket)
    out = []
    for prompt in prompts:
        ids = np.zeros((1, bucket), np.int64)
        ids[0, :len(prompt)] = prompt
        parts = pred.run([ids, np.full((1, 1), len(prompt), np.int64)])
        out.append([np.asarray(p[:len(prompt)], np.float32) for p in parts])
    return out


def check(run, sut, control=None):
    """Once the window has closed and the engine's weights and state are
    freed, two numbers over a seeded sample of finished requests.

    `logit_gap_sigma`: one reference pass over each sampled prompt with its
    served tokens (teacher-forced); the widest gap, in units of the
    position's logit standard deviation, by which a served token lies below
    the reference's best. It holds the whole served path (prefill, slot
    state, the decode step), but under the published initialisation the
    held experts' part is a hundredth of the stream's power and it hardly
    sees that layer.

    `routed_gap`: that layer alone. The held experts' part of every expert
    layer over each sampled prompt, from the system's prefill program
    (`served_routed_parts`) against the reference's
    (`nemotron_h_lm.routed_gap`: the median over positions, the largest
    layer and request).

    `control` names a lower precision: its own first choice is judged in
    place of the served token, its own held experts' part in place of the
    system's."""
    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    w = nemotron_h_lm.make_weights(sut.model, run.seed)
    cache_len = sut.serving["cache_len"]
    gaps = nemotron_h_lm.served_gaps(
        w, [(r["prompt"], r["tokens"]) for r in sample], sut.model,
        seq_len=cache_len,
        out_len=run.traffic["max_new_tokens"]["max"], control=control)
    prompts = [r["prompt"] for r in sample]

    def reference_parts(prompt, precision="float32"):
        seq = np.zeros((cache_len,), np.int32)
        seq[:len(prompt)] = prompt
        return [p[:len(prompt)] for p in nemotron_h_lm.routed_parts(
            w, seq, sut.model, precision)]

    judged = ([reference_parts(p, control) for p in prompts] if control
              else served_routed_parts(
                  sut, w, max(run.traffic["prompt_buckets"]), prompts))
    routed = max((nemotron_h_lm.routed_gap(got, reference_parts(p))
                  for got, p in zip(judged, prompts)), default=None)
    del w
    n_tok = int(sum(len(g) for g in gaps))
    worst = float(max((g.max() for g in gaps), default=np.inf))
    exact = sum(int((g == 0).sum()) for g in gaps)
    run.note("compared %d requests, %d served tokens, %d of them the "
             "reference's first choice; buckets %s; longest %d"
             % (len(sample), n_tok, exact,
                sorted({r["bucket"] for r in sample}),
                max((len(r["prompt"]) + len(r["tokens"]) for r in sample),
                    default=0)))
    run.compared["logit_gap_sigma"] = {
        "value": worst if n_tok >= chk["min_tokens"] else None,
        "limit": chk["limits"]["logit_gap_sigma"]}
    run.compared["routed_gap"] = {
        "value": routed, "limit": chk["limits"]["routed_gap"]}
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
