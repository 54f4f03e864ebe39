"""System under test: the decoder served as users reach it, streaming
`POST /v1/models/gpt:generate` -> ServingServer -> ModelRegistry ->
DecodeEngine (continuous batching over slots, batch-1 bucketed prefill) ->
Predictor. The weights are the benchmark's own, made on the device from the
seed and handed to the engine as its checkpoint."""
import gc

import numpy as np

from benchmark.reference import gpt_lm

MODEL_NAME = "gpt"


class Server:
    def __init__(self, run):
        from paddle_tpu import serving
        from paddle_tpu.models import gpt

        m, sv = run.config["model"], run.config["serving"]
        self.model, self.serving = m, sv
        cfg = gpt.GPTConfig(
            vocab=m["vocab_size"], hidden=m["n_embd"], num_layers=m["n_layer"],
            heads=m["n_head"], ffn=m["n_inner"], max_len=m["n_positions"],
            dropout=0.0)
        weights = gpt_lm.make_weights(m, run.seed)
        run.mark("seeded weights")
        self.engine = serving.DecodeEngine(
            cfg, weights, slots=sv["slots"], cache_len=sv["cache_len"],
            prompt_buckets=run.traffic["prompt_buckets"],
            queue_capacity=sv["queue_capacity"], kv_dtype=sv["kv_dtype"],
            request_timeout_s=sv["request_timeout_s"], name=MODEL_NAME)
        del weights
        run.mark("engine built")
        self.warm_report = self.engine.warmup()
        run.mark("engine.warmup")
        self.registry = serving.ModelRegistry()
        self.registry.publish(MODEL_NAME, self.engine)
        self.server = serving.ServingServer(self.registry).start()  # port 0
        self.host, self.port = self.server.host, self.server.port
        self.path = "/v1/models/%s:generate" % MODEL_NAME

    def counters(self):
        """The engine's lifetime counters and the hub's histogram sums,
        for the readers to difference over the window."""
        from paddle_tpu import observability as obs

        out = dict(self.engine.stats())
        for h in ("ttft_seconds", "prefill_seconds", "step_seconds"):
            s = obs.histogram("serving.decode." + h) or {}
            out[h + ".count"] = s.get("count", 0)
            out[h + ".sum"] = s.get("sum", 0.0)
        return out

    def gauges(self):
        from paddle_tpu import observability as obs

        return {"slot_utilization": obs.gauge(
                    "serving.decode.slot_utilization." + MODEL_NAME),
                "queue_depth": obs.gauge("serving.queue_depth." + MODEL_NAME)}

    def live_slots(self):
        return self.engine.stats()["live_slots"]

    def close(self):
        try:
            self.server.stop(close_registry=False)
        finally:
            try:
                self.engine.stop(drain=False, timeout=10)
            finally:
                self.engine = self.registry = self.server = None
                gc.collect()


def build(run):
    return Server(run)


def pick_sample(finished, n, seed):
    """A sample of the finished requests, drawn from the seed, with the
    longest in it and one of every prompt bucket that was used."""
    if not finished:
        return []
    rng = np.random.default_rng(int(seed) + 1)
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"])
                                              + len(r["tokens"])))
    chosen = {by_len[0]["index"]: by_len[0]}
    for bucket in sorted({r["bucket"] for r in finished}):
        r = next(r for r in by_len if r["bucket"] == bucket)
        chosen.setdefault(r["index"], r)
    for i in rng.permutation(len(finished)):
        if len(chosen) >= n:
            break
        chosen.setdefault(finished[i]["index"], finished[i])
    return list(chosen.values())


def check(run, sut, control=None):
    """Once the window has closed and the engine's state is freed: one
    reference pass over each sampled prompt with its served tokens; the
    number compared is the widest gap, in units of the position's logit
    standard deviation, by which a served token lies below the reference's
    best. `control` names a lower precision whose own first choice is
    judged in place of the served token."""
    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    w = gpt_lm.make_weights(sut.model, run.seed)
    gaps = gpt_lm.served_gaps(
        w, [(r["prompt"], r["tokens"]) for r in sample], sut.model,
        seq_len=sut.serving["cache_len"],
        out_len=run.traffic["max_new_tokens"]["max"], control=control)
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
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
