"""System under test: the Kimi-VL decoder and its vision tower
(`paddle_tpu/models/kimi_vl.py`: latent attention over the whole cache, 64
sigmoid-routed SwiGLU experts all held plus a shared expert, a
native-resolution tower whose rows are spliced into the prompt) served as
users reach it, streaming `POST /v1/models/kimi:generate` with an `images`
field -> ServingServer (pixels to patches on the handler's thread) ->
ModelRegistry -> DecodeEngine (continuous batching over slots; a fill goes a
UNIT a turn beside live streams: an image through the tower, a chunk of
4,096 positions) -> Predictor: the same served path as
`gpt_decode_server.py`, with one `rows` entry a layer in the engine's
SlotCache that is no K and V (a latent row a position).

The weights are the benchmark's own, made on the device from the seed by the
reference (`benchmark/reference/kimi_vl.py`, bfloat16) and handed to the
engine as owned: 7.1 GB are not copied through the host.

`check()` compares eight numbers with the plain reference over sampled
finished requests, from the SYSTEM's own tower, chunk and step programs at
the timed sizes: the served tokens (`logit_gap_sigma`), the routed experts'
part (`routed_gap`), what each layer's attention block adds (`mla_gap`) and
what its second half adds (`ffn_gap`), the projector's rows (`tower_gap`),
what a tower block's attention adds (`tower_attn_gap`), the resized position
table (`table_gap`) and the stream into layer 0 (`splice_gap`). `CONTROLS` (`benchmark/controls_kimi_vl.py`) names what must
fail them.

`counters()` adds to the engine's lifetime counters what the step program
counts on the device: `moe_assignments_held`, `moe_assignments_total`,
`moe_expert_load_max_sum`, `moe_experts_touched_sum`, `latent_rows_live`,
`latent_rows_read`."""
import numpy as np

from benchmark import costs_kimi_vl, harness, traffic_media
from benchmark.controls_kimi_vl import CONTROLS, FAULTS  # noqa: F401
from benchmark.reference import blocks
from benchmark.reference import kimi_vl as ref
from benchmark.systems import gpt_decode_server
from benchmark.systems.solar_decode_server import widest

MODEL_NAME = "kimi"


def tower_blocks_of(m):
    """The tower's blocks whose attention is compared alone
    (`tower_attn_gap`): its first three."""
    return tuple(range(min(3, m["vision_config"]["num_hidden_layers"])))
reference_sizes = costs_kimi_vl.sizes


def model_config(m):
    try:
        from paddle_tpu.models import kimi_vl
    except ImportError as e:
        raise harness.Refuse(
            harness.EXIT_MANIFEST, "the system under test has no "
            "paddle_tpu.models.kimi_vl: %s" % e)
    return kimi_vl.KimiVlConfig.from_hf(m)


class Server(gpt_decode_server.Server):
    """The GPT server's `counters`, `live_slots` and `close` (they read the
    engine, whatever it serves), around this family's model and weights."""

    def __init__(self, run):
        self.model = m = reference_sizes(run.config)
        self.serving = sv = run.config["serving"]
        self.cfg = cfg = model_config(m)
        from paddle_tpu import serving

        self.patch, self.patch_buckets = cfg.vision.patch, cfg.vision.buckets
        self.table_side = min(cfg.vision.table)
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

    def gauges(self):
        from paddle_tpu import observability as obs

        def g(name):
            return obs.gauge("serving.%s.%s" % (name, MODEL_NAME))

        return {"slot_utilization": g("decode.slot_utilization"),
                "queue_depth": g("queue_depth"),
                "state_bytes_rows": g("decode.state_bytes_rows")}


def build(run):
    return Server(run)


class ServedLayers:
    """The SYSTEM's own programs over one sequence at a time, as the engine
    builds them (the same builders, lowering and kernels; a cache of one
    slot): the tower of an image's patch bucket, the CHUNK program over the
    prompt (every chunk of it, as a fill beside live streams runs) and the
    step program over the served tokens, with what the engine does not
    fetch as further fetches: the resized table; per layer the stream before
    it and what its attention block adds; per sparse layer the routed
    experts' part."""

    def __init__(self, sut, w, rows):
        import paddle_tpu.fluid as fluid
        from paddle_tpu.fluid.inference import Predictor
        from paddle_tpu.models import kimi_vl as kimi

        self.fluid, self.Predictor, self.kimi = fluid, Predictor, kimi
        self.cfg, self.w = sut.cfg, w
        self.cache_len = cache_len = sut.serving["cache_len"]
        model = sut.cfg.decode_model(cache_len)
        self.decl, self.enc, self.rows = model.state, model.encoder, rows
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            cv = kimi.build_chunk(sut.cfg, self.rows, cache_len)
            self.chunk_names = cv["feed_names"]
            self.chunk = Predictor(
                fluid.default_main_program(), cv["feed_names"],
                cv["fetch_vars"] + cv["moe_routed"] + cv["attn_in"]
                + cv["attn_out"], scope=w, name="check_chunk_%d" % self.rows,
                donate_feeds=cv["cache_feed_names"])
        with fluid.program_guard(fluid.Program(), fluid.Program()):
            sv = kimi.build_step(sut.cfg, cache_len)
            self.step_names = sv["feed_names"]
            self.step = Predictor(
                fluid.default_main_program(), sv["feed_names"],
                sv["fetch_vars"] + sv["attn_in"] + sv["attn_out"], scope=w,
                name="check_step", donate_feeds=sv["cache_feed_names"])
        self.towers, self.blocks = {}, None
        self.blocks_at = tower_blocks_of(sut.model)

    def _fed(self, image, b):
        g = self.cfg.vision.patch
        h, w = image.shape[0] // g, image.shape[1] // g
        fed = np.zeros((1, b, self.enc.patch_width), np.uint8)
        fed[0, :h * w] = self.enc.patchify(image)
        return [fed, np.asarray([[h, w]], np.int64)], h, w

    def tower(self, image):
        """One image's pixels -> the projector's real rows (h w / 4, hidden)
        float32 on the host, from the program of the image's patch bucket
        under the ENGINE's own name, feeds and fetches: the module the
        engine timed, found again in the XLA cache (a tower's 27 flash
        calls make it the largest program of the cell, PERF.md 7.19 l)."""
        g = self.cfg.vision.patch
        b = self.enc.bucket_for(image.shape[0] // g * (image.shape[1] // g))
        if b not in self.towers:
            fluid = self.fluid
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                tv = self.kimi.build_tower(self.cfg, b)
                self.towers[b] = self.Predictor(
                    fluid.default_main_program(), tv["feed_names"],
                    tv["fetch_vars"], scope=self.w, name="tower_%d" % b)
        feeds, h, w = self._fed(image, b)
        rows, = self.towers[b].run(feeds)
        return np.asarray(rows).astype(np.float32)[:h * w // 4]

    def tower_blocks(self, image):
        """-> (the resized table (h w, width) float32; per block of
        `tower_blocks_of` (the stream before it, what its attention adds), (h w,
        width)), ROW-MAJOR on the host: the tower's builder cut to its first
        blocks, over the largest patch bucket (one small program for every
        image; the whole depth is `tower`'s)."""
        import copy

        b = self.enc.buckets[-1]
        if self.blocks is None:
            fluid, cut = self.fluid, copy.copy(self.cfg)
            cut.vision = copy.copy(cut.vision)
            cut.vision.layers = len(self.blocks_at)
            with fluid.program_guard(fluid.Program(), fluid.Program()):
                tv = self.kimi.build_tower(cut, b)
                self.blocks = self.Predictor(
                    fluid.default_main_program(), tv["feed_names"],
                    [tv["table"]] + tv["attn_in"] + tv["attn_out"],
                    scope=self.w, name="check_tower_blocks_%d" % b)
        feeds, h, w = self._fed(image, b)
        out = self.blocks.run(feeds)

        def row_major(x):        # the program's rows are in merge order
            x = np.asarray(x).reshape(b, -1)[:h * w]
            x = x.reshape(h // 2, w // 2, 2, 2, -1)
            return x.transpose(0, 2, 1, 3, 4).reshape(h * w, -1)

        k = len(self.blocks_at)
        return row_major(out[0]), [
            (row_major(out[1 + j]), row_major(out[1 + k + j]))
            for j in range(k)]

    def run(self, prompt, served, media_rows):
        """`media_rows`: the system's own rows of the request's images, in
        order (n, hidden). -> (routed: per sparse layer (plen, H); stream,
        added: per layer (plen + len(served) - 1, H), the stream before the
        layer and what its attention block adds, the prompt's rows from the
        chunk program and one row from each decode step that takes served
        token j at position plen + j), host arrays."""
        import jax.numpy as jnp

        cfg, n = self.cfg, self.cfg.num_layers
        sparse, rows, plen = cfg.expert_layers, self.rows, len(prompt)
        prompt = np.asarray(prompt, np.int64)
        buf = np.zeros((cfg.media_rows, cfg.hidden), np.float32)
        buf[:len(media_rows)] = media_rows
        buf = jnp.asarray(buf, jnp.bfloat16)
        marked = prompt == cfg.media_id
        index = np.where(marked, np.cumsum(marked) - 1, -1).astype(np.int32)
        state = [jnp.zeros((1,) + tuple(e.shape), e.dtype) for e in self.decl]
        routed = [[] for _ in range(sparse)]
        stream, added = [[] for _ in range(n)], [[] for _ in range(n)]
        for at in range(0, plen, rows):
            k = min(rows, plen - at)
            ids = np.zeros((1, rows), np.int64)
            ids[0, :k] = prompt[at:at + k]
            ix = np.full((1, rows), -1, np.int32)
            ix[0, :k] = index[at:at + k]
            out = self.chunk.run(
                [ids, np.full((1, 1), k, np.int64),
                 np.full((1, 1), at, np.int64), buf, ix] + state,
                return_numpy=False)
            state = list(out[1:1 + n])
            rest = out[1 + n:]
            for j in range(sparse):
                routed[j].append(np.asarray(rest[j][:k]))
            for i in range(n):
                stream[i].append(np.asarray(rest[sparse + i][0, :k]))
                added[i].append(np.asarray(rest[sparse + n + i][0, :k]))
            del out, rest
        for j, tok in enumerate(served[:-1]):
            feeds = dict(zip(self.step_names,
                             [np.full((1, 1), tok, np.int64),
                              np.full((1, 1), plen + j, np.int64)] + state))
            out = self.step.run(feeds, return_numpy=False)
            state = list(out[1:1 + n])
            rest = out[2 + n:]
            for i in range(n):
                stream[i].append(np.asarray(rest[i]))
                added[i].append(np.asarray(rest[n + i]))
        return ([np.concatenate(r, 0) for r in routed],
                [np.concatenate(r, 0) for r in stream],
                [np.concatenate(r, 0) for r in added])


def pick_sample(finished, n, seed):
    """`n` of the finished requests drawn from the seed: first the longest
    of those that carry two images of unlike grids, then the longest of all,
    then any."""
    if not finished:
        return []
    by_len = sorted(finished, key=lambda r: -(len(r["prompt"])
                                              + len(r["tokens"])))
    unlike = [r for r in by_len
              if len({(h, w) for h, w, _ in r["images"]}) >= 2]
    chosen = {r["index"]: r for r in unlike[:1] + by_len[:1]}
    rng = np.random.default_rng(int(seed) + 1)
    for i in rng.permutation(len(finished)):
        if len(chosen) >= n:
            break
        chosen.setdefault(finished[i]["index"], finished[i])
    return list(chosen.values())[:n]


def compared_rows(plen, n_steps, count, first_chunk, seed):
    """The rows the attention blocks are compared at: `count` positions of
    the prompt past its first chunk (of the whole prompt where it has one
    chunk), the last among them (the chunk's path against earlier latents),
    and every decoded position (the step's path)."""
    rng = np.random.default_rng(int(seed) + 2)
    lo = first_chunk if plen > first_chunk else 0
    fill = np.unique(np.concatenate([
        rng.integers(lo, plen, max(count - 1, 1)), [plen - 1]]))
    return {"chunk": fill.astype(np.int32),
            "step": (plen + np.arange(n_steps)).astype(np.int32)}


def check(run, sut, control=None):
    """Once the window has closed and the engine's weights and state are
    freed, over a seeded sample of finished requests (one with two images of
    unlike grids, the longest):

    `logit_gap_sigma`: one reference pass over each sampled prompt (its
    media rows the REFERENCE's own tower's) with its served tokens; the
    widest gap, in units of the position's logit standard deviation, by
    which a served token lies below the reference's best: the whole served
    path (towers, splice, chunks, the rows handed over, the steps).
    `routed_gap`: the routed experts' part of every sparse layer over each
    sampled prompt, from the system's chunk program against the reference's
    own pass (per layer the median over positions, the largest layer).
    `mla_gap`: what each layer's attention block adds (after Wo), the
    system's chunk program (at positions past the first chunk: queries
    against latents an earlier chunk wrote) and step program (every decoded
    position) against the reference's block over the SAME stream (the
    system's own), as `rms_gap`; the largest layer, path and request.
    `ffn_gap`: what each layer's second half adds (the next layer's stream
    less this layer's and its attention block's, bfloat16 differences) at
    the same prompt rows against the reference's feed-forward over the
    system's own stream after attention; the MEDIAN row of a layer, the
    largest layer (routing is not continuous).
    `tower_gap`: the projector's rows of every image of the sample, the
    system's tower program of the image's bucket against the reference's
    tower, as `rms_gap`; the largest image.
    `tower_attn_gap` / `table_gap`: what the attention of each of the
    tower's first blocks adds, against the reference's block over the SAME
    stream (the system's own: on seeded weights a block's attention is 3% of
    its stream, and a wrong 2-D rotary term would lie inside `tower_gap`'s
    rounding), and the resized position table; the largest block and image.
    `splice_gap`: the stream into layer 0 over the prompt, the system's
    against the reference's embedding with the reference's rows spliced in.

    `control` names a lower precision or one planted departure (`FAULTS`):
    the reference so computed is judged in the system's place."""
    import jax.numpy as jnp

    chk = run.traffic["check"]
    sample = pick_sample(run.obs.get("finished", []),
                         chk["sample_requests"], run.seed)
    m, cache_len = sut.model, sut.serving["cache_len"]
    out_len = run.traffic["max_new_tokens"]["max"]
    faulty = dict(m, fault=control) if control in FAULTS else m
    low = control if control in blocks.PRECISIONS else "float32"
    n_layers, dense = m["num_hidden_layers"], m["first_k_dense_replace"]
    w = ref.make_weights(m, run.seed)
    first_chunk = min(sut.cfg.decode_model(cache_len).chunk_rows,
                      max(run.traffic["prompt_buckets"]))
    served = None if control or not sample else ServedLayers(
        sut, w, first_chunk)
    # every request's compared rows padded to one count: one program each
    fixed = chk["attention_positions"] + out_len
    gaps, mla, ffn, towers, tables, splices = [], {}, {}, [], [], []
    tower_attn, blocks_at = [], tower_blocks_of(m)
    routed = [[] for _ in range(n_layers - dense)]
    for r in sample:
        prompt, toks = list(r["prompt"]), list(r["tokens"])
        plen, n = len(prompt), len(toks)
        images = [traffic_media.pixels(h, wd, s, sut.patch)
                  for h, wd, s in r["images"]]
        want_rows, want_tables = ref.tower_rows(w, images, m)
        want_media = jnp.concatenate(want_rows, 0)
        if control:
            got_rows, got_tables = ref.tower_rows(w, images, faulty, low)
            got_media = jnp.concatenate(got_rows, 0)
            before = ref.tower_streams(w, images, m, blocks_at)
            got_blocks = [[(z[j], ref.tower_attention_at(
                w, j, z[j], h, wd, faulty, low)) for j in blocks_at]
                for z, (h, wd, _) in zip(before, r["images"])]
        else:
            got_rows = [served.tower(px) for px in images]
            got_media = np.concatenate(got_rows, 0)
            got_tables, got_blocks = zip(*(served.tower_blocks(px)
                                           for px in images))
        for got, want in zip(got_rows, want_rows):
            towers.append(ref.rms_gap(got, want))
        for got, want, blocks_of, (h, wd, _) in zip(
                got_tables, want_tables, got_blocks, r["images"]):
            tables.append(ref.rms_gap(
                np.asarray(got).reshape(h * wd, -1), want))
            for j, (z, added_by) in zip(blocks_at, blocks_of):
                tower_attn.append(ref.rms_gap(
                    added_by, ref.tower_attention_at(w, j, z, h, wd, m)))
        seq = np.zeros((cache_len,), np.int32)
        seq[:plen + n] = prompt + toks
        at = np.minimum(plen - 1 + np.arange(out_len),
                        cache_len - 1).astype(np.int32)
        if control:
            xc, streams, got_routed = ref.forward(
                w, seq, faulty, low, media=got_media, keep_streams=True,
                on_part=lambda j, p: np.asarray(
                    p[:plen].astype(jnp.bfloat16)))
            toks = np.asarray(ref.head_logits(
                w, xc, at, faulty, low))[:n].argmax(-1)
            del xc
            x0 = np.asarray(streams[0][:plen], np.float32)
        else:
            got_routed, streams, added = served.run(prompt, toks, got_media)
            x0 = np.asarray(streams[0][:plen], np.float32)
        splices.append(ref.rms_gap(
            x0, ref.embed(w, seq[:plen], m, want_media)))
        # each layer's part is compared as the reference makes it, not kept
        x, ref_streams, _ = ref.forward(
            w, seq, m, media=want_media, keep_streams=bool(control),
            on_part=lambda j, p: routed[j].append(ref.routed_errors(
                got_routed[j][:plen], p[:plen])))
        gaps.append(ref.token_gaps(ref.head_logits(w, x, at, m), toks))
        del x, got_routed
        if control:          # the blocks over the reference's own stream
            streams = ref_streams
        groups = compared_rows(plen, n - 1, chk["attention_positions"],
                               first_chunk, run.seed)
        rows = np.concatenate(list(groups.values()))
        real = len(rows)
        rows = np.pad(rows, (0, fixed - real), mode="edge")
        for i in range(n_layers):
            stream = jnp.zeros((cache_len, m["hidden_size"]),
                               jnp.bfloat16).at[:plen + n - 1].set(
                                   jnp.asarray(streams[i][:plen + n - 1]))
            want = ref.attention_at(w, i, stream, rows, m)
            if control:
                got = ref.attention_at(w, i, stream, rows, faulty, low)
            else:
                got = jnp.take(jnp.asarray(added[i]), rows, axis=0)
            first = 0
            for path, at_rows in groups.items():
                part = slice(first, first + len(at_rows))
                first += len(at_rows)
                if len(at_rows):
                    mla.setdefault(path, []).append(
                        ref.rms_gap(got[part], want[part]))
            # the layer's second half over the stream after ITS attention
            mid = (jnp.take(stream, rows, axis=0).astype(jnp.float32)
                   + jnp.asarray(got, jnp.float32)).astype(jnp.bfloat16)
            want_ffn = ref.ffn_at(w, i, mid, m)
            if control:
                got_ffn = ref.ffn_at(w, i, mid, faulty, low)
            elif i + 1 < n_layers:
                nxt = jnp.take(jnp.asarray(streams[i + 1][:plen + n - 1]),
                               rows, axis=0).astype(jnp.float32)
                got_ffn = nxt - mid.astype(jnp.float32)
            else:
                continue          # the last layer's sum is not brought out
            ffn.setdefault(i, []).append(float(np.median(
                ref.routed_errors(got_ffn[:real], want_ffn[:real]))))
        del streams
    by_path = {k: widest(v) for k, v in mla.items()}
    by_layer = {k: widest(v) for k, v in ffn.items()}
    run.note("mla_gap by program: %s; ffn_gap by layer: %s; tower_gap by "
             "image: %s; tower_attn_gap by image and block: %s; table_gap: "
             "%s; splice_gap: %s"
             % ({k: round(v, 5) for k, v in sorted(by_path.items())},
                {k: round(v, 5) for k, v in sorted(by_layer.items())},
                [round(v, 5) for v in towers],
                [round(v, 5) for v in tower_attn],
                [round(v, 7) for v in tables],
                [round(v, 5) for v in splices]))
    del w, served
    n_tok = int(sum(len(g) for g in gaps))
    worst = float(max((g.max() for g in gaps), default=np.inf))
    exact = sum(int((g == 0).sum()) for g in gaps)
    run.note("compared %d requests, %d served tokens, %d of them the "
             "reference's first choice; images %s; longest %d"
             % (len(sample), n_tok, exact,
                [[(h, wd) for h, wd, _ in r["images"]] for r in sample],
                max((len(r["prompt"]) + len(r["tokens"]) for r in sample),
                    default=0)))
    limits = chk["limits"]
    for name, value in (
            ("logit_gap_sigma", worst if n_tok >= chk["min_tokens"] else None),
            ("routed_gap", ref.routed_gap(routed)),
            ("mla_gap", widest(list(by_path.values()))),
            ("ffn_gap", widest(list(by_layer.values()))),
            ("tower_gap", widest(towers)),
            ("tower_attn_gap", widest(tower_attn)),
            ("table_gap", widest(tables)),
            ("splice_gap", widest(splices))):
        run.compared[name] = {"value": value, "limit": limits[name]}
    run.compared["tokens_short_of_sample"] = {
        "value": float(max(0, chk["min_tokens"] - n_tok)), "limit": 0.0}
    return gaps
