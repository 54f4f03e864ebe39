"""Kimi-VL family (a DeepSeek-V3 style decoder: multi-head latent attention
over the WHOLE cache, sigmoid-routed SwiGLU experts plus a shared expert;
and a native-resolution vision tower whose rows are spliced into the
prompt) as slot-decode programs for ``serving.DecodeEngine``.

Decoder layer, pre-norm residual twice over: ``y = x + Wo Attn(u)`` with ``u
= RMSNorm(x)``, then ``y + FF(RMSNorm(y))``. The query is ONE matrix (no low
rank), per head ``[q_nope | q_rope]``, the second part turned by the
token's position (interleaved pairs). Keys and values come from one latent a
position: ``[ckv | k_rope] = u Wkv_a``, ``ckv = RMSNorm(ckv)``, ``k_rope``
turned and shared by all heads; head h's key is ``[ckv Wuk_h | k_rope]`` and
its value ``ckv Wuv_h``; every query sees ALL earlier positions
(``layers.mla_attention`` without a selection). ``FF``: the first
``first_dense`` layers a SwiGLU MLP; the others a router over all
``num_experts`` (sigmoid scores, the ``top_k`` largest of score + correction,
renormalised and scaled) over SwiGLU experts, every one of which this chip
holds (``held`` = all of them: the partial sum of ``parallel.moe.
held_experts_ffn`` is then the whole sum), plus a shared SwiGLU expert with
weight 1. Final RMSNorm, untied head. Media rows take plain positions.

The tower (:func:`build_tower`) is a pre-norm ViT over the patches of ONE
image of any even grid ``h x w`` (the grid a feed; a program a patch
bucket): a linear patch embedding, the learned position table resized to the
grid by torch's bicubic, blocks ``z + Wo Attn(LN0 z)``, ``z + W2 gelu_tanh(W1
LN1 z)`` with a 2-D rotary term and attention over the image's own patches, a
final LayerNorm, a 2 x 2 merge and a two-layer projector to the decoder's
width. A program's rows hold the patches in MERGE ORDER
(``ops/vision_ops.py``; :func:`patches_in_merge_order` makes them on the
host), so the merge is a reshape. Its rows REPLACE the embedding's at the
prompt's ``media_id`` positions, in order (``kimi.splice``): the fill
programs feed the request's rows ``(media_rows, hidden)`` and, per position,
the row it takes or -1.

The state a sequence carries is one ``rows`` entry a layer that is no K and
V: ``lat_<i>`` ``(cache_len, latent_width)``, a position's ``[ckv | k_rope]``
and zeros up to a multiple of 128. A prompt, and a CHUNK of one
(:func:`build_chunk`: the chunk's rows written at ``start``, its queries
against the rows so far), take the expanded path; a step the absorbed path
over the slot's rows ``<= pos`` where they lie, no gather.

Weights are bfloat16; products take bfloat16 operands and accumulate in
float32; the router, the rotary terms, the norms' statistics, the table's
resize and the logits are float32; the residual streams and the cache are
bfloat16.

Name scopes: ``kimi.mla``, ``kimi.mlp``, ``kimi.experts.route``,
``kimi.experts.experts``, ``kimi.experts.shared``, ``kimi.head``,
``kimi.splice``, ``kimi.tower.patch``, ``kimi.tower.attn``,
``kimi.tower.mlp``, ``kimi.tower.merge``, ``kimi.project``.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from . import decoder_blocks as blocks
from .decode_utils import (DecodeModel, MediaEncoder, StateEntry,
                           require_rows_only, update_cache)

__all__ = ["KimiVlConfig", "VisionConfig", "build_prefill", "build_chunk",
           "build_step", "build_tower", "param_shapes",
           "patches_in_merge_order"]

DTYPE = "bfloat16"
CHUNK_ROWS = 4096
MAX_IMAGES = 4      # a request's images: the media buffer holds their rows


class VisionConfig:
    """The tower's sizes under the names of the family's ``vision_config``;
    ``in_token_limit`` the most patches an image may hold."""

    def __init__(self, num_hidden_layers, hidden_size, num_attention_heads,
                 intermediate_size, patch_size, init_pos_emb_height,
                 init_pos_emb_width, merge_kernel_size=(2, 2),
                 in_token_limit=4096, **_):
        self.layers, self.hidden = int(num_hidden_layers), int(hidden_size)
        self.heads, self.ffn = int(num_attention_heads), int(
            intermediate_size)
        self.patch = int(patch_size)
        self.table = (int(init_pos_emb_height), int(init_pos_emb_width))
        self.merge = tuple(int(k) for k in merge_kernel_size)
        if self.merge != (2, 2):
            raise ValueError("merge_kernel_size %r is not built (only 2 x 2)"
                             % (self.merge,))
        if self.hidden % self.heads or (self.hidden // self.heads) % 4:
            raise ValueError("a head (%d / %d) is turned in pairs of pairs"
                             % (self.hidden, self.heads))
        self.max_patches = min(int(in_token_limit),
                               self.table[0] * self.table[1])
        # a program a bucket: the limit, its half and its quarter
        self.buckets = tuple(sorted({
            max(self.max_patches // 4, 4), max(self.max_patches // 2, 4),
            self.max_patches}))
        self.max_rows = self.max_patches // 4


class KimiVlConfig:
    """Sizes under the names of the family's ``config.json``. Every routed
    expert is held (``held`` = ``(0, num_experts)``)."""

    def __init__(self, num_layers, first_dense, vocab, hidden, heads, kv_rank,
                 nope_dim, rope_dim, v_dim, ffn, moe_ffn, shared_ffn,
                 num_experts, top_k, theta, vision, media_id,
                 routed_scale=1.0, eps=1e-5):
        self.num_layers, self.first_dense = int(num_layers), int(first_dense)
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError("first_dense %d outside [0, %d layers]"
                             % (self.first_dense, self.num_layers))
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.heads, self.kv_rank = int(heads), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        if self.rope_dim % 2:
            raise ValueError("the rotary part (%d) is turned in pairs"
                             % self.rope_dim)
        self.ffn, self.moe_ffn = int(ffn), int(moe_ffn)
        self.shared_ffn = int(shared_ffn)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (0, self.num_experts)
        self.theta = float(theta)
        self.routed_scale, self.eps = float(routed_scale), float(eps)
        self.vision = vision
        self.media_id = int(media_id)
        if not 0 <= self.media_id < self.vocab:
            raise ValueError("media_id %d outside the vocabulary (%d)"
                             % (self.media_id, self.vocab))
        self.max_images = MAX_IMAGES
        # rows of the media buffer a fill program is fed: every image's rows
        # one after the other, and room for the padded tail of the last
        # image's bucket (the engine writes a bucket's rows whole)
        self.media_rows = (self.max_images + 1) * vision.max_rows

    @classmethod
    def from_hf(cls, m):
        """From a dict with the keys of the published ``config.json`` plus
        ``vision_config`` and ``media_placeholder_token_id``. What the keys
        name and this file does not build is refused."""
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("moe_layer_freq", 1), ("q_lora_rank", None),
                          ("rope_scaling", None),
                          ("tie_word_embeddings", False)):
            if m.get(key, want) != want:
                raise ValueError("%s = %r is not built (only %r)"
                                 % (key, m[key], want))
        return cls(
            num_layers=m["num_hidden_layers"],
            first_dense=m["first_k_dense_replace"], vocab=m["vocab_size"],
            hidden=m["hidden_size"], heads=m["num_attention_heads"],
            kv_rank=m["kv_lora_rank"], nope_dim=m["qk_nope_head_dim"],
            rope_dim=m["qk_rope_head_dim"], v_dim=m["v_head_dim"],
            ffn=m["intermediate_size"], moe_ffn=m["moe_intermediate_size"],
            shared_ffn=m["moe_intermediate_size"] * m["n_shared_experts"],
            num_experts=m["n_routed_experts"],
            top_k=m["num_experts_per_tok"], theta=m["rope_theta"],
            vision=VisionConfig(**m["vision_config"]),
            media_id=m["media_placeholder_token_id"],
            routed_scale=m.get("routed_scaling_factor", 1.0),
            eps=m.get("rms_norm_eps", 1e-5))

    @property
    def latent_width(self):
        """Width of a latent row in the cache: ``kv_rank + rope_dim`` values,
        then zeros up to a whole number of 128 lanes (PERF.md, PR 39)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def expert_layers(self):
        return self.num_layers - self.first_dense

    def decode_model(self, cache_len, kv_dtype="fp32"):
        """Builders, state declaration and the encoder for
        ``serving.DecodeEngine``."""
        import jax.numpy as jnp

        state = [StateEntry("lat_%d" % i, (int(cache_len), self.latent_width),
                            jnp.bfloat16, "rows")
                 for i in range(self.num_layers)]
        v = self.vision
        model = DecodeModel(
            self, state, build_prefill, build_step,
            step_counters=self._step_counters, rows_are_kv=False,
            build_chunk=build_chunk,
            chunk_rows=CHUNK_ROWS,
            encoder=MediaEncoder(
                build=build_tower, buckets=v.buckets, media_id=self.media_id,
                width=self.hidden, merge=v.merge, max_grid=v.table,
                buffer_rows=self.media_rows, max_images=self.max_images,
                patch_width=3 * v.patch * v.patch, patch=v.patch,
                patchify=lambda px: patches_in_merge_order(px, v.patch)))
        if kv_dtype != "fp32":
            require_rows_only(model, "kv_dtype=%r" % (kv_dtype,))
        return model

    def _step_counters(self, aux, live):
        """The step's counts (:func:`build_step`) -> lifetime counters."""
        moe = np.asarray(aux[:-2]).reshape(self.expert_layers, -1)
        return {"moe_assignments_held": int(moe[:, 0].sum()),
                "moe_assignments_total":
                    int(live) * self.top_k * self.expert_layers,
                "moe_expert_load_max_sum": int(moe[:, 1].sum()),
                "moe_experts_touched_sum": int(moe[:, 2].sum()),
                "latent_rows_live": int(aux[-2]),
                "latent_rows_read": int(aux[-1])}


def patches_in_merge_order(pixels, patch=14):
    """uint8 pixels (patch h, patch w, 3) of one image -> uint8 (h w, 3
    patch^2): a patch a row, channel first inside it as the family's
    convolution reads it, the rows in MERGE ORDER (the four patches of the 2
    x 2 group (R, C) one after the other, row-major inside it, the groups
    row-major): what :func:`build_tower` is fed. Host code (numpy)."""
    pixels = np.asarray(pixels, np.uint8)
    h, w = pixels.shape[0] // patch, pixels.shape[1] // patch
    x = pixels.reshape(h // 2, 2, patch, w // 2, 2, patch, 3)
    x = x.transpose(0, 3, 1, 4, 6, 2, 5)       # R, C, dr, dc, ch, py, px
    return np.ascontiguousarray(x).reshape(h * w, 3 * patch * patch)


# -- the tower ---------------------------------------------------------------
def _dense(x, size, name, nfd=2):
    return layers.fc(x, size, num_flatten_dims=nfd,
                     param_attr=ParamAttr(name=name + ".w"),
                     bias_attr=ParamAttr(name=name + ".b"))


def _ln(x, name, axis=2):
    return layers.layer_norm(x, begin_norm_axis=axis, epsilon=1e-5,
                             param_attr=ParamAttr(name=name + ".w"),
                             bias_attr=ParamAttr(name=name + ".b"))


def build_tower(cfg, patches):
    """The tower and the projector over ONE image padded to ``patches``
    rows. Feeds ``kimi_tower_patches`` (1, patches, 3 x patch^2) uint8 (merge
    order, zeros past ``h w``) and ``kimi_tower_grid`` (1, 2) int64 ``[h,
    w]``. Fetches the projector's rows ``(patches / 4, hidden)`` bfloat16,
    row r the merged row of group r (row-major over ``(h / 2, w / 2)``);
    rows past ``h w / 4`` are padding's. For whoever wants them as further
    fetches: ``table`` the resized position table ``(patches, width)``
    float32; ``attn_in`` / ``attn_out`` per block the stream before it and
    what its attention adds, ``(1, patches, width)`` in merge order."""
    v = cfg.vision
    if patches % 4:
        raise ValueError("a patch bucket (%d) holds whole 2 x 2 groups"
                         % patches)
    d, dh = v.hidden, v.hidden // v.heads
    raw = fluid.data("kimi_tower_patches",
                     shape=[1, patches, 3 * v.patch * v.patch], dtype="uint8")
    grid = fluid.data("kimi_tower_grid", shape=[1, 2], dtype="int64")
    with fluid.name_scope("kimi.tower.patch"):
        # x / 255 then (. - 0.5) / 0.5
        x = layers.cast(layers.scale(layers.cast(raw, "float32"),
                                     scale=2.0 / 255.0, bias=-1.0), DTYPE)
        table = layers.bicubic_table("kimi.vit.pos", list(v.table) + [d],
                                     grid, patches, DTYPE)
        z = layers.cast(layers.elementwise_add(
            layers.cast(_dense(x, d, "kimi.vit.patch"), "float32"),
            layers.unsqueeze(table, [0])), DTYPE)
    attn_in, attn_out = [], []
    for j in range(v.layers):
        n = "kimi.vit%d" % j
        attn_in.append(z)
        with fluid.name_scope("kimi.tower.attn"):
            qkv = _dense(_ln(z, n + ".ln0"), 3 * d, n + ".qkv")
            q, k, val = (layers.slice(qkv, [2], [i * d], [(i + 1) * d])
                         for i in range(3))
            q, k = (layers.reshape(layers.rotary_2d(
                layers.reshape(t, [1, patches, v.heads, dh]), grid),
                [1, patches, d]) for t in (q, k))
            attn_out.append(_dense(
                layers.tower_attention(q, k, val, grid, v.heads), d,
                n + ".o"))
            z = layers.elementwise_add(z, attn_out[-1])
        with fluid.name_scope("kimi.tower.mlp"):
            z = layers.elementwise_add(z, _dense(layers.gelu(
                _dense(_ln(z, n + ".ln1"), v.ffn, n + ".fc1"),
                approximate=True), d, n + ".fc2"))
    with fluid.name_scope("kimi.tower.merge"):
        z = _ln(_ln(z, "kimi.vit.ln_f"), "kimi.proj.ln")
        z = layers.reshape(z, [patches // 4, 4 * d])
    with fluid.name_scope("kimi.project"):
        rows = _dense(layers.gelu(_dense(z, 4 * d, "kimi.proj.fc1", 1)),
                      cfg.hidden, "kimi.proj.fc2", 1)
    return {"patches": raw, "grid": grid, "rows": rows, "table": table,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["kimi_tower_patches", "kimi_tower_grid"],
            "fetch_vars": [rows]}


# -- the decoder -------------------------------------------------------------
def _attention(u, lat_of, lead, cfg, n, first=None, pos=None, offset=None):
    """A layer's attention over u (B, lead, H), whose row t stands at
    position ``first[b] + t`` (row t without). ``lat_of(new rows)`` -> the
    rows the queries go over (the new ones themselves, or the cache they
    were written into); ``pos`` / ``offset`` as ``layers.mla_attention``'s.
    -> (Wo Attn (B, lead, H), those rows)."""
    with fluid.name_scope("kimi.mla"):
        width = cfg.nope_dim + cfg.rope_dim
        q = blocks.turned(blocks.fc(u, cfg.heads * width, n + ".mla.q", 2),
                          lead, cfg.heads, width, cfg, False, first)
        lat = lat_of(blocks.latent_rows(u, lead, cfg, n, first))
        a = layers.mla_attention(q, lat, None, n + ".mla", cfg.heads,
                                 cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
                                 cfg.v_dim, pos=pos, offset=offset)
        return blocks.fc(a, cfg.hidden, n + ".mla.o", 2), lat


def _feed_forward(w, cfg, i, live, counts, routed):
    """The layer's second half on flat rows (T, H)."""
    n = "kimi%d" % i
    if i < cfg.first_dense:
        with fluid.name_scope("kimi.mlp"):
            return blocks.swiglu(w, cfg.ffn, cfg.hidden, n + ".mlp")
    part = blocks.routed_in_calls(
        w, live, counts, cfg.num_experts, cfg.top_k, cfg.held, cfg.moe_ffn,
        n + ".moe", "kimi.experts", scale=cfg.routed_scale)
    routed.append(part)
    with fluid.name_scope("kimi.experts.shared"):
        shared = blocks.swiglu(w, cfg.shared_ffn, cfg.hidden,
                               n + ".moe.shared")
    return layers.elementwise_add(part, shared)


def _embed(ids, cfg):
    return layers.embedding(ids, size=[cfg.vocab, cfg.hidden], dtype=DTYPE,
                            param_attr=ParamAttr(name="kimi.emb"))


def _spliced(ids, rows, cfg, prefix):
    """The stream into layer 0 of a fill program over ``rows`` positions:
    the embedding's rows, those of the positions that take a media row
    replaced by it. Feeds ``<prefix>_media`` (cfg.media_rows, hidden) and
    ``<prefix>_media_index`` (1, rows) int32, -1 where the position keeps
    its embedding. -> (x (1, rows, H), the two feeds' names)."""
    media = fluid.data(prefix + "_media", shape=[cfg.media_rows, cfg.hidden],
                       dtype=DTYPE)
    index = fluid.data(prefix + "_media_index", shape=[1, rows],
                       dtype="int32")
    x = layers.reshape(_embed(ids, cfg), [rows, cfg.hidden])
    with fluid.name_scope("kimi.splice"):
        at = layers.reshape(index, [rows])
        takes = layers.reshape(layers.greater_equal(
            at, layers.fill_constant([1], "int32", 0)), [rows, 1])
        picked = layers.gather(media, layers.elementwise_max(
            at, layers.fill_constant([1], "int32", 0)))
        x = layers.where(takes, picked, x)
    return (layers.reshape(x, [1, rows, cfg.hidden]),
            [prefix + "_media", prefix + "_media_index"])


def _head(x, cfg):
    with fluid.name_scope("kimi.head"):
        return blocks.greedy_head(x, cfg.vocab, cfg.eps, "kimi.norm_f",
                                  "kimi.head")


def _last_row(x, n):
    """x (1, T, H), n (1, 1) real rows -> (1, H) the row n - 1 (row 0 of a
    program that holds no real row)."""
    from .gpt import _row_coords

    last = layers.elementwise_max(
        layers.elementwise_sub(n, layers.fill_constant([1], "int64", 1)),
        layers.fill_constant([1], "int64", 0))
    return layers.gather_nd(x, _row_coords(last))


def build_prefill(cfg, prompt_len, cache_len):
    """Slot-prefill program: one pass over a right-padded prompt bucket.
    Feeds ``kimi_prefill_ids`` (1, prompt_len) int64, ``kimi_prefill_len``
    (1, 1) and the media feeds (:func:`_spliced`; ``media_feed_names``).
    Fetches the greedy token after the last real position and the
    sequence's state: per layer the latent rows ``(1, cache_len,
    latent_width)``, zero past ``len``. ``moe_routed`` names, per sparse
    layer, the experts' part ``(prompt_len, hidden)``; ``attn_in`` /
    ``attn_out``, per layer, the stream before the layer and what its
    attention block adds to it ``(1, prompt_len, hidden)``: for whoever wants
    to fetch them (the engine does not)."""
    if not 1 <= prompt_len <= cache_len:
        raise ValueError("need 1 <= prompt_len (%d) <= cache_len (%d)"
                         % (prompt_len, cache_len))
    ids = fluid.data("kimi_prefill_ids", shape=[1, prompt_len], dtype="int64")
    plen = fluid.data("kimi_prefill_len", shape=[1, 1], dtype="int64")
    x, media_names = _spliced(ids, prompt_len, cfg, "kimi_prefill")
    steps = layers.unsqueeze(layers.range(0, prompt_len, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, plen), DTYPE)   # (1, P)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [prompt_len, 1])
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i in range(cfg.num_layers):
        n = "kimi%d" % i
        attn_in.append(x)
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        y, lat = _attention(u, lambda new: new, prompt_len, cfg, n)
        rows = layers.elementwise_mul(lat, valid3)
        if cache_len > prompt_len:
            rows = layers.concat([rows, layers.fill_constant(
                [1, cache_len - prompt_len, rows.shape[2]], DTYPE, 0.0)],
                axis=1)
        state.append(rows)
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.reshape(
            layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps),
            [prompt_len, cfg.hidden])
        y = _feed_forward(w, cfg, i, live, counts, routed)
        x = layers.elementwise_add(
            x, layers.reshape(y, [1, prompt_len, cfg.hidden]))
    logits, nxt = _head(_last_row(x, plen), cfg)
    return {"ids": ids, "len": plen, "next": nxt, "logits": logits,
            "state": state, "moe_counts": counts, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["kimi_prefill_ids", "kimi_prefill_len"]
            + media_names,
            "media_feed_names": media_names, "fetch_vars": [nxt] + state}


def build_chunk(cfg, rows, cache_len):
    """A prefill that CONTINUES: ``rows`` positions of a prompt from the
    latent rows the chunks before wrote (zeros before the first). Feeds
    ``kimi_chunk_ids`` (1, rows) int64, ``kimi_chunk_len`` (1, 1) the real
    tokens of THIS chunk (right-padded), ``kimi_chunk_start`` (1, 1) the row
    of its first position (``start + rows <= cache_len``), the media feeds
    (``media_feed_names``; the index is the chunk's own positions') and the
    sequence's state, one feed ``(1, cache_len, latent_width)`` a layer
    (``cache_feed_names``), all donated. Each layer's new latent rows are
    written at ``start`` (zeros past ``len``) and the chunk's queries go
    against the rows ``[0, start + len)``, expanded
    (``layers.mla_attention(offset=start)``); the routed layer is one call.
    Fetches the greedy token after the chunk's last real position and the
    state carried on: after the last chunk, what :func:`build_prefill`
    fetches (the same rows; there is no carried float32 state)."""
    if not 1 <= rows <= cache_len:
        raise ValueError("need 1 <= rows (%d) <= cache_len (%d)"
                         % (rows, cache_len))
    ids = fluid.data("kimi_chunk_ids", shape=[1, rows], dtype="int64")
    clen = fluid.data("kimi_chunk_len", shape=[1, 1], dtype="int64")
    start = fluid.data("kimi_chunk_start", shape=[1, 1], dtype="int64")
    x, media_names = _spliced(ids, rows, cfg, "kimi_chunk")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("kimi_chunk_" + e.name, shape=[1] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    steps = layers.unsqueeze(layers.range(0, rows, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, clen), DTYPE)   # (1, rows)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [rows, 1])
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i in range(cfg.num_layers):
        n = "kimi%d" % i
        attn_in.append(x)
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        y, lat = _attention(
            u, lambda new, i=i: update_cache(
                feeds[i], layers.elementwise_mul(new, valid3), pos=start),
            rows, cfg, n, first=start, offset=start)
        state.append(lat)
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.reshape(
            layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps),
            [rows, cfg.hidden])
        y = _feed_forward(w, cfg, i, live, counts, routed)
        x = layers.elementwise_add(
            x, layers.reshape(y, [1, rows, cfg.hidden]))
    logits, nxt = _head(_last_row(x, clen), cfg)
    names = [f.name for f in feeds]
    return {"ids": ids, "len": clen, "start": start, "next": nxt,
            "logits": logits, "state": state, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["kimi_chunk_ids", "kimi_chunk_len",
                           "kimi_chunk_start"] + media_names + names,
            "media_feed_names": media_names,
            "cache_feed_names": names, "fetch_vars": [nxt] + state}


def build_step(cfg, cache_len):
    """One decode step for all slots. Feeds ``kimi_step_tok`` /
    ``kimi_step_pos`` (S, 1) int64 and the latent caches, one feed a layer
    (``cache_feed_names``), all donated: each slot's new latent row (turned
    by the slot's ``pos``) is written at row ``pos`` and the query goes over
    the slot's rows ``<= pos`` where they lie (the absorbed path, no
    gather). Fetches the greedy tokens, the updated state, and ``counts``
    int32: per sparse layer the live tokens' assignments, the largest count
    on one expert, the experts that got any and the sorted rows the experts'
    loops covered; then, summed over the layers, the latent rows that hold a
    position of a live slot (``pos + 1``) and the latent rows the step went
    over (``cache_len`` a slot, live or not). A slot with ``pos == 0`` is
    dead: its row is computed and ignored, and it is routed to no expert.
    ``attn_in`` / ``attn_out`` as :func:`build_prefill`'s, ``(S, hidden)``."""
    tok = fluid.data("kimi_step_tok", shape=[None, 1], dtype="int64")
    pos = fluid.data("kimi_step_pos", shape=[None, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("kimi_step_" + e.name, shape=[None] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    x = layers.reshape(_embed(tok, cfg), [-1, cfg.hidden])       # (S, H)
    alive = layers.greater_than(pos, layers.fill_constant([1], "int64", 0))
    live = layers.cast(alive, DTYPE)                             # (S, 1)
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i in range(cfg.num_layers):
        n = "kimi%d" % i
        attn_in.append(x)
        u = layers.unsqueeze(
            layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps), [1])
        y, lat = _attention(
            u, lambda new, i=i: update_cache(feeds[i], new, pos=pos,
                                             per_row=True),
            1, cfg, n, first=pos, pos=pos)
        y = layers.squeeze(y, [1])
        state.append(lat)
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps)
        x = layers.elementwise_add(
            x, _feed_forward(w, cfg, i, live, counts, routed))
    logits, nxt = _head(x, cfg)
    held_rows = layers.elementwise_mul(
        layers.cast(alive, "int64"),
        layers.scale(pos, scale=1.0, bias=1.0))                  # (S, 1)

    def total(v):
        return layers.reshape(layers.cast(layers.reduce_sum(
            layers.scale(v, scale=float(cfg.num_layers))), "int32"), [1])

    aux = layers.concat(
        [layers.reshape(c, [-1]) for c in counts]
        + [total(held_rows),
           total(layers.fill_constant_batch_size_like(
               pos, shape=[-1, 1], dtype="int64", value=int(cache_len)))],
        axis=0)
    names = [f.name for f in feeds]
    return {"tok": tok, "pos": pos, "next": nxt, "logits": logits,
            "state": state, "counts": aux, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["kimi_step_tok", "kimi_step_pos"] + names,
            "cache_feed_names": names,
            "fetch_vars": [nxt] + state + [aux]}


def param_shapes(cfg):
    """{name: (shape, dtype name)} of every parameter the programs read:
    what a checkpoint for this model holds."""
    h = cfg.hidden
    out = {"kimi.emb": ((cfg.vocab, h), DTYPE),
           "kimi.head.w": ((h, cfg.vocab), DTYPE),
           "kimi.norm_f.w": ((h,), DTYPE)}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), DTYPE),
                    name + ".w3.w": ((h, width), DTYPE),
                    name + ".w2.w": ((width, h), DTYPE)})

    def dense(name, a, b):
        out.update({name + ".w": ((a, b), DTYPE), name + ".b": ((b,), DTYPE)})

    def ln(name, d):
        out.update({name + ".w": ((d,), DTYPE), name + ".b": ((d,), DTYPE)})

    for i in range(cfg.num_layers):
        n = "kimi%d" % i
        out.update({
            n + ".attn_norm.w": ((h,), DTYPE),
            n + ".mlp_norm.w": ((h,), DTYPE),
            n + ".mla.q.w": ((h, cfg.heads * (cfg.nope_dim + cfg.rope_dim)),
                             DTYPE),
            n + ".mla.kv_a.w": ((h, cfg.kv_rank + cfg.rope_dim), DTYPE),
            n + ".mla.kv_norm.w": ((cfg.kv_rank,), DTYPE),
            n + ".mla.uk.w": ((cfg.kv_rank, cfg.heads * cfg.nope_dim), DTYPE),
            n + ".mla.uv.w": ((cfg.kv_rank, cfg.heads * cfg.v_dim), DTYPE),
            n + ".mla.o.w": ((cfg.heads * cfg.v_dim, h), DTYPE)})
        if i < cfg.first_dense:
            ffn(n + ".mlp", cfg.ffn)
            continue
        e = n + ".moe"
        ffn(e + ".shared", cfg.shared_ffn)
        out.update({e + ".gate.w": ((h, cfg.num_experts), DTYPE),
                    e + ".gate.bias": ((cfg.num_experts,), "float32"),
                    e + ".experts.w1": ((cfg.num_experts, h, cfg.moe_ffn),
                                        DTYPE),
                    e + ".experts.w3": ((cfg.num_experts, h, cfg.moe_ffn),
                                        DTYPE),
                    e + ".experts.w2": ((cfg.num_experts, cfg.moe_ffn, h),
                                        DTYPE)})
    v = cfg.vision
    d = v.hidden
    dense("kimi.vit.patch", 3 * v.patch * v.patch, d)
    out["kimi.vit.pos"] = (tuple(v.table) + (d,), DTYPE)
    ln("kimi.vit.ln_f", d)
    ln("kimi.proj.ln", d)
    dense("kimi.proj.fc1", 4 * d, 4 * d)
    dense("kimi.proj.fc2", 4 * d, h)
    for j in range(v.layers):
        n = "kimi.vit%d" % j
        ln(n + ".ln0", d)
        ln(n + ".ln1", d)
        dense(n + ".qkv", d, 3 * d)
        dense(n + ".o", d, d)
        dense(n + ".fc1", d, v.ffn)
        dense(n + ".fc2", v.ffn, d)
    return out
