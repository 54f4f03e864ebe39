"""GLM-5 family decoder (``glm_moe_dsa``: multi-head latent attention over a
learned sparse selection of keys, sigmoid-routed SwiGLU experts plus a
shared expert) as slot-decode programs for ``serving.DecodeEngine``.

Every layer is pre-norm residual twice over: ``y = x + Wo Attn(u)`` with
``u = RMSNorm(x)``, then ``y + FF(RMSNorm(y))``.

Attention. Queries come through a low rank: ``cq = RMSNorm(u Wdq)``, ``q =
cq Wuq``, per head ``[q_nope | q_rope]``, the second part turned by the
token's position (interleaved pairs). Keys and values come from ONE latent
a position: ``[ckv | k_rope] = u Wdkv``, ``ckv = RMSNorm(ckv)``, ``k_rope``
turned and shared by all heads; head h's key is ``[ckv Wuk_h | k_rope]`` and
its value ``ckv Wuv_h``. Each query attends only the ``index_topk`` earlier
positions its layer's indexer scores highest (``layers.dsa_select``): ``qi =
cq Wiq`` (index heads x index dim), ``ki = LayerNorm(u Wik)`` (one for all
index heads), the first ``rope`` dimensions of both turned, a weight per
index head ``u Wiw`` scaled by ``heads^-1/2 dim^-1/2``; all earlier
positions while there are no more than ``index_topk``.

``FF``: the first ``first_dense`` layers a SwiGLU MLP; the others a router
over all ``num_experts`` (sigmoid scores, the ``top_k`` largest of score +
correction, renormalised and scaled) weighting SwiGLU experts of which this
chip holds a contiguous range (``parallel.moe.held_experts_ffn``; what the
experts held elsewhere would add is left out), plus a shared SwiGLU expert
with weight 1. After the last layer a final RMSNorm and an untied head over
the held rows of the vocabulary. No bias but the indexer's LayerNorm.

The state a sequence carries (:meth:`GlmMoeDsaConfig.decode_model`) is two
``rows`` entries a layer that are no K and V: ``lat_<i>`` ``(cache_len,
latent_width)``, a position's ``[ckv | k_rope]`` and zeros up to a multiple
of 128, and ``idx_<i>`` ``(cache_len, index dim)``, its ``ki``. A prefill
computes attention on the expanded path (keys and values of every head made
from the latents, masked to each query's selection), a step on the absorbed
path (``layers.mla_attention``): its query is taken through ``Wuk`` into
the latent space and goes over the ``index_topk`` gathered rows of the
slot's cache alone.

Weights are bfloat16; products take bfloat16 operands and accumulate in
float32; the router, the indexer's scores and weights, the rotary term, the
norms' statistics and the logits are float32; the residual stream and both
caches are bfloat16.

The SwiGLU feed-forward, the routed layer and the greedy head are
``models/decoder_blocks.py``'s. Ops appended here carry a name scope
(``glm.mla``, ``glm.indexer``, ``glm.select``, ``glm.mlp``,
``glm.experts.route``, ``glm.experts.experts``, ``glm.experts.shared``,
``glm.head``) that the lowering opens as a ``jax.named_scope``.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from . import decoder_blocks as blocks
from .decode_utils import (DecodeModel, StateEntry, require_rows_only,
                           update_cache)

__all__ = ["GlmMoeDsaConfig", "build_prefill", "build_step", "param_shapes"]

DTYPE = "bfloat16"


class GlmMoeDsaConfig:
    """Sizes under the names of the family's ``config.json``. ``held`` is
    the contiguous range ``(first, count)`` of each sparse layer's
    ``num_experts`` routed experts that live here; the router keeps its
    full width. ``vocab`` rows of the embedding and of the head are held."""

    def __init__(self, num_layers, first_dense, vocab, hidden, heads, q_rank,
                 kv_rank, nope_dim, rope_dim, v_dim, index_heads, index_dim,
                 index_topk, ffn, moe_ffn, shared_ffn, num_experts, held,
                 top_k, theta, routed_scale=1.0, eps=1e-5):
        self.num_layers, self.first_dense = int(num_layers), int(first_dense)
        if not 0 <= self.first_dense <= self.num_layers:
            raise ValueError("first_dense %d outside [0, %d layers]"
                             % (self.first_dense, self.num_layers))
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.heads = int(heads)
        self.q_rank, self.kv_rank = int(q_rank), int(kv_rank)
        self.nope_dim, self.rope_dim = int(nope_dim), int(rope_dim)
        self.v_dim = int(v_dim)
        self.index_heads, self.index_dim = int(index_heads), int(index_dim)
        self.index_topk = int(index_topk)
        if self.rope_dim % 2 or self.rope_dim > self.index_dim:
            raise ValueError("the rotary part (%d) is turned in pairs and is "
                             "the first part of an index head (%d)"
                             % (self.rope_dim, self.index_dim))
        self.ffn, self.moe_ffn = int(ffn), int(moe_ffn)
        self.shared_ffn = int(shared_ffn)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.num_experts:
            raise ValueError("held experts %r outside [0, %d)"
                             % (self.held, self.num_experts))
        self.theta = float(theta)
        self.routed_scale, self.eps = float(routed_scale), float(eps)

    @classmethod
    def from_hf(cls, m, router_experts=None, first_expert=0):
        """From a dict with the keys of the published ``config.json``.
        ``n_routed_experts`` is the number of experts held here, from
        ``first_expert`` on, of the ``router_experts`` (default: the same
        number) that the router spans. What the keys name and this file
        does not build is refused."""
        for key, want in (("scoring_func", "sigmoid"),
                          ("topk_method", "noaux_tc"), ("n_group", 1),
                          ("topk_group", 1), ("norm_topk_prob", True),
                          ("attention_bias", False), ("hidden_act", "silu"),
                          ("moe_layer_freq", 1), ("rope_interleave", True),
                          ("indexer_rope_interleave", True),
                          ("tie_word_embeddings", False)):
            if m.get(key, want) != want:
                raise ValueError("%s = %r is not built (only %r)"
                                 % (key, m[key], want))
        if m.get("num_nextn_predict_layers", 0):
            raise ValueError(
                "num_nextn_predict_layers = %r is not built: a step that "
                "yields more than one token is not what DecodeEngine runs"
                % (m["num_nextn_predict_layers"],))
        rope = m["rope_parameters"]
        if rope.get("rope_type", "default") != "default":
            raise ValueError("rope_type %r is not built" % rope["rope_type"])
        count = int(m["n_routed_experts"])
        return cls(
            num_layers=m["num_hidden_layers"],
            first_dense=m["first_k_dense_replace"], vocab=m["vocab_size"],
            hidden=m["hidden_size"], heads=m["num_attention_heads"],
            q_rank=m["q_lora_rank"], kv_rank=m["kv_lora_rank"],
            nope_dim=m["qk_nope_head_dim"], rope_dim=m["qk_rope_head_dim"],
            v_dim=m["v_head_dim"], index_heads=m["index_n_heads"],
            index_dim=m["index_head_dim"], index_topk=m["index_topk"],
            ffn=m["intermediate_size"], moe_ffn=m["moe_intermediate_size"],
            shared_ffn=m["moe_intermediate_size"] * m["n_shared_experts"],
            num_experts=int(router_experts or count),
            held=(int(first_expert), count), top_k=m["num_experts_per_tok"],
            theta=rope["rope_theta"],
            routed_scale=m.get("routed_scaling_factor", 1.0),
            eps=m.get("rms_norm_eps", 1e-5))

    # -- derived sizes ---------------------------------------------------
    @property
    def latent_width(self):
        """Width of a latent row in the cache: its ``kv_rank + rope_dim``
        values, then zeros up to a whole number of the chip's 128 lanes.
        Those are the bytes a row-major row takes in the chip's tiles
        anyway, and a width that is no multiple makes the runtime lay the
        cache out with positions minor, under which gathering a row reads
        strided (1.77 ms for 16 x 2,048 rows of 576; PERF.md, PR 39)."""
        return -(-(self.kv_rank + self.rope_dim) // 128) * 128

    @property
    def expert_layers(self):
        return self.num_layers - self.first_dense

    def decode_model(self, cache_len, kv_dtype="fp32"):
        """Builders and state declaration for ``serving.DecodeEngine``: per
        layer the latent rows and the indexer's rows. ``kv_dtype`` names a
        residency of K/V rows and is refused for anything but the
        default."""
        import jax.numpy as jnp

        state = []
        for i in range(self.num_layers):
            state += [StateEntry("lat_%d" % i,
                                 (int(cache_len), self.latent_width),
                                 jnp.bfloat16, "rows"),
                      StateEntry("idx_%d" % i,
                                 (int(cache_len), self.index_dim),
                                 jnp.bfloat16, "rows")]
        model = DecodeModel(self, state, build_prefill, build_step,
                            step_counters=self._step_counters, rows_are_kv=False)
        if kv_dtype != "fp32":
            require_rows_only(model, "kv_dtype=%r" % (kv_dtype,))
        return model

    def _step_counters(self, aux, live):
        """The step's counts (:func:`build_step`) -> lifetime counters: per
        expert layer the assignments that landed on held experts, the
        largest count on one held expert and the held experts that got any,
        summed over the layers; all assignments of the live tokens; and,
        summed over the layers, the indexer rows the step scored, the
        positions its live slots kept, the latent rows that hold a position
        of a live slot and the latent rows it gathered."""
        moe = np.asarray(aux[:-4]).reshape(self.expert_layers, -1)
        return {"moe_assignments_held": int(moe[:, 0].sum()),
                "moe_assignments_total":
                    int(live) * self.top_k * self.expert_layers,
                "moe_expert_load_max_sum": int(moe[:, 1].sum()),
                "moe_experts_touched_sum": int(moe[:, 2].sum()),
                "dsa_rows_scored": int(aux[-4]),
                "dsa_rows_selected": int(aux[-3]),
                "latent_rows_live": int(aux[-2]),
                "latent_rows_read": int(aux[-1])}


# under this module's own name: the GLM-5 cell's planted fault
# ``selection_before_the_rotary_term`` (tests/benchmark_tests/test_glm5_cell.py)
# replaces ``glm_moe_dsa._turned`` to leave the indexer's rows unturned
_turned = blocks.turned


def _attention_inputs(u, lead, cfg, n, pos=None):
    """The projections of a layer's attention over u (B, lead, H) -> (q (B,
    lead, heads * (nope + rope)), the latent rows (B, lead, latent_width),
    the indexer's queries (B, lead, index heads * dim), its keys (B,
    lead, dim) and its heads' weights (B, lead, index heads) float32)."""
    with fluid.name_scope("glm.mla"):
        cq = layers.rms_norm(blocks.fc(u, cfg.q_rank, n + ".mla.q_a", 2),
                             n + ".mla.q_norm", epsilon=cfg.eps)
        q = _turned(
            blocks.fc(cq, cfg.heads * (cfg.nope_dim + cfg.rope_dim),
                      n + ".mla.q_b", 2),
            lead, cfg.heads, cfg.nope_dim + cfg.rope_dim, cfg, False, pos)
        lat = blocks.latent_rows(u, lead, cfg, n, pos)
    with fluid.name_scope("glm.indexer"):
        qi = _turned(
            blocks.fc(cq, cfg.index_heads * cfg.index_dim, n + ".idx.q", 2),
            lead, cfg.index_heads, cfg.index_dim, cfg, True, pos)
        ki = layers.layer_norm(
            blocks.fc(u, cfg.index_dim, n + ".idx.k", 2), begin_norm_axis=2,
            epsilon=1e-6, param_attr=ParamAttr(name=n + ".idx.k_norm.w"),
            bias_attr=ParamAttr(name=n + ".idx.k_norm.b"))
        ki = _turned(ki, lead, 1, cfg.index_dim, cfg, True, pos)
        wi = layers.scale(
            layers.dense_acc32(u, cfg.index_heads, n + ".idx.w"),
            scale=cfg.index_heads ** -0.5 * cfg.index_dim ** -0.5)
    return q, lat, qi, ki, wi


def _attend(q, lat, qi, ki, wi, cfg, n, pos=None):
    """-> (Wo Attn (B, lead, H), what the indexer kept)."""
    with fluid.name_scope("glm.select"):
        kept = layers.dsa_select(qi, ki, wi, cfg.index_heads, cfg.index_topk,
                                 pos=pos)
    with fluid.name_scope("glm.mla"):
        a = layers.mla_attention(q, lat, kept, n + ".mla", cfg.heads,
                                 cfg.kv_rank, cfg.nope_dim, cfg.rope_dim,
                                 cfg.v_dim, pos=pos)
        return blocks.fc(a, cfg.hidden, n + ".mla.o", 2), kept


def _feed_forward(w, cfg, i, live, counts, routed):
    """The layer's second half on flat rows (T, H); a prompt's routed layer
    in calls of ``blocks.MOE_PROMPT_ROWS`` tokens."""
    n = "glm%d" % i
    if i < cfg.first_dense:
        with fluid.name_scope("glm.mlp"):
            return blocks.swiglu(w, cfg.ffn, cfg.hidden, n + ".mlp")
    part = blocks.routed_in_calls(
        w, live, counts, cfg.num_experts, cfg.top_k, cfg.held, cfg.moe_ffn,
        n + ".moe", "glm.experts", scale=cfg.routed_scale)
    routed.append(part)
    with fluid.name_scope("glm.experts.shared"):
        shared = blocks.swiglu(w, cfg.shared_ffn, cfg.hidden,
                               n + ".moe.shared")
    return layers.elementwise_add(part, shared)


def _embed(ids, cfg):
    return layers.embedding(ids, size=[cfg.vocab, cfg.hidden], dtype=DTYPE,
                            param_attr=ParamAttr(name="glm.emb"))


def _head(x, cfg):
    with fluid.name_scope("glm.head"):
        return blocks.greedy_head(x, cfg.vocab, cfg.eps, "glm.norm_f",
                                  "glm.head")


def build_prefill(cfg, prompt_len, cache_len):
    """Slot-prefill program: one pass over a right-padded prompt bucket.
    Feeds ``glm_prefill_ids`` (1, prompt_len) int64 and ``glm_prefill_len``
    (1, 1); the batch is one sequence (the routed layer takes flat rows of
    a static count). Fetches the greedy token after the last real position
    and the sequence's state in the declaration's order: per layer the
    latent rows ``(1, cache_len, latent_width)`` and the indexer's rows
    ``(1, cache_len, index dim)``, zero past ``len``. Attention runs the
    expanded path masked to each query's selection, in blocks of queries
    (``ops.hybrid_ops._dsa_select`` / ``_mla_attention``): no (heads, T, T)
    array exists. ``moe_routed`` names, per sparse layer, the held experts'
    part ``(prompt_len, hidden)``; ``attn_in`` / ``attn_out``, per layer,
    the stream before the layer and what its attention block adds to it
    ``(1, prompt_len, hidden)``; ``selected``, per layer, what the indexer
    kept ``(1, prompt_len, prompt_len)`` int8: for whoever wants to fetch
    them (the engine does not)."""
    from .gpt import _row_coords

    if not 1 <= prompt_len <= cache_len:
        raise ValueError("need 1 <= prompt_len (%d) <= cache_len (%d)"
                         % (prompt_len, cache_len))
    ids = fluid.data("glm_prefill_ids", shape=[1, prompt_len], dtype="int64")
    plen = fluid.data("glm_prefill_len", shape=[1, 1], dtype="int64")
    x = layers.reshape(_embed(ids, cfg), [1, prompt_len, cfg.hidden])
    steps = layers.unsqueeze(layers.range(0, prompt_len, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, plen), DTYPE)   # (1, P)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [prompt_len, 1])
    state, counts, routed, attn_in, attn_out, selected = [], [], [], [], [], []
    for i in range(cfg.num_layers):
        n = "glm%d" % i
        attn_in.append(x)
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        q, lat, qi, ki, wi = _attention_inputs(u, prompt_len, cfg, n)
        y, kept = _attend(q, lat, qi, ki, wi, cfg, n)
        for rows in (lat, ki):
            rows = layers.elementwise_mul(rows, valid3)
            if cache_len > prompt_len:
                rows = layers.concat([rows, layers.fill_constant(
                    [1, cache_len - prompt_len, rows.shape[2]], DTYPE, 0.0)],
                    axis=1)
            state.append(rows)
        attn_out.append(y)
        selected.append(kept)
        x = layers.elementwise_add(x, y)
        w = layers.reshape(
            layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps),
            [prompt_len, cfg.hidden])
        y = _feed_forward(w, cfg, i, live, counts, routed)
        x = layers.elementwise_add(
            x, layers.reshape(y, [1, prompt_len, cfg.hidden]))
    one = layers.fill_constant([1], "int64", 1)
    x_last = layers.gather_nd(x, _row_coords(
        layers.elementwise_sub(plen, one)))                     # (1, H)
    logits, nxt = _head(x_last, cfg)
    return {"ids": ids, "len": plen, "next": nxt, "logits": logits,
            "state": state, "moe_counts": counts, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out, "selected": selected,
            "feed_names": ["glm_prefill_ids", "glm_prefill_len"],
            "fetch_vars": [nxt] + state}


def build_step(cfg, cache_len):
    """One decode step for all slots. Feeds ``glm_step_tok`` /
    ``glm_step_pos`` (S, 1) int64 and the state buffers, one feed per
    declared entry (``cache_feed_names``), all donated: each slot's new
    latent row and indexer row (both turned by the slot's ``pos``) are
    written at row ``pos``, the indexer scores the slot's rows ``<= pos``
    and keeps ``index_topk`` of them, and the query goes over those rows of
    the latent cache alone, gathered (the absorbed path). Fetches the
    greedy tokens, the updated state in the same order, and ``counts``
    int32: per sparse layer the live tokens' assignments that landed on
    held experts, the largest count on one held expert, the held experts
    that got any and the sorted rows the experts' loops covered; then,
    summed over the layers, the indexer rows scored (every column of every
    slot), the positions the live slots kept (``min(pos + 1, index_topk)``
    each), the latent rows that hold a position of a live slot (``pos +
    1``) and the latent rows gathered (``index_topk`` a slot, live or not).
    A slot with ``pos == 0`` is dead: its row is computed and ignored, and
    it is routed to no expert. ``attn_in`` / ``attn_out`` as
    :func:`build_prefill`'s, ``(S, hidden)``; ``selected`` per layer ``(S,
    index_topk)`` int32."""
    tok = fluid.data("glm_step_tok", shape=[None, 1], dtype="int64")
    pos = fluid.data("glm_step_pos", shape=[None, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("glm_step_" + e.name, shape=[None] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    by_name = {e.name: f for e, f in zip(decl, feeds)}
    x = layers.reshape(_embed(tok, cfg), [-1, cfg.hidden])       # (S, H)
    alive = layers.greater_than(pos, layers.fill_constant([1], "int64", 0))
    live = layers.cast(alive, DTYPE)                             # (S, 1)
    state, counts, routed, attn_in, attn_out, selected = [], [], [], [], [], []
    for i in range(cfg.num_layers):
        n = "glm%d" % i
        attn_in.append(x)
        u = layers.unsqueeze(
            layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps), [1])
        q, lat, qi, ki, wi = _attention_inputs(u, 1, cfg, n, pos=pos)
        lat = update_cache(by_name["lat_%d" % i], lat, pos=pos, per_row=True)
        ki = update_cache(by_name["idx_%d" % i], ki, pos=pos, per_row=True)
        y, kept = _attend(q, lat, qi, ki, wi, cfg, n, pos=pos)
        y = layers.squeeze(y, [1])
        state += [lat, ki]
        attn_out.append(y)
        selected.append(kept)
        x = layers.elementwise_add(x, y)
        w = layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps)
        x = layers.elementwise_add(
            x, _feed_forward(w, cfg, i, live, counts, routed))
    logits, nxt = _head(x, cfg)
    nl, kept_n = cfg.num_layers, min(cfg.index_topk, int(cache_len))
    held_rows = layers.elementwise_mul(
        layers.cast(alive, "int64"),
        layers.scale(pos, scale=1.0, bias=1.0))                  # (S, 1)

    def total(v):
        return layers.reshape(layers.cast(layers.reduce_sum(
            layers.scale(v, scale=float(nl))), "int32"), [1])

    def every_slot(value):
        return layers.fill_constant_batch_size_like(
            pos, shape=[-1, 1], dtype="int64", value=value)

    aux = layers.concat(
        [layers.reshape(c, [-1]) for c in counts]
        + [total(every_slot(int(cache_len))),
           total(layers.elementwise_min(
               held_rows, layers.fill_constant([1], "int64", kept_n))),
           total(held_rows), total(every_slot(kept_n))], axis=0)
    names = [f.name for f in feeds]
    return {"tok": tok, "pos": pos, "next": nxt, "logits": logits,
            "state": state, "counts": aux, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out, "selected": selected,
            "feed_names": ["glm_step_tok", "glm_step_pos"] + names,
            "cache_feed_names": names,
            "fetch_vars": [nxt] + state + [aux]}


def param_shapes(cfg):
    """{name: (shape, dtype name)} of every parameter the programs read:
    what a checkpoint for this model holds."""
    h = cfg.hidden
    out = {"glm.emb": ((cfg.vocab, h), DTYPE),
           "glm.head.w": ((h, cfg.vocab), DTYPE),
           "glm.norm_f.w": ((h,), DTYPE)}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), DTYPE),
                    name + ".w3.w": ((h, width), DTYPE),
                    name + ".w2.w": ((width, h), DTYPE)})

    for i in range(cfg.num_layers):
        n = "glm%d" % i
        out.update({
            n + ".attn_norm.w": ((h,), DTYPE),
            n + ".mlp_norm.w": ((h,), DTYPE),
            n + ".mla.q_a.w": ((h, cfg.q_rank), DTYPE),
            n + ".mla.q_norm.w": ((cfg.q_rank,), DTYPE),
            n + ".mla.q_b.w": ((cfg.q_rank, cfg.heads
                                * (cfg.nope_dim + cfg.rope_dim)), DTYPE),
            n + ".mla.kv_a.w": ((h, cfg.kv_rank + cfg.rope_dim), DTYPE),
            n + ".mla.kv_norm.w": ((cfg.kv_rank,), DTYPE),
            n + ".mla.uk.w": ((cfg.kv_rank, cfg.heads * cfg.nope_dim), DTYPE),
            n + ".mla.uv.w": ((cfg.kv_rank, cfg.heads * cfg.v_dim), DTYPE),
            n + ".mla.o.w": ((cfg.heads * cfg.v_dim, h), DTYPE),
            n + ".idx.q.w": ((cfg.q_rank, cfg.index_heads * cfg.index_dim),
                             DTYPE),
            n + ".idx.k.w": ((h, cfg.index_dim), DTYPE),
            n + ".idx.k_norm.w": ((cfg.index_dim,), DTYPE),
            n + ".idx.k_norm.b": ((cfg.index_dim,), DTYPE),
            n + ".idx.w.w": ((h, cfg.index_heads), DTYPE)})
        if i < cfg.first_dense:
            ffn(n + ".mlp", cfg.ffn)
            continue
        e, held = n + ".moe", cfg.held[1]
        ffn(e + ".shared", cfg.shared_ffn)
        out.update({e + ".gate.w": ((h, cfg.num_experts), DTYPE),
                    e + ".gate.bias": ((cfg.num_experts,), "float32"),
                    e + ".experts.w1": ((held, h, cfg.moe_ffn), DTYPE),
                    e + ".experts.w3": ((held, h, cfg.moe_ffn), DTYPE),
                    e + ".experts.w2": ((held, cfg.moe_ffn, h), DTYPE)})
    return out
