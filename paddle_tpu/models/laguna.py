"""Laguna family decoder (window and full grouped-query attention layers
with different numbers of query heads, a sigmoid gate per head on the
attention output, rotary positions that are plain on the window layers and
YaRN over part of a head on the full ones, softmax-routed SwiGLU experts
plus a shared expert) as slot-decode programs for ``serving.DecodeEngine``.

Every layer is pre-norm residual twice over: ``y = x + Wo (g * Attn(u))``
with ``u = RMSNorm(x)`` and ``g = sigmoid(u Wg)`` one value a head, then
``y + FF(RMSNorm(y))``. ``layer_types`` gives the attention of each layer:
``full_attention`` (causal over the whole sequence) or ``sliding_attention``
(causal over the last ``sliding_window`` positions), each with its own
number of query heads over one K/V width. ``mlp_layer_types`` gives ``FF``:
``dense`` a SwiGLU MLP, ``sparse`` a router over all ``num_experts`` with
softmax scores, the ``top_k`` largest renormalised and scaled, weighting the
outputs of SwiGLU experts of which this chip holds a contiguous range
(``parallel.moe.held_experts_ffn``; what the experts held elsewhere would
add is left out), plus a shared SwiGLU expert added with weight 1. After
the last layer a final RMSNorm and an untied head over the held rows of the
vocabulary. No bias anywhere.

The model declares the state a sequence carries (:meth:`LagunaConfig.
decode_model`): per full layer a K and a V of ``rows`` (one per position,
``cache_len`` long), per window layer a K and a V that are a ``ring`` of
``sliding_window`` rows (position p at row ``p mod sliding_window``); K is
kept turned by its position, so a step turns only its own row.

Weights are bfloat16; products take bfloat16 operands and accumulate in
float32; the router, the rotary term, the norms' statistics and the logits
are float32; the residual stream and K/V are bfloat16.

The SwiGLU feed-forward, the routed layer and the greedy head are
``models/decoder_blocks.py``'s, shared with ``models/lfm2.py`` (trained) and
``models/nemotron_h.py``; the attention block (gate, two rotary terms, ring)
is this family's own.

Ops appended here carry a name scope (``laguna.attn.full``,
``laguna.attn.window``, ``laguna.mlp``, ``laguna.moe.route``,
``laguna.moe.experts``, ``laguna.moe.shared``, ``laguna.head``) that the
lowering opens as a ``jax.named_scope``.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from . import decoder_blocks as blocks
from .decode_utils import (DecodeModel, StateEntry, require_rows_only,
                           update_cache)

__all__ = ["LagunaConfig", "build_prefill", "build_step", "param_shapes"]

DTYPE = "bfloat16"
FULL, WINDOW = "full_attention", "sliding_attention"


class LagunaConfig:
    """Sizes under the names of the family's ``config.json``. ``held`` is
    the contiguous range ``(first, count)`` of each sparse layer's
    ``num_experts`` routed experts that live here; the router keeps its
    full width. ``vocab`` rows of the embedding and of the head are held.
    ``rope`` maps each layer type to ``(theta, rotary dimensions, yarn)``
    as ``layers.rotary_embedding`` takes them."""

    def __init__(self, layer_types, mlp_layer_types, heads_per_layer, vocab,
                 hidden, kv_heads, head_dim, ffn, moe_ffn, shared_ffn,
                 num_experts, held, top_k, window, rope, routed_scale=1.0,
                 eps=1e-6):
        self.layer_types = tuple(layer_types)
        self.mlp_layer_types = tuple(mlp_layer_types)
        self.heads_per_layer = tuple(int(h) for h in heads_per_layer)
        if set(self.layer_types) - {FULL, WINDOW}:
            raise ValueError("layer_types %r has kinds other than %s and %s"
                             % (self.layer_types, FULL, WINDOW))
        if set(self.mlp_layer_types) - {"dense", "sparse"}:
            raise ValueError("mlp_layer_types %r has kinds other than dense "
                             "and sparse" % (self.mlp_layer_types,))
        if not (len(self.layer_types) == len(self.mlp_layer_types)
                == len(self.heads_per_layer)):
            raise ValueError("layer_types, mlp_layer_types and the heads per "
                             "layer differ in length")
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.kv_heads, self.head_dim = int(kv_heads), int(head_dim)
        if any(h % self.kv_heads for h in self.heads_per_layer):
            raise ValueError("heads per layer %r are no multiples of %d K/V "
                             "heads" % (self.heads_per_layer, self.kv_heads))
        self.ffn, self.moe_ffn = int(ffn), int(moe_ffn)
        self.shared_ffn = int(shared_ffn)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.num_experts:
            raise ValueError("held experts %r outside [0, %d)"
                             % (self.held, self.num_experts))
        self.window = int(window)
        self.rope = {k: (float(t), int(d), tuple(y) if y else None)
                     for k, (t, d, y) in rope.items()}
        self.routed_scale, self.eps = float(routed_scale), float(eps)

    @classmethod
    def from_hf(cls, m, router_experts=None, first_expert=0):
        """From a dict with the keys of the published ``config.json``.
        ``num_experts`` is the number of experts held here, from
        ``first_expert`` on, of the ``router_experts`` (default: the same
        number) that the router spans; ``num_hidden_layers`` is not read:
        the depth is the length of ``layer_types``. What the keys name and
        this file does not build is refused."""
        for key, want in (("gating", "per-head"), ("attention_bias", False),
                          ("norm_topk_prob", True),
                          ("moe_apply_router_weight_on_input", False),
                          ("moe_router_logit_softcapping", 0),
                          ("decoder_sparse_step", 1),
                          ("tie_word_embeddings", False)):
            if m.get(key, want) != want:
                raise ValueError("%s = %r is not built (only %r)"
                                 % (key, m[key], want))
        if set(m.get("gating_types", [])) - {"per_head"}:
            raise ValueError("gating_types other than per_head are not built")
        rope = {}
        for kind, r in m["rope_parameters"].items():
            rot = int(round(m["head_dim"] * r.get("partial_rotary_factor", 1)))
            yarn = None
            if r.get("rope_type", "default") == "yarn":
                yarn = (r["factor"], r["original_max_position_embeddings"],
                        r.get("beta_fast", 32), r.get("beta_slow", 1),
                        r["attention_factor"])
            elif r.get("rope_type", "default") != "default":
                raise ValueError("rope_type %r is not built" % r["rope_type"])
            rope[kind] = (r["rope_theta"], rot, yarn)
        count = int(m["num_experts"])
        return cls(
            layer_types=m["layer_types"],
            mlp_layer_types=m["mlp_layer_types"],
            heads_per_layer=m["num_attention_heads_per_layer"],
            vocab=m["vocab_size"], hidden=m["hidden_size"],
            kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            ffn=m["intermediate_size"], moe_ffn=m["moe_intermediate_size"],
            shared_ffn=m["shared_expert_intermediate_size"],
            num_experts=int(router_experts or count),
            held=(int(first_expert), count), top_k=m["num_experts_per_tok"],
            window=m["sliding_window"], rope=rope,
            routed_scale=m.get("moe_routed_scaling_factor", 1.0),
            eps=m.get("rms_norm_eps", 1e-6))

    # -- derived sizes ---------------------------------------------------
    @property
    def kv_width(self):
        return self.kv_heads * self.head_dim

    @property
    def expert_layers(self):
        return self.mlp_layer_types.count("sparse")

    def decode_model(self, cache_len, kv_dtype="fp32"):
        """Builders and state declaration for ``serving.DecodeEngine``.
        ``kv_dtype`` names a residency of K/V rows alone and is refused
        for anything but the default."""
        import jax.numpy as jnp

        state = []
        for i, kind in enumerate(self.layer_types):
            rows, how = ((int(cache_len), "rows") if kind == FULL
                         else (self.window, "ring"))
            for part in ("k", "v"):
                state.append(StateEntry("%s_%d" % (part, i),
                                        (rows, self.kv_width), jnp.bfloat16,
                                        how))
        model = DecodeModel(self, state, build_prefill, build_step,
                            step_counters=self._step_counters)
        if kv_dtype != "fp32":
            require_rows_only(model, "kv_dtype=%r" % (kv_dtype,))
            raise ValueError("this model's K/V rows are bfloat16; kv_dtype "
                             "%r is not one of its residencies"
                             % (kv_dtype,))
        return model

    def _step_counters(self, aux, live):
        """The step's counts (:func:`build_step`) -> lifetime counters: per
        expert layer the assignments that landed on held experts, the
        largest count on one held expert and the held experts that got any,
        summed over the layers; all assignments of the live tokens; and the
        K/V rows that held a position of a live sequence against the rows
        the step's attention went over."""
        moe = np.asarray(aux[:-2]).reshape(self.expert_layers, -1)
        return {"moe_assignments_held": int(moe[:, 0].sum()),
                "moe_assignments_total":
                    int(live) * self.top_k * self.expert_layers,
                "moe_expert_load_max_sum": int(moe[:, 1].sum()),
                "moe_experts_touched_sum": int(moe[:, 2].sum()),
                "kv_rows_live": int(aux[-2]), "kv_rows_read": int(aux[-1])}


def _heads(x, lead, count, cfg, kind, pos=None):
    """(.., count * dh) -> the same, each head turned by its position."""
    theta, rot, yarn = cfg.rope[kind]
    x = layers.reshape(x, [-1, lead, count, cfg.head_dim])
    x = layers.rotary_embedding(x, theta, pos=pos, rotary_dim=rot, yarn=yarn)
    return layers.reshape(x, [-1, lead, count * cfg.head_dim])


def _gated(a, u, lead, heads, cfg, n):
    """``Wo (g * a)`` with ``g = sigmoid(u Wg)`` one value a head. a and u
    (B, lead, .)."""
    g = layers.sigmoid(blocks.fc(u, heads, n + ".g", 2))        # (B, T, heads)
    a = layers.elementwise_mul(
        layers.reshape(a, [-1, lead, heads, cfg.head_dim]),
        layers.unsqueeze(g, [3]))
    return blocks.fc(layers.reshape(a, [-1, lead, heads * cfg.head_dim]),
                     cfg.hidden, n + ".o", 2)


def _feed_forward(w, cfg, i, live, counts, routed):
    """The layer's second half on flat rows (T, H)."""
    n = "lg%d" % i
    if cfg.mlp_layer_types[i] == "dense":
        with fluid.name_scope("laguna.mlp"):
            return blocks.swiglu(w, cfg.ffn, cfg.hidden, n + ".mlp")
    part, c = blocks.routed_gated_experts(
        w, cfg.num_experts, cfg.top_k, cfg.held, cfg.moe_ffn, n + ".moe",
        "laguna.moe", live=live, scale=cfg.routed_scale,
        score_func="softmax", bias=False)
    counts.append(c)
    routed.append(part)
    with fluid.name_scope("laguna.moe.shared"):
        shared = blocks.swiglu(w, cfg.shared_ffn, cfg.hidden,
                               n + ".moe.shared")
    return layers.elementwise_add(part, shared)


def _scope(kind):
    return "laguna.attn.full" if kind == FULL else "laguna.attn.window"


def _embed(ids, cfg):
    return layers.embedding(ids, size=[cfg.vocab, cfg.hidden], dtype=DTYPE,
                            param_attr=ParamAttr(name="lg.emb"))


def _head(x, cfg):
    with fluid.name_scope("laguna.head"):
        return blocks.greedy_head(x, cfg.vocab, cfg.eps, "lg.norm_f",
                                  "lg.head")


def build_prefill(cfg, prompt_len, cache_len):
    """Slot-prefill program: one pass over a right-padded prompt bucket.
    Feeds ``lg_prefill_ids`` (B, prompt_len) int64 and ``lg_prefill_len``
    (B, 1). Fetches the greedy token after the last real position and the
    sequence's state in the declaration's order: per full layer K and V
    ``(B, cache_len, kv width)``, zero past ``len``; per window layer K and
    V ``(B, window, kv width)``, the last ``window`` real positions' rows at
    ``position mod window``. A window layer's scores are banded: neither a
    (T, T) array nor a full causal call's cost (on the chip one kernel a
    layer, ``window_attn_fwd``, the scores never in HBM; on the CPU or
    under a mesh the banded blocks through XLA: ``gqa_attention`` chooses
    from the call it sees). ``moe_routed`` names, per
    sparse layer, the held experts' part ``(B * prompt_len, hidden)``;
    ``attn_in`` / ``attn_out``, per layer, the stream before the layer and
    what its attention block adds to it ``(B, prompt_len, hidden)``: for
    whoever wants to fetch them (the engine does not)."""
    from .gpt import _row_coords

    if not 1 <= prompt_len <= cache_len:
        raise ValueError("need 1 <= prompt_len (%d) <= cache_len (%d)"
                         % (prompt_len, cache_len))
    ids = fluid.data("lg_prefill_ids", shape=[None, prompt_len],
                     dtype="int64")
    plen = fluid.data("lg_prefill_len", shape=[None, 1], dtype="int64")
    x = layers.reshape(_embed(ids, cfg), [-1, prompt_len, cfg.hidden])
    steps = layers.unsqueeze(layers.range(0, prompt_len, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, plen), DTYPE)   # (B, P)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [-1, 1])
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i, kind in enumerate(cfg.layer_types):
        n, heads = "lg%d" % i, cfg.heads_per_layer[i]
        attn_in.append(x)
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        with fluid.name_scope(_scope(kind)):
            q = _heads(blocks.fc(u, heads * cfg.head_dim, n + ".attn.q", 2),
                       prompt_len, heads, cfg, kind)
            k = _heads(blocks.fc(u, cfg.kv_width, n + ".attn.k", 2),
                       prompt_len, cfg.kv_heads, cfg, kind)
            v = blocks.fc(u, cfg.kv_width, n + ".attn.v", 2)
            a = layers.gqa_attention(
                q, k, v, heads, cfg.kv_heads,
                window=cfg.window if kind == WINDOW else None)
            y = _gated(a, u, prompt_len, heads, cfg, n + ".attn")
            if kind == WINDOW:
                k = layers.kv_ring_gather(k, plen, cfg.window)
                v = layers.kv_ring_gather(v, plen, cfg.window)
            else:
                k = layers.elementwise_mul(k, valid3)
                v = layers.elementwise_mul(v, valid3)
                if cache_len > prompt_len:
                    pad = layers.fill_constant_batch_size_like(
                        ids, shape=[-1, cache_len - prompt_len, cfg.kv_width],
                        dtype=DTYPE, value=0.0)
                    k = layers.concat([k, pad], axis=1)
                    v = layers.concat([v, pad], axis=1)
        state += [k, v]
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.reshape(
            layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps),
            [-1, cfg.hidden])
        y = _feed_forward(w, cfg, i, live, counts, routed)
        x = layers.elementwise_add(
            x, layers.reshape(y, [-1, prompt_len, cfg.hidden]))
    one = layers.fill_constant([1], "int64", 1)
    x_last = layers.gather_nd(x, _row_coords(
        layers.elementwise_sub(plen, one)))                     # (B, H)
    logits, nxt = _head(x_last, cfg)
    return {"ids": ids, "len": plen, "next": nxt, "logits": logits,
            "state": state, "moe_counts": counts, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["lg_prefill_ids", "lg_prefill_len"],
            "fetch_vars": [nxt] + state}


def build_step(cfg, cache_len):
    """One decode step for all slots. Feeds ``lg_step_tok`` / ``lg_step_pos``
    (S, 1) int64 and the state buffers, one feed per declared entry
    (``cache_feed_names``), all donated: each slot's new K (turned by the
    slot's ``pos``) and V are written at row ``pos`` of a full layer's
    ``rows`` and at row ``pos mod window`` of a window layer's ``ring``,
    and its query, turned the same way, goes over the columns ``<= pos``
    (every column of a ring once it has wrapped). Fetches the greedy
    tokens, the updated state in the same order, and ``counts`` int32: per
    sparse layer the live tokens' assignments that landed on held experts,
    the largest count on one held expert, the held experts that got any and
    the sorted rows the experts' loops covered, then the K/V rows that hold
    a position of a live slot and the rows the step's attention went over
    (all slots, every column). A slot with ``pos == 0`` is dead: its row is
    computed and ignored, and it is routed to no expert. ``attn_in`` /
    ``attn_out`` as :func:`build_prefill`'s, ``(S, hidden)``."""
    tok = fluid.data("lg_step_tok", shape=[None, 1], dtype="int64")
    pos = fluid.data("lg_step_pos", shape=[None, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("lg_step_" + e.name, shape=[None] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    by_name = {e.name: f for e, f in zip(decl, feeds)}
    x = layers.reshape(_embed(tok, cfg), [-1, cfg.hidden])       # (S, H)
    alive = layers.greater_than(pos, layers.fill_constant([1], "int64", 0))
    live = layers.cast(alive, DTYPE)                             # (S, 1)
    ring_pos = layers.elementwise_mod(
        pos, layers.fill_constant([1], "int64", cfg.window))
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i, kind in enumerate(cfg.layer_types):
        n, heads = "lg%d" % i, cfg.heads_per_layer[i]
        attn_in.append(x)
        u = layers.unsqueeze(
            layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps), [1])
        at = pos if kind == FULL else ring_pos
        with fluid.name_scope(_scope(kind)):
            q = _heads(blocks.fc(u, heads * cfg.head_dim, n + ".attn.q", 2),
                       1, heads, cfg, kind, pos=pos)
            k = update_cache(
                by_name["k_%d" % i],
                _heads(blocks.fc(u, cfg.kv_width, n + ".attn.k", 2), 1,
                       cfg.kv_heads, cfg, kind, pos=pos),
                pos=at, per_row=True)
            v = update_cache(by_name["v_%d" % i],
                             blocks.fc(u, cfg.kv_width, n + ".attn.v", 2),
                             pos=at, per_row=True)
            a = layers.gqa_attention(q, k, v, heads, cfg.kv_heads, pos=pos)
            y = layers.squeeze(_gated(a, u, 1, heads, cfg, n + ".attn"), [1])
        state += [k, v]
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps)
        x = layers.elementwise_add(
            x, _feed_forward(w, cfg, i, live, counts, routed))
    logits, nxt = _head(x, cfg)
    # rows that hold a position of a live slot: pos + 1 of every full
    # layer's, min(pos + 1, window) of every ring's (the step has just
    # written row pos); rows gone over: every column of every slot
    n_full = cfg.layer_types.count(FULL)
    n_ring = len(cfg.layer_types) - n_full
    held_rows = layers.elementwise_mul(
        layers.cast(alive, "int64"),
        layers.scale(pos, scale=1.0, bias=1.0))                  # (S, 1)
    in_ring = layers.elementwise_min(
        held_rows, layers.fill_constant([1], "int64", cfg.window))
    kv_live = layers.cast(layers.reduce_sum(layers.elementwise_add(
        layers.scale(held_rows, scale=float(n_full)),
        layers.scale(in_ring, scale=float(n_ring)))), "int32")
    kv_read = layers.cast(layers.reduce_sum(
        layers.fill_constant_batch_size_like(
            pos, shape=[-1, 1], dtype="int64",
            value=n_full * int(cache_len) + n_ring * cfg.window)), "int32")
    aux = layers.concat(
        [layers.reshape(c, [-1]) for c in counts]
        + [layers.reshape(kv_live, [1]), layers.reshape(kv_read, [1])],
        axis=0)
    names = [f.name for f in feeds]
    return {"tok": tok, "pos": pos, "next": nxt, "logits": logits,
            "state": state, "counts": aux, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["lg_step_tok", "lg_step_pos"] + names,
            "cache_feed_names": names,
            "fetch_vars": [nxt] + state + [aux]}


def param_shapes(cfg):
    """{name: (shape, dtype name)} of every parameter the programs read:
    what a checkpoint for this model holds."""
    h, dh = cfg.hidden, cfg.head_dim
    out = {"lg.emb": ((cfg.vocab, h), DTYPE),
           "lg.head.w": ((h, cfg.vocab), DTYPE),
           "lg.norm_f.w": ((h,), DTYPE)}

    def ffn(name, width):
        out.update({name + ".w1.w": ((h, width), DTYPE),
                    name + ".w3.w": ((h, width), DTYPE),
                    name + ".w2.w": ((width, h), DTYPE)})

    for i, heads in enumerate(cfg.heads_per_layer):
        n = "lg%d" % i
        out.update({n + ".attn_norm.w": ((h,), DTYPE),
                    n + ".mlp_norm.w": ((h,), DTYPE),
                    n + ".attn.q.w": ((h, heads * dh), DTYPE),
                    n + ".attn.k.w": ((h, cfg.kv_width), DTYPE),
                    n + ".attn.v.w": ((h, cfg.kv_width), DTYPE),
                    n + ".attn.g.w": ((h, heads), DTYPE),
                    n + ".attn.o.w": ((heads * dh, h), DTYPE)})
        if cfg.mlp_layer_types[i] == "dense":
            ffn(n + ".mlp", cfg.ffn)
            continue
        e, held = n + ".moe", cfg.held[1]
        ffn(e + ".shared", cfg.shared_ffn)
        out.update({e + ".gate.w": ((h, cfg.num_experts), DTYPE),
                    e + ".experts.w1": ((held, h, cfg.moe_ffn), DTYPE),
                    e + ".experts.w3": ((held, h, cfg.moe_ffn), DTYPE),
                    e + ".experts.w2": ((held, cfg.moe_ffn, h), DTYPE)})
    return out
