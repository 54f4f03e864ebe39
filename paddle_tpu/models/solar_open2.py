"""Solar-Open2 family decoder (``solar_open2``: softmax grouped-query
attention layers without a position term under an elementwise gate, each
followed by gated delta-rule linear-attention layers with a decay a channel;
sigmoid-routed SwiGLU experts plus a shared expert in every layer) as
slot-decode programs for ``serving.DecodeEngine``.

Every layer is pre-norm residual twice over: ``y = x + Mixer(u)`` with ``u =
RMSNorm(x)``, then ``y + FF(RMSNorm(y))``.

A softmax layer (``gqa``): ``q = u Wq`` (heads x head_dim), ``k, v = u Wk, u
Wv`` (kv_heads x head_dim), no position term (the delta-rule layers carry
order), causal softmax at ``head_dim^-1/2``, ``(attn * sigmoid(u Wg)) Wo``
with the gate elementwise.

A delta-rule layer (``kda``; Kimi Delta Attention, arXiv:2510.26692): ``q, k,
v = silu(conv(u Wq)), silu(conv(u Wk)), silu(conv(u Wv))``, depthwise causal
convolutions of ``conv_kernel`` taps without bias; q and k L2-normed a head;
a log decay a channel ``g = -exp(A_log) softplus((u Wfa) Wfb + dt_bias)``;
``beta = 2 sigmoid(u Wb)`` a head (``beta_scale`` 2: eigenvalues of ``I -
beta k k^T`` in (-1, 1]); the state ``S`` (head_dim x head_dim a head,
float32) follows ``S <- (I - beta k k^T) diag(exp(g)) S + beta k v^T``, ``o
= S^T q`` (``layers.kda_scan`` over a prompt, ``layers.kda_step`` a
position); ``(RMSNorm_head(o) * sigmoid((u Wga) Wgb)) Wo`` with one learned
gain of head_dim.

``FF``: a router over all ``num_experts`` (sigmoid scores, the ``top_k``
largest of score + correction, renormalised and scaled) weighting SwiGLU
experts of which this chip holds a contiguous range
(``parallel.moe.held_experts_ffn``; what the experts held elsewhere would add
is left out), plus a shared SwiGLU expert with weight 1. After the last layer
a final RMSNorm and an untied head over the held rows of the vocabulary. No
bias anywhere.

The state a sequence carries (:meth:`SolarOpen2Config.decode_model`): per
softmax layer a K and a V of ``rows`` (one per position, ``kv_heads *
head_dim`` wide), per delta-rule layer the three convolutions' windows
(``conv_kernel - 1`` columns of the convolution's input) and ``S``, all
``fixed``.

Weights are bfloat16 (``A_log``, ``dt_bias`` and the router's score
correction float32); products take bfloat16 operands and accumulate in
float32; the router, the decay, ``beta``, the delta rule and its state, the
softmax and the norms' statistics are float32; the residual stream, K/V and
the windows are bfloat16.

Ops appended here carry a name scope (``solar.gqa``, ``solar.kda``,
``solar.experts.route``, ``solar.experts.experts``, ``solar.experts.shared``,
``solar.head``) that the lowering opens as a ``jax.named_scope``.
"""
import numpy as np

import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from . import decoder_blocks as blocks
from .decode_utils import (DecodeModel, StateEntry, require_rows_only,
                           update_cache)

__all__ = ["SolarOpen2Config", "build_prefill", "build_chunk", "build_step",
           "param_shapes"]

DTYPE = "bfloat16"
GQA, KDA = "gqa", "kda"
KDA_PROMPT_ROWS = 4096     # positions of a prompt a run of a delta-rule
# layer's mixer takes: q, k, v, the decay and the convolutions' float32
# copies are runs x 8,192 wide (0.13 GB each, not 0.54 at 16,384). Also the
# rows of a chunk (:func:`build_chunk`, ``DecodeModel.chunk_rows``): a chunk
# is one such run, and one call of the routed layer (MOE_PROMPT_ROWS)


class SolarOpen2Config:
    """Sizes under the names of the family's ``config.json``. ``kinds``
    lists each layer's mixer (``"gqa"`` or ``"kda"``). ``held`` is the
    contiguous range ``(first, count)`` of each layer's ``num_experts``
    routed experts that live here; the router keeps its full width.
    ``vocab`` rows of the embedding and of the head are held."""

    def __init__(self, kinds, vocab, hidden, heads, kv_heads, head_dim,
                 kda_heads, kda_head_dim, conv_kernel, kda_rank, moe_ffn,
                 shared_ffn, num_experts, held, top_k, beta_scale=2.0,
                 routed_scale=1.0, eps=1e-5, max_positions=None):
        self.kinds = tuple(kinds)
        if set(self.kinds) - {GQA, KDA}:
            raise ValueError("layer kinds %r other than %r and %r"
                             % (self.kinds, GQA, KDA))
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        self.head_dim = int(head_dim)
        self.kda_heads, self.kda_head_dim = int(kda_heads), int(kda_head_dim)
        self.conv_kernel, self.kda_rank = int(conv_kernel), int(kda_rank)
        self.moe_ffn, self.shared_ffn = int(moe_ffn), int(shared_ffn)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.num_experts:
            raise ValueError("held experts %r outside [0, %d)"
                             % (self.held, self.num_experts))
        self.beta_scale = float(beta_scale)
        self.routed_scale, self.eps = float(routed_scale), float(eps)
        self.max_positions = max_positions and int(max_positions)

    @classmethod
    def from_hf(cls, m, router_experts=None, first_expert=0):
        """From a dict with the keys of the published ``config.json``:
        ``gqa_layers`` the softmax layers held here, each followed by
        ``gqa_interval`` delta-rule layers (``num_hidden_layers`` is not
        read). ``n_routed_experts`` is the number of experts held here, from
        ``first_expert`` on, of the ``router_experts`` (default: the same
        number) that the router spans. ``kda_rank`` (default: the linear
        layers' head size) is the rank of the decay's and the gate's
        low-rank pairs. What the keys name and this file does not build is
        refused."""
        lin = m["linear_attn_config"]
        for key, want in (("use_rope", False), ("use_gqa_gate", True),
                          ("kda_use_full_proj", False),
                          ("first_k_dense_replace", 0),
                          ("norm_topk_prob", True),
                          ("tie_word_embeddings", False)):
            if m.get(key, want) != want:
                raise ValueError("%s = %r is not built (only %r)"
                                 % (key, m[key], want))
        if lin.get("num_kv_heads") not in (None, lin["num_heads"]):
            raise ValueError("linear_attn_config.num_kv_heads = %r is not "
                             "built (only as many as num_heads)"
                             % (lin["num_kv_heads"],))
        count = int(m["n_routed_experts"])
        kinds = [k for _ in m["gqa_layers"]
                 for k in [GQA] + [KDA] * int(m["gqa_interval"])]
        return cls(
            kinds=kinds, vocab=m["vocab_size"], hidden=m["hidden_size"],
            heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], head_dim=m["head_dim"],
            kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"],
            kda_rank=m.get("kda_rank", lin["head_dim"]),
            moe_ffn=m["moe_intermediate_size"],
            shared_ffn=m["moe_intermediate_size"] * m["n_shared_experts"],
            num_experts=int(router_experts or count),
            held=(int(first_expert), count), top_k=m["num_experts_per_tok"],
            beta_scale=2.0 if m.get("kda_allow_neg_eigval") else 1.0,
            routed_scale=m.get("routed_scaling_factor", 1.0),
            eps=m.get("rms_norm_eps", 1e-5),
            max_positions=m.get("max_position_embeddings"))

    # -- derived sizes ---------------------------------------------------
    @property
    def kv_width(self):
        return self.kv_heads * self.head_dim

    @property
    def kda_width(self):
        return self.kda_heads * self.kda_head_dim

    @property
    def num_layers(self):
        return len(self.kinds)

    def decode_model(self, cache_len, kv_dtype="fp32"):
        """Builders and state declaration for ``serving.DecodeEngine``.
        ``kv_dtype`` names a residency of K/V rows alone and is refused
        for anything but the default."""
        import jax.numpy as jnp

        if self.max_positions and int(cache_len) > self.max_positions:
            raise ValueError("cache_len %d is over the model's %d positions"
                             % (cache_len, self.max_positions))
        state = []
        for i, kind in enumerate(self.kinds):
            if kind == GQA:
                state += [StateEntry("%s_%d" % (part, i),
                                     (int(cache_len), self.kv_width),
                                     jnp.bfloat16, "rows")
                          for part in ("k", "v")]
                continue
            state += [StateEntry("conv_%s_%d" % (part, i),
                                 (self.conv_kernel - 1, self.kda_width),
                                 jnp.bfloat16, "fixed") for part in "qkv"]
            state.append(StateEntry(
                "kda_%d" % i, (self.kda_heads, self.kda_head_dim,
                               self.kda_head_dim), np.float32, "fixed"))
        model = DecodeModel(self, state, build_prefill, build_step,
                            step_counters=self._step_counters,
                            build_chunk=build_chunk,
                            chunk_rows=KDA_PROMPT_ROWS)
        if kv_dtype != "fp32":
            require_rows_only(model, "kv_dtype=%r" % (kv_dtype,))
        return model

    def _step_counters(self, aux, live):
        """The step's counts (:func:`build_step`) -> lifetime counters: per
        layer the assignments that landed on held experts, the largest
        count on one held expert and the held experts that got any, summed
        over the layers; all assignments of the live tokens; the delta-rule
        states that belong to a live slot against those the step updated;
        the K/V rows that hold a position of a live slot against the rows
        the step's attention went over."""
        moe = np.asarray(aux[:-4]).reshape(self.num_layers, -1)
        return {"moe_assignments_held": int(moe[:, 0].sum()),
                "moe_assignments_total":
                    int(live) * self.top_k * self.num_layers,
                "moe_expert_load_max_sum": int(moe[:, 1].sum()),
                "moe_experts_touched_sum": int(moe[:, 2].sum()),
                "kda_states_live": int(aux[-4]),
                "kda_states_updated": int(aux[-3]),
                "kv_rows_live": int(aux[-2]), "kv_rows_read": int(aux[-1])}


def _gqa_inputs(u, cfg, n):
    """u (B, lead, H) -> q (B, lead, heads * dh), k, v (B, lead, kv width)."""
    return (blocks.fc(u, cfg.heads * cfg.head_dim, n + ".attn.q", 2),
            blocks.fc(u, cfg.kv_width, n + ".attn.k", 2),
            blocks.fc(u, cfg.kv_width, n + ".attn.v", 2))


def _gqa_out(a, u, cfg, n):
    """``Wo (a * sigmoid(u Wg))``, the gate elementwise."""
    gate = layers.sigmoid(
        blocks.fc(u, cfg.heads * cfg.head_dim, n + ".attn.g", 2))
    return blocks.fc(layers.elementwise_mul(a, gate), cfg.hidden,
                     n + ".attn.o", 2)


def _kda_inputs(u, cfg, n):
    """u (B, lead, H) -> the three projections before their convolutions
    (B, lead, width), the raw log decay (B, lead, width) and the raw beta
    (B, lead, heads), both float32."""
    k = n + ".kda"
    q, kk, v = (blocks.fc(u, cfg.kda_width, "%s.%s" % (k, part), 2)
                for part in "qkv")
    g = layers.dense_acc32(blocks.fc(u, cfg.kda_rank, k + ".fa", 2),
                           cfg.kda_width, k + ".fb")
    return q, kk, v, g, layers.dense_acc32(u, cfg.kda_heads, k + ".b")


def _kda_out(o, u, lead, cfg, n):
    """``Wo (RMSNorm_head(o) * sigmoid((u Wga) Wgb))``: o, u (B, lead, .)."""
    k = n + ".kda"
    o = layers.rms_norm(
        layers.reshape(o, [-1, lead, cfg.kda_heads, cfg.kda_head_dim]),
        k + ".o_norm", epsilon=cfg.eps)
    gate = layers.sigmoid(blocks.fc(blocks.fc(u, cfg.kda_rank, k + ".ga", 2),
                                    cfg.kda_width, k + ".gb", 2))
    return blocks.fc(layers.elementwise_mul(
        layers.reshape(o, [-1, lead, cfg.kda_width]), gate), cfg.hidden,
        k + ".o", 2)


def _kda_args(cfg):
    return dict(heads=cfg.kda_heads, head_dim=cfg.kda_head_dim,
                beta_scale=cfg.beta_scale)


def _kda_prompt(u, plen, prompt_len, cfg, n, carried=None):
    """A delta-rule layer's mixer over a right-padded prompt u (1, P, H) of
    ``plen`` (1, 1) real tokens, in runs of KDA_PROMPT_ROWS positions, each
    from the windows and the state the run before it hands on (``layers.
    causal_conv1d`` and ``layers.kda_scan`` both take what they carry), so
    that the float32 copies alive are a run's and not the prompt's; the
    first from ``carried`` (the three windows and the state an earlier
    program handed on; zeros without). -> (what the mixer adds (1, P, H),
    [the three windows and the state at the last real token])."""
    run = (KDA_PROMPT_ROWS if prompt_len > KDA_PROMPT_ROWS
           and prompt_len % KDA_PROMPT_ROWS == 0 else prompt_len)
    windows, s, outs = [None] * 3, None, []
    if carried:
        *windows, s = carried
    for at in range(0, prompt_len, run):
        part, left = u, plen
        if run < prompt_len:
            part = layers.slice(u, [1], [at], [at + run])
            # the run's own count of real tokens: 0 leaves windows and
            # state as they came in
            left = layers.elementwise_min(
                layers.elementwise_max(
                    layers.scale(plen, scale=1.0, bias=float(-at)),
                    layers.fill_constant([1], "int64", 0)),
                layers.fill_constant([1], "int64", run))
        q, k, v, g, beta = _kda_inputs(part, cfg, n)
        mixed = []
        for j, (name, proj) in enumerate(zip("qkv", (q, k, v))):
            out, windows[j] = layers.causal_conv1d(
                proj, cfg.conv_kernel, "%s.kda.%s_conv" % (n, name),
                state=windows[j], length=left, bias=False)
            mixed.append(out)
        o, s = layers.kda_scan(*mixed, g, beta, n + ".kda", length=left,
                               state=s, **_kda_args(cfg))
        outs.append(_kda_out(o, part, run, cfg, n))
    y = outs[0] if len(outs) == 1 else layers.concat(outs, axis=1)
    return y, windows + [s]


def _feed_forward(w, cfg, i, live, counts, routed):
    """The layer's second half on flat rows (T, H); a prompt's routed layer
    in calls of ``blocks.MOE_PROMPT_ROWS`` tokens."""
    n = "so%d" % i
    part = blocks.routed_in_calls(
        w, live, counts, cfg.num_experts, cfg.top_k, cfg.held, cfg.moe_ffn,
        n + ".moe", "solar.experts", scale=cfg.routed_scale)
    routed.append(part)
    with fluid.name_scope("solar.experts.shared"):
        shared = blocks.swiglu(w, cfg.shared_ffn, cfg.hidden,
                               n + ".moe.shared")
    return layers.elementwise_add(part, shared)


def _embed(ids, cfg):
    return layers.embedding(ids, size=[cfg.vocab, cfg.hidden], dtype=DTYPE,
                            param_attr=ParamAttr(name="so.emb"))


def _head(x, cfg):
    with fluid.name_scope("solar.head"):
        return blocks.greedy_head(x, cfg.vocab, cfg.eps, "so.norm_f",
                                  "so.head")


def build_prefill(cfg, prompt_len, cache_len):
    """Slot-prefill program: one pass over a right-padded prompt bucket.
    Feeds ``so_prefill_ids`` (1, prompt_len) int64 and ``so_prefill_len``
    (1, 1); the batch is one sequence (the routed layer takes flat rows of
    a static count). Fetches the greedy token after the last real position
    and the sequence's state in the declaration's order: K/V rows ``(1,
    cache_len, kv width)`` zero past ``len``; the three windows and the
    delta rule's state AT THE LAST REAL TOKEN (padded positions get ``beta
    = 0`` and no decay, the windows are cut at ``len``), not at the
    bucket's end. ``moe_routed`` names, per layer, the held experts' part
    ``(prompt_len, hidden)``; ``attn_in`` / ``attn_out``, per layer, the
    stream before the layer and what its mixer adds to it ``(1,
    prompt_len, hidden)``: for whoever wants to fetch them (the engine
    does not)."""
    from .gpt import _row_coords

    if not 1 <= prompt_len <= cache_len:
        raise ValueError("need 1 <= prompt_len (%d) <= cache_len (%d)"
                         % (prompt_len, cache_len))
    ids = fluid.data("so_prefill_ids", shape=[1, prompt_len], dtype="int64")
    plen = fluid.data("so_prefill_len", shape=[1, 1], dtype="int64")
    x = layers.reshape(_embed(ids, cfg), [1, prompt_len, cfg.hidden])
    steps = layers.unsqueeze(layers.range(0, prompt_len, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, plen), DTYPE)   # (1, P)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [prompt_len, 1])
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i, kind in enumerate(cfg.kinds):
        n = "so%d" % i
        attn_in.append(x)
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        if kind == GQA:
            with fluid.name_scope("solar.gqa"):
                q, k, v = _gqa_inputs(u, cfg, n)
                y = _gqa_out(layers.gqa_attention(q, k, v, cfg.heads,
                                                  cfg.kv_heads), u, cfg, n)
                for rows in (k, v):
                    rows = layers.elementwise_mul(rows, valid3)
                    if cache_len > prompt_len:
                        rows = layers.concat([rows, layers.fill_constant(
                            [1, cache_len - prompt_len, cfg.kv_width], DTYPE,
                            0.0)], axis=1)
                    state.append(rows)
        else:
            with fluid.name_scope("solar.kda"):
                y, handed = _kda_prompt(u, plen, prompt_len, cfg, n)
                state += handed
        attn_out.append(y)
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
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["so_prefill_ids", "so_prefill_len"],
            "fetch_vars": [nxt] + state}


def build_chunk(cfg, rows, cache_len):
    """A prefill that CONTINUES: ``rows`` positions of a prompt from the
    state the chunks before handed on (zeros before the first), so that the
    engine can run a decode step between two chunks of a long prompt. Feeds
    ``so_chunk_ids`` (1, rows) int64, ``so_chunk_len`` (1, 1) the real
    tokens of THIS chunk (right-padded), ``so_chunk_start`` (1, 1) the row
    of its first position (a multiple of ``rows``; ``start + rows <=
    cache_len``), and the sequence's state, one feed ``(1,) + entry.shape``
    per declared entry (``cache_feed_names``), all donated. Fetches the
    greedy token after the chunk's last real position and the state carried
    on, in the same order (what :func:`build_prefill` fetches once the last
    chunk has run): K and V with the chunk's rows written at ``start``
    (zeros past ``len``) and the chunk's queries against the rows ``[0,
    start + len)`` (``layers.gqa_attention(offset=start)``); each delta-rule
    layer one run of :func:`_kda_prompt` from the carried windows and state;
    the routed layer one call. The same runs at the same sizes as a
    ``rows``-long part of the one-shot program, in the same precisions."""
    from .gpt import _row_coords

    if not 1 <= rows <= cache_len:
        raise ValueError("need 1 <= rows (%d) <= cache_len (%d)"
                         % (rows, cache_len))
    ids = fluid.data("so_chunk_ids", shape=[1, rows], dtype="int64")
    clen = fluid.data("so_chunk_len", shape=[1, 1], dtype="int64")
    start = fluid.data("so_chunk_start", shape=[1, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("so_chunk_" + e.name, shape=[1] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    by_name = {e.name: f for e, f in zip(decl, feeds)}
    x = layers.reshape(_embed(ids, cfg), [1, rows, cfg.hidden])
    steps = layers.unsqueeze(layers.range(0, rows, 1, "int64"), [0])
    valid = layers.cast(layers.less_than(steps, clen), DTYPE)   # (1, rows)
    valid3 = layers.unsqueeze(valid, [2])
    live = layers.reshape(valid, [rows, 1])
    state, counts, routed = [], [], []
    for i, kind in enumerate(cfg.kinds):
        n = "so%d" % i
        u = layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps)
        if kind == GQA:
            with fluid.name_scope("solar.gqa"):
                q, k, v = _gqa_inputs(u, cfg, n)
                k, v = (update_cache(by_name["%s_%d" % (part, i)],
                                     layers.elementwise_mul(new, valid3),
                                     pos=start)
                        for part, new in (("k", k), ("v", v)))
                y = _gqa_out(layers.gqa_attention(
                    q, k, v, cfg.heads, cfg.kv_heads, offset=start), u, cfg, n)
                state += [k, v]
        else:
            with fluid.name_scope("solar.kda"):
                y, handed = _kda_prompt(
                    u, clen, rows, cfg, n,
                    carried=[by_name["conv_%s_%d" % (part, i)]
                             for part in "qkv"] + [by_name["kda_%d" % i]])
                state += handed
        x = layers.elementwise_add(x, y)
        w = layers.reshape(
            layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps),
            [rows, cfg.hidden])
        y = _feed_forward(w, cfg, i, live, counts, routed)
        x = layers.elementwise_add(
            x, layers.reshape(y, [1, rows, cfg.hidden]))
    # a chunk with no real token (never dispatched by the engine) reads row 0
    last = layers.elementwise_max(
        layers.elementwise_sub(clen, layers.fill_constant([1], "int64", 1)),
        layers.fill_constant([1], "int64", 0))
    logits, nxt = _head(layers.gather_nd(x, _row_coords(last)), cfg)
    names = [f.name for f in feeds]
    return {"ids": ids, "len": clen, "start": start, "next": nxt,
            "logits": logits, "state": state,
            "feed_names": ["so_chunk_ids", "so_chunk_len", "so_chunk_start"]
            + names,
            "cache_feed_names": names, "fetch_vars": [nxt] + state}


def build_step(cfg, cache_len):
    """One decode step for all slots. Feeds ``so_step_tok`` / ``so_step_pos``
    (S, 1) int64 and the state buffers, one feed per declared entry
    (``cache_feed_names``), all donated: K and V get one row written at
    each slot's ``pos`` and the query goes over the columns ``<= pos``;
    each window and each delta-rule state is replaced. Fetches the greedy
    tokens, the updated state in the same order, and ``counts`` int32: per
    layer the live tokens' assignments that landed on held experts, the
    largest count on one held expert, the held experts that got any and
    the sorted rows the experts' loops covered; then the delta-rule states
    of live slots, the states the step updated (every slot's), the K/V
    rows that hold a position of a live slot and the rows the attention
    went over (all slots, every column). A slot with ``pos == 0`` is dead:
    its row is computed and ignored, and it is routed to no expert.
    ``attn_in`` / ``attn_out`` as :func:`build_prefill`'s, ``(S,
    hidden)``."""
    tok = fluid.data("so_step_tok", shape=[None, 1], dtype="int64")
    pos = fluid.data("so_step_pos", shape=[None, 1], dtype="int64")
    decl = cfg.decode_model(cache_len).state
    feeds = [fluid.data("so_step_" + e.name, shape=[None] + list(e.shape),
                        dtype=str(np.dtype(e.dtype))) for e in decl]
    by_name = {e.name: f for e, f in zip(decl, feeds)}
    x = layers.reshape(_embed(tok, cfg), [-1, cfg.hidden])       # (S, H)
    alive = layers.greater_than(pos, layers.fill_constant([1], "int64", 0))
    live = layers.cast(alive, DTYPE)                             # (S, 1)
    state, counts, routed, attn_in, attn_out = [], [], [], [], []
    for i, kind in enumerate(cfg.kinds):
        n = "so%d" % i
        attn_in.append(x)
        u = layers.unsqueeze(
            layers.rms_norm(x, n + ".attn_norm", epsilon=cfg.eps), [1])
        if kind == GQA:
            with fluid.name_scope("solar.gqa"):
                q, k, v = _gqa_inputs(u, cfg, n)
                k = update_cache(by_name["k_%d" % i], k, pos=pos,
                                 per_row=True)
                v = update_cache(by_name["v_%d" % i], v, pos=pos,
                                 per_row=True)
                y = _gqa_out(layers.gqa_attention(
                    q, k, v, cfg.heads, cfg.kv_heads, pos=pos), u, cfg, n)
                state += [k, v]
        else:
            with fluid.name_scope("solar.kda"):
                q, k, v, g, beta = _kda_inputs(u, cfg, n)
                mixed = []
                for part, proj in zip("qkv", (q, k, v)):
                    out, window = layers.causal_conv1d(
                        proj, cfg.conv_kernel, "%s.kda.%s_conv" % (n, part),
                        state=by_name["conv_%s_%d" % (part, i)], bias=False)
                    mixed.append(layers.squeeze(out, [1]))
                    state.append(window)
                o, s = layers.kda_step(
                    *mixed, layers.squeeze(g, [1]), layers.squeeze(beta, [1]),
                    by_name["kda_%d" % i], n + ".kda", **_kda_args(cfg))
                state.append(s)
                y = _kda_out(layers.unsqueeze(o, [1]), u, 1, cfg, n)
        y = layers.squeeze(y, [1])
        attn_out.append(y)
        x = layers.elementwise_add(x, y)
        w = layers.rms_norm(x, n + ".mlp_norm", epsilon=cfg.eps)
        x = layers.elementwise_add(
            x, _feed_forward(w, cfg, i, live, counts, routed))
    logits, nxt = _head(x, cfg)
    n_gqa = cfg.kinds.count(GQA)
    n_kda = cfg.num_layers - n_gqa
    alive64 = layers.cast(alive, "int64")
    held_rows = layers.elementwise_mul(
        alive64, layers.scale(pos, scale=1.0, bias=1.0))         # (S, 1)

    def total(v, times):
        return layers.reshape(layers.cast(layers.reduce_sum(
            layers.scale(v, scale=float(times))), "int32"), [1])

    def every_slot(value):
        return layers.fill_constant_batch_size_like(
            pos, shape=[-1, 1], dtype="int64", value=value)

    aux = layers.concat(
        [layers.reshape(c, [-1]) for c in counts]
        + [total(alive64, n_kda), total(every_slot(1), n_kda),
           total(held_rows, n_gqa), total(every_slot(int(cache_len)), n_gqa)],
        axis=0)
    names = [f.name for f in feeds]
    return {"tok": tok, "pos": pos, "next": nxt, "logits": logits,
            "state": state, "counts": aux, "moe_routed": routed,
            "attn_in": attn_in, "attn_out": attn_out,
            "feed_names": ["so_step_tok", "so_step_pos"] + names,
            "cache_feed_names": names,
            "fetch_vars": [nxt] + state + [aux]}


def param_shapes(cfg):
    """{name: (shape, dtype name)} of every parameter the programs read:
    what a checkpoint for this model holds."""
    h = cfg.hidden
    qw, lw = cfg.heads * cfg.head_dim, cfg.kda_width
    out = {"so.emb": ((cfg.vocab, h), DTYPE),
           "so.head.w": ((h, cfg.vocab), DTYPE),
           "so.norm_f.w": ((h,), DTYPE)}
    for i, kind in enumerate(cfg.kinds):
        n = "so%d" % i
        out.update({n + ".attn_norm.w": ((h,), DTYPE),
                    n + ".mlp_norm.w": ((h,), DTYPE)})
        if kind == GQA:
            a = n + ".attn"
            out.update({a + ".q.w": ((h, qw), DTYPE),
                        a + ".k.w": ((h, cfg.kv_width), DTYPE),
                        a + ".v.w": ((h, cfg.kv_width), DTYPE),
                        a + ".g.w": ((h, qw), DTYPE),
                        a + ".o.w": ((qw, h), DTYPE)})
        else:
            k = n + ".kda"
            for part in "qkv":
                out["%s.%s.w" % (k, part)] = ((h, lw), DTYPE)
                out["%s.%s_conv.w" % (k, part)] = ((lw, cfg.conv_kernel),
                                                   DTYPE)
            out.update({k + ".fa.w": ((h, cfg.kda_rank), DTYPE),
                        k + ".fb.w": ((cfg.kda_rank, lw), DTYPE),
                        k + ".A_log": ((cfg.kda_heads,), "float32"),
                        k + ".dt_bias": ((lw,), "float32"),
                        k + ".b.w": ((h, cfg.kda_heads), DTYPE),
                        k + ".ga.w": ((h, cfg.kda_rank), DTYPE),
                        k + ".gb.w": ((cfg.kda_rank, lw), DTYPE),
                        k + ".o_norm.w": ((cfg.kda_head_dim,), DTYPE),
                        k + ".o.w": ((lw, h), DTYPE)})
        e, held = n + ".moe", cfg.held[1]
        for part, wide in (("w1", True), ("w3", True), ("w2", False)):
            out["%s.shared.%s.w" % (e, part)] = (
                (h, cfg.shared_ffn) if wide else (cfg.shared_ffn, h), DTYPE)
            out["%s.experts.%s" % (e, part)] = (
                (held, h, cfg.moe_ffn) if wide else (held, cfg.moe_ffn, h),
                DTYPE)
        out.update({e + ".gate.w": ((h, cfg.num_experts), DTYPE),
                    e + ".gate.bias": ((cfg.num_experts,), "float32")})
    return out
