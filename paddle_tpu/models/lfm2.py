"""LFM2-MoE family decoder (gated short convolutions + grouped-query
attention with rotary positions and per-head QK RMS-norm + sigmoid-routed
SwiGLU experts) as a causal-LM pretraining program.

Every block is pre-norm residual twice over: ``h = x + Op(RMSNorm(x))``,
``y = h + FF(RMSNorm(h))``. ``layer_types`` gives one ``Op`` per block,
``conv`` (``[B, C, u] = W_in x``, a depthwise causal convolution of ``B *
u`` over ``conv_L_cache`` positions, ``W_out (C * conv)``) or
``full_attention``; ``FF`` is a dense SwiGLU MLP in the first
``num_dense_layers`` blocks and after them an expert layer of which this
chip holds a contiguous range (``parallel.moe.held_experts_ffn``): the
router spans all ``num_experts`` and every token keeps its published
``top_k``; what the experts held elsewhere would add is left out. With
``bias_update_rate`` every step moves each router's ``expert_bias`` towards
an even load over ALL its experts (the auxiliary-loss-free balancing rule):
on one chip's share the experts held elsewhere answer nothing, so the
gradient through the weights on each assignment is a partial sum (the held
experts' terms alone), and applied it teaches the routers AND every layer
under them to raise the held experts' scores. Where fewer experts are
held than the router spans (``Lfm2Config.router_trains`` false) that
gradient is therefore computed down to the routers' matrices, reaches the
optimizer's state, and moves nothing until an exchange makes it whole. The
embedding is tied to the head, of which ``vocab`` rows are held. No bias
anywhere, no dropout.

Built from Fluid ops only and trained the way every other model here is:
``decorate(Adam, use_bf16=True).minimize(loss)`` and ``Executor.run``.
Under that rewrite the matrix products (``mul``, the experts' grouped
products, the head) take bfloat16 operands and accumulate in float32;
master weights, the residual stream, norms, the router and the loss stay
float32.

Ops appended here carry a name scope (``lfm2.conv``, ``lfm2.attn``,
``lfm2.mlp``, ``lfm2.moe.route``, ``lfm2.moe.experts``, ``lfm2.head``)
that the lowering opens as a ``jax.named_scope``: a device trace groups the
step's operations by them, all blocks of a kind under one name.
"""
import paddle_tpu.fluid as fluid
from paddle_tpu.fluid import layers
from paddle_tpu.fluid.param_attr import ParamAttr

from . import decoder_blocks as blocks

__all__ = ["Lfm2Config", "build_lfm2_pretrain", "param_shapes",
           "step_counters"]

KINDS = ("conv", "full_attention")


class Lfm2Config:
    """Sizes under the names of the family's ``config.json``. ``held`` is
    the contiguous range ``(first, count)`` of each expert layer's
    ``num_experts`` that live here; ``vocab`` the rows of the tied
    embedding held."""

    def __init__(self, layer_types, vocab, hidden, heads, kv_heads, ffn,
                 moe_ffn, num_dense_layers, num_experts, held, top_k,
                 conv_kernel=3, rope_theta=1e6, routed_scale=1.0,
                 norm_topk_prob=True, eps=1e-5, route_eps=1e-6,
                 bias_update_rate=0.0):
        layer_types = tuple(layer_types)
        if set(layer_types) - set(KINDS):
            raise ValueError("layer_types %r has kinds other than %s"
                             % (layer_types, KINDS))
        if not norm_topk_prob:
            raise ValueError("the router op normalises over the k chosen "
                             "(norm_topk_prob); false is not built")
        self.layer_types = layer_types
        self.vocab, self.hidden = int(vocab), int(hidden)
        self.heads, self.kv_heads = int(heads), int(kv_heads)
        if self.hidden % self.heads or self.heads % self.kv_heads:
            raise ValueError("hidden %d / heads %d / kv heads %d do not "
                             "divide" % (hidden, heads, kv_heads))
        self.head_dim = self.hidden // self.heads
        self.ffn, self.moe_ffn = int(ffn), int(moe_ffn)
        self.num_dense_layers = int(num_dense_layers)
        self.num_experts, self.top_k = int(num_experts), int(top_k)
        self.held = (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] <= sum(self.held) <= self.num_experts:
            raise ValueError("held experts %r outside [0, %d)"
                             % (self.held, self.num_experts))
        self.conv_kernel = int(conv_kernel)
        self.rope_theta, self.routed_scale = float(rope_theta), float(
            routed_scale)
        self.eps, self.route_eps = float(eps), float(route_eps)
        self.bias_update_rate = float(bias_update_rate)

    @classmethod
    def from_hf(cls, m, router_experts=None, first_expert=0,
                bias_update_rate=0.0):
        """From a dict with the keys of the published ``config.json``.
        ``num_experts`` is the number held here, from ``first_expert`` on,
        of the ``router_experts`` (default: the same number) the router
        spans; ``num_hidden_layers`` is not read: the depth is the length
        of ``layer_types``. ``bias_update_rate`` > 0 (the published keys
        give none) has every training step move each router's
        ``expert_bias`` towards an even load (``layers.moe_route_topk``)."""
        if m.get("conv_bias"):
            raise ValueError("conv_bias true is not built")
        count = int(m["num_experts"])
        return cls(
            layer_types=m["layer_types"], vocab=m["vocab_size"],
            hidden=m["hidden_size"], heads=m["num_attention_heads"],
            kv_heads=m["num_key_value_heads"], ffn=m["intermediate_size"],
            moe_ffn=m["moe_intermediate_size"],
            num_dense_layers=m["num_dense_layers"],
            num_experts=int(router_experts or count),
            held=(int(first_expert), count), top_k=m["num_experts_per_tok"],
            conv_kernel=m["conv_L_cache"], rope_theta=m["rope_theta"],
            routed_scale=m.get("routed_scaling_factor", 1.0),
            norm_topk_prob=m.get("norm_topk_prob", True),
            eps=m.get("norm_eps", 1e-5), bias_update_rate=bias_update_rate)

    @property
    def router_trains(self):
        """Whether the gradient through the weights on each assignment is
        whole here, which it is only where every expert the router spans
        is held; a partial sum is computed, kept in the optimizer's state
        and applied nowhere (``layers.moe_route_topk(apply_gradient=)``)."""
        return self.held[1] == self.num_experts

    @property
    def kv_width(self):
        return self.kv_heads * self.head_dim


def _fc(x, size, name):
    return blocks.fc(x, size, name, nfd=2)


def _conv(h, cfg, n):
    """Gated short convolution over (B, T, H)."""
    b, c, u = layers.split(_fc(h, 3 * cfg.hidden, n + ".in"), 3, dim=-1)
    conv, _ = layers.causal_conv1d(
        layers.elementwise_mul(b, u), cfg.conv_kernel, n + ".k", bias=False,
        activation=None)
    return _fc(layers.elementwise_mul(c, conv), cfg.hidden, n + ".out")


def _attention(h, cfg, n, seq_len):
    """Causal grouped-query attention: per-head RMS norm of q and k, then
    the rotary term, then softmax(q k^T / sqrt(head_dim)) v."""
    def heads(x, count, norm):
        x = layers.reshape(x, [-1, seq_len, count, cfg.head_dim])
        x = layers.rms_norm(x, n + norm, epsilon=cfg.eps)
        x = layers.rotary_embedding(x, cfg.rope_theta)
        return layers.reshape(x, [-1, seq_len, count * cfg.head_dim])

    q = heads(_fc(h, cfg.hidden, n + ".q"), cfg.heads, ".q_norm")
    k = heads(_fc(h, cfg.kv_width, n + ".k"), cfg.kv_heads, ".k_norm")
    v = _fc(h, cfg.kv_width, n + ".v")
    a = layers.gqa_attention(q, k, v, cfg.heads, cfg.kv_heads)
    return _fc(a, cfg.hidden, n + ".o")


def _experts(h, cfg, n, seq_len):
    """-> (the held experts' part (B, T, H), its counts (4,) int32)."""
    out, counts = blocks.routed_gated_experts(
        layers.reshape(h, [-1, cfg.hidden]), cfg.num_experts, cfg.top_k,
        cfg.held, cfg.moe_ffn, n, "lfm2.moe", scale=cfg.routed_scale,
        norm_eps=cfg.route_eps, bias_update_rate=cfg.bias_update_rate,
        apply_gradient=cfg.router_trains)
    return layers.reshape(out, [-1, seq_len, cfg.hidden]), counts


def build_lfm2_pretrain(cfg, seq_len):
    """Build the next-token pretraining graph in the current default
    programs. Feeds ``input_ids`` and ``labels`` (B, seq_len) int64; the
    label of position t is the id at t + 1, and -1 (ignored) at the last
    position of a row, so the loss is the mean cross-entropy over the
    B * (seq_len - 1) labelled positions. Returns the interface variables:
    ``loss``; ``moe_counts`` (expert layers, 4) int32, per expert layer the
    assignments that landed on held experts, the largest count on one held
    expert, the held experts that got any and the sorted rows the layer's
    loops covered; ``head_rows`` and
    ``head_chunks`` as the fused head counts them; ``block_outputs``, the
    residual stream after each block, which a ``RecomputeOptimizer`` takes
    as checkpoints where the activations of a step do not fit."""
    ids = fluid.data(name="input_ids", shape=[None, seq_len], dtype="int64")
    labels = fluid.data(name="labels", shape=[None, seq_len], dtype="int64")
    x = layers.embedding(ids, size=[cfg.vocab, cfg.hidden],
                         param_attr=ParamAttr(name="lfm2.emb"))
    counts, block_outputs = [], []
    for i, kind in enumerate(cfg.layer_types):
        n = "lfm2.l%d" % i
        h = layers.rms_norm(x, n + ".op_norm", epsilon=cfg.eps)
        if kind == "conv":
            with fluid.name_scope("lfm2.conv"):
                y = _conv(h, cfg, n + ".conv")
        else:
            with fluid.name_scope("lfm2.attn"):
                y = _attention(h, cfg, n + ".attn", seq_len)
        x = layers.elementwise_add(x, y)
        h = layers.rms_norm(x, n + ".ffn_norm", epsilon=cfg.eps)
        if i < cfg.num_dense_layers:
            with fluid.name_scope("lfm2.mlp"):
                y = blocks.swiglu(h, cfg.ffn, cfg.hidden, n + ".mlp", nfd=2)
        else:
            y, c = _experts(h, cfg, n + ".moe", seq_len)
            counts.append(c)
        x = layers.elementwise_add(x, y)
        block_outputs.append(x)
    with fluid.name_scope("lfm2.head"):
        x = layers.rms_norm(x, "lfm2.norm_f", epsilon=cfg.eps)
        emb = fluid.default_main_program().global_block().var("lfm2.emb")
        loss, head_rows, head_chunks = (
            layers.linear_softmax_with_cross_entropy(
                x, emb, labels, ignore_index=-1, return_counts=True))
        # the ignored positions add zeros: mean over all, times T / (T - 1)
        loss = layers.scale(layers.mean(loss),
                            scale=seq_len / float(seq_len - 1))
    moe_counts = (layers.stack(counts, axis=0) if counts else
                  layers.fill_constant([0, 4], "int32", 0))
    moe_counts.stop_gradient = True
    return {"input_ids": ids, "labels": labels, "loss": loss,
            "moe_counts": moe_counts, "head_rows": head_rows,
            "head_chunks": head_chunks, "block_outputs": block_outputs}


def param_shapes(cfg):
    """{name: shape} of every parameter the program reads, float32: what a
    checkpoint of this model holds. ``<layer>.moe.gate.bias`` is the
    router's score correction, a buffer no optimizer trains."""
    h = cfg.hidden
    out = {"lfm2.emb": (cfg.vocab, h), "lfm2.norm_f.w": (h,)}
    for i, kind in enumerate(cfg.layer_types):
        n = "lfm2.l%d" % i
        out[n + ".op_norm.w"] = out[n + ".ffn_norm.w"] = (h,)
        if kind == "conv":
            out.update({n + ".conv.in.w": (h, 3 * h),
                        n + ".conv.k.w": (h, cfg.conv_kernel),
                        n + ".conv.out.w": (h, h)})
        else:
            out.update({n + ".attn.q.w": (h, h),
                        n + ".attn.k.w": (h, cfg.kv_width),
                        n + ".attn.v.w": (h, cfg.kv_width),
                        n + ".attn.o.w": (h, h),
                        n + ".attn.q_norm.w": (cfg.head_dim,),
                        n + ".attn.k_norm.w": (cfg.head_dim,)})
        if i < cfg.num_dense_layers:
            out.update({n + ".mlp.w1.w": (h, cfg.ffn),
                        n + ".mlp.w3.w": (h, cfg.ffn),
                        n + ".mlp.w2.w": (cfg.ffn, h)})
        else:
            held = cfg.held[1]
            out.update({n + ".moe.gate.w": (h, cfg.num_experts),
                        n + ".moe.gate.bias": (cfg.num_experts,),
                        n + ".moe.experts.w1": (held, h, cfg.moe_ffn),
                        n + ".moe.experts.w3": (held, h, cfg.moe_ffn),
                        n + ".moe.experts.w2": (held, cfg.moe_ffn, h)})
    return out


def step_counters(moe_counts, head_rows=None, head_chunks=None, steps=1):
    """The fetched counts of one or more steps -> the counters a trainer
    publishes, under the names the decode engine's counters have:
    ``moe_counts`` (..., expert layers, 3 or 4) summed over whatever
    leads, the fourth column (``moe_rows_covered``: sorted rows the gated
    experts' loops went over) where the program counts it; adds them to
    the telemetry hub (``lfm2.<name>``) and returns them."""
    import numpy as np

    from .. import observability as obs

    c = np.asarray(moe_counts)
    c = c.reshape(-1, c.shape[-1]).sum(0)
    out = {"steps": int(steps), "moe_assignments_held": int(c[0]),
           "moe_expert_load_max_sum": int(c[1]),
           "moe_experts_touched_sum": int(c[2])}
    if len(c) > 3:
        out["moe_rows_covered"] = int(c[3])
    if head_rows is not None:
        out["head_rows"] = int(np.asarray(head_rows).sum())
    if head_chunks is not None:
        out["head_chunks"] = int(np.asarray(head_chunks).sum())
    for name, value in out.items():
        obs.inc("lfm2." + name, value)
    return out
