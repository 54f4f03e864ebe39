"""Plain reference of LFM2-MoE next-token pretraining (gated short
convolutions + grouped-query attention with rotary positions and per-head
QK RMS-norm + sigmoid-routed SwiGLU experts): forward, loss, gradients by
`jax.grad`, textbook Adam, all in float32 `jax.numpy` at matmul precision
"highest". No kernel, no sorting, no grouped product: the experts are a
dense loop over the held range, every held expert over every token, times
the router's weight (zero where the token did not choose it). Imports
nothing of paddle_tpu.

Every block: ``h = x + Op(RMSNorm(x))``; ``y = h + FF(RMSNorm(h))``.

- ``conv``: ``[B, C, u] = split3(W_in x)``; ``c_t = sum_j k[:, j] (B u)_{t
  - (K-1) + j}`` (depthwise, causal, zeros before the row's start, no
  bias, no activation); ``W_out (C * c)``.
- ``full_attention``: ``q, k, v``; per head ``RMSNorm_dh`` of q and k with
  learned gains; rotary over the whole head (half-split pairs, positions
  0..T-1); causal softmax of ``q k^T / sqrt(dh)``; ``W_o``. Computed a
  block of queries at a time so that no (heads, T, T) array exists.
- dense MLP (blocks before ``num_dense_layers``): ``W_2 (silu(W_1 x) * W_3
  x)``.
- experts: ``s = sigmoid(W_g x)`` over ALL ``router_experts``; the k
  largest of ``s + bias`` chosen; ``g = s[chosen] / (sum s[chosen] + 1e-6)``
  times ``routed_scaling_factor``, normalised BEFORE any expert is left
  out; ``sum over the chosen experts held here of g_e W2_e (silu(W1_e x) *
  W3_e x)``. What the experts held elsewhere would add is left out, as the
  system leaves it out. After every training step ``bias`` moves by the
  auxiliary-loss-free balancing rule (`updated_expert_bias`), from the
  step's own counts over all ``router_experts``.

Final RMSNorm, logits ``x E^T`` over the held rows of the tied embedding,
loss = sum of next-token cross-entropies over the labelled positions
(label >= 0) / their number.

`m` is the configuration file's top-level values (the published names)
plus `router_experts` (the router's width) and `first_expert` (where the
held range starts); `num_experts` is the number held.

The seeded weights are made here, leaf by leaf on the device, keyed by the
system's parameter names, and handed to it; never read back from it. The
router's score correction is then balanced by the family's own rule
(`balanced_expert_bias`): with raw random draws the share of a step's
assignments that lands on the held experts differs by a tenth from seed to
seed, and a training step's time with it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

from . import blocks

F32 = jnp.float32
ROUTE_EPS = 1e-6
QUERY_BLOCK = 512
PROBE_TOKENS = 4096     # the sequence the score correction is balanced on
BALANCE_ROUNDS = 200    # rounds of the rule a layer
BALANCE_STEP = 1e-3     # what a round moves an expert's correction by


def head_dim(m):
    return m["hidden_size"] // m["num_attention_heads"]


def weight_shapes(m):
    """{name: (shape, how it is initialised)}; `bias` leaves are buffers
    (the router's score correction), not trained."""
    h, dh = m["hidden_size"], head_dim(m)
    kvw = m["num_key_value_heads"] * dh
    out = {"lfm2.emb": ((m["vocab_size"], h), "normal"),
           "lfm2.norm_f.w": ((h,), "one")}
    for i, kind in enumerate(m["layer_types"]):
        n = "lfm2.l%d." % i
        out[n + "op_norm.w"] = out[n + "ffn_norm.w"] = ((h,), "one")
        if kind == "conv":
            out.update({n + "conv.in.w": ((h, 3 * h), "normal"),
                        n + "conv.k.w": ((h, m["conv_L_cache"]), "normal"),
                        n + "conv.out.w": ((h, h), "normal")})
        else:
            out.update({n + "attn.q.w": ((h, h), "normal"),
                        n + "attn.k.w": ((h, kvw), "normal"),
                        n + "attn.v.w": ((h, kvw), "normal"),
                        n + "attn.o.w": ((h, h), "normal"),
                        n + "attn.q_norm.w": ((dh,), "one"),
                        n + "attn.k_norm.w": ((dh,), "one")})
        if i < m["num_dense_layers"]:
            f = m["intermediate_size"]
            out.update({n + "mlp.w1.w": ((h, f), "normal"),
                        n + "mlp.w3.w": ((h, f), "normal"),
                        n + "mlp.w2.w": ((f, h), "normal")})
        else:
            f, held = m["moe_intermediate_size"], m["num_experts"]
            out.update({
                n + "moe.gate.w": ((h, m["router_experts"]), "normal"),
                n + "moe.gate.bias": ((m["router_experts"],), "normal"),
                n + "moe.experts.w1": ((held, h, f), "normal"),
                n + "moe.experts.w3": ((held, h, f), "normal"),
                n + "moe.experts.w2": ((held, f, h), "normal")})
    return out


def trained(m):
    """Names of the leaves an optimizer trains, sorted."""
    return sorted(n for n in weight_shapes(m) if not n.endswith(".bias"))


@functools.lru_cache(maxsize=None)
def _leaf_maker(shape, how, std):
    @jax.jit
    def make(key):
        x = std * jax.random.normal(key, shape, F32)
        return 1.0 + x if how == "one" else x

    return make


def iter_weights(m, seed):
    """(name, leaf) for every leaf from the seed, one jitted draw per leaf
    on the default device (leaves of one shape share a program), in the
    names' order: matrices, the convolution kernels and the router's score
    correction normal(0, initializer_range), norm gains 1 + that. A caller
    that drops each leaf before it takes the next holds one at a time."""
    key = blocks.mask_key(seed)
    std = float(m.get("initializer_range", 0.02))
    for i, (name, (shape, how)) in enumerate(sorted(
            weight_shapes(m).items())):
        yield name, _leaf_maker(tuple(shape), how, std)(
            jax.random.fold_in(key, i))


def make_weights(m, seed):
    """Every leaf, the score corrections balanced."""
    w = dict(iter_weights(m, seed))
    w.update(balanced_expert_bias(w, m, seed))
    return w


# -- the layers, one sequence (T, H) at a time ---------------------------
def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def rotary(x, theta):
    """x (T, heads, dh): pair (x[i], x[i + dh/2]) of every head turned by
    t * theta^(-2i/dh)."""
    t, dh = x.shape[0], x.shape[-1]
    half = dh // 2
    freq = theta ** (-jnp.arange(half, dtype=F32) * 2.0 / dh)
    ang = jnp.arange(t, dtype=F32)[:, None, None] * freq
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)


def short_conv(x, bw, m, rnd):
    k, t = m["conv_L_cache"], x.shape[0]
    b, c, u = jnp.split(blocks.matmul(x, bw["conv.in.w"], rnd), 3, -1)
    v = jnp.concatenate([jnp.zeros((k - 1, b.shape[-1]), F32), b * u], 0)
    conv = sum(v[j:j + t] * bw["conv.k.w"][:, j] for j in range(k))
    return blocks.matmul(c * conv, bw["conv.out.w"], rnd)


def attention(x, bw, m, rnd):
    nq, nkv, dh = (m["num_attention_heads"], m["num_key_value_heads"],
                   head_dim(m))
    t, eps = x.shape[0], m["norm_eps"]
    q = blocks.matmul(x, bw["attn.q.w"], rnd).reshape(t, nq, dh)
    k = blocks.matmul(x, bw["attn.k.w"], rnd).reshape(t, nkv, dh)
    v = blocks.matmul(x, bw["attn.v.w"], rnd).reshape(t, nkv, dh)
    q = rotary(rms_norm(q, bw["attn.q_norm.w"], eps), m["rope_theta"])
    k = rotary(rms_norm(k, bw["attn.k_norm.w"], eps), m["rope_theta"])
    kh = jnp.swapaxes(jnp.repeat(k, nq // nkv, axis=1), 0, 1)   # (nq,T,dh)
    vh = jnp.swapaxes(jnp.repeat(v, nq // nkv, axis=1), 0, 1)
    qb = min(QUERY_BLOCK, t)
    if t % qb:
        raise ValueError("sequence %d is no multiple of the query block %d"
                         % (t, qb))
    pos = jnp.arange(t)

    @jax.checkpoint
    def block(q_blk, q_pos):
        """q_blk (qb, nq, dh) -> context (qb, nq * dh)."""
        scores = blocks.matmul(jnp.swapaxes(q_blk, 0, 1),
                               jnp.swapaxes(kh, 1, 2), rnd) * dh ** -0.5
        scores = jnp.where(pos[None, None, :] <= q_pos[None, :, None],
                           scores, -jnp.inf)
        ctx = blocks.matmul(jax.nn.softmax(scores, -1), vh, rnd)
        return jnp.swapaxes(ctx, 0, 1).reshape(qb, nq * dh)

    ctx = jax.lax.map(lambda a: block(*a), (q.reshape(t // qb, qb, nq, dh),
                                            pos.reshape(t // qb, qb)))
    return blocks.matmul(ctx.reshape(t, nq * dh), bw["attn.o.w"], rnd)


def mlp(x, w1, w3, w2, rnd):
    return blocks.matmul(jax.nn.silu(blocks.matmul(x, w1, rnd))
                         * blocks.matmul(x, w3, rnd), w2, rnd)


def router_trains(m):
    """The gradient through the weights on each assignment sums a term per
    chosen expert; with fewer experts held than the router spans it is a
    partial sum (the deployment adds the other chips' terms), which is
    computed and compared but applied nowhere."""
    return m["num_experts"] == m["router_experts"]


def route(x, bw, m):
    """-> ((T, router_experts) float32: each token's weight on every expert
    (zero on those it did not choose), normalised over the k chosen BEFORE
    any expert is left out, times the routed scaling factor; (router_experts,)
    float32: the tokens that chose each expert). Float32 at every precision:
    the router is not among the rounded products. On a share
    (`router_trains` false) the gradient through the weights stops at the
    router's matrix and does not enter `x`."""
    if not router_trains(m):
        x = jax.lax.stop_gradient(x)
    s = jax.nn.sigmoid(jnp.matmul(x, bw["moe.gate.w"], precision="highest"))
    _, idx = jax.lax.top_k(s + bw["moe.gate.bias"], m["num_experts_per_tok"])
    g = jnp.take_along_axis(s, idx, -1)
    g = g / (jnp.sum(g, -1, keepdims=True) + ROUTE_EPS)
    g = g * m["routed_scaling_factor"]
    got = jnp.zeros(s.shape[-1:], F32).at[idx.reshape(-1)].add(1.0)
    return (jnp.zeros_like(s).at[jnp.arange(x.shape[0])[:, None], idx].set(g),
            jax.lax.stop_gradient(got))


def experts(x, weights, w1, w3, w2, rnd):
    """sum over the given experts e of weights[:, e] * mlp_e(x), one expert
    at a time over all tokens. weights (T, experts given)."""

    def one(acc, ew):
        e1, e3, e2, col = ew
        return acc + col[:, None] * jax.checkpoint(
            functools.partial(mlp, rnd=rnd))(x, e1, e3, e2), None

    acc, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (w1, w3, w2, jnp.swapaxes(weights, 0, 1)))
    return acc


def held_experts(x, bw, m, rnd):
    """-> (the held experts' part, the router's counts over all experts)."""
    first, held = m["first_expert"], m["num_experts"]
    weights, got = route(x, bw, m)
    return experts(x, weights[:, first:first + held], bw["moe.experts.w1"],
                   bw["moe.experts.w3"], bw["moe.experts.w2"], rnd), got


def mixed(x, bw, m, rnd, kind):
    """x + Op(RMSNorm(x)) over one sequence (T, H)."""
    h = rms_norm(x, bw["op_norm.w"], m["norm_eps"])
    return x + (short_conv if kind == "conv" else attention)(h, bw, m, rnd)


def layer(x, bw, m, rnd, kind, dense):
    """One block over one sequence (T, H) -> (its output, the tokens that
    chose each of the router's experts; None for a dense block)."""
    x = mixed(x, bw, m, rnd, kind)
    h = rms_norm(x, bw["ffn_norm.w"], m["norm_eps"])
    if dense:
        return x + mlp(h, bw["mlp.w1.w"], bw["mlp.w3.w"], bw["mlp.w2.w"],
                       rnd), None
    out, got = held_experts(h, bw, m, rnd)
    return x + out, got


def balanced_expert_bias(w, m, seed):
    """{name: the score correction of that expert layer} after the family's
    load-balancing rule run at the seeded weights: over one probe sequence
    of PROBE_TOKENS ids drawn from the seed, block by block, BALANCE_ROUNDS
    times an expert that got fewer than its even share of the assignments
    has its correction raised by BALANCE_STEP, one that got more has it
    lowered; the balanced layer's held experts then feed the next block.
    `w` maps every name to its leaf (the system's own arrays will do);
    float32 at "highest", so both sides compute the same buffer. From there
    every training step moves it by the same rule (`follow`)."""
    k, experts = m["num_experts_per_tok"], m["router_experts"]
    dense_blocks = m["num_dense_layers"]
    same = lambda a: a  # noqa: E731 — no rounding

    def block(x, bw, kind, dense):
        x = mixed(x, bw, m, same, kind)
        h = rms_norm(x, bw["ffn_norm.w"], m["norm_eps"])
        if dense:
            return x + mlp(h, bw["mlp.w1.w"], bw["mlp.w3.w"],
                           bw["mlp.w2.w"], same), None
        s = jax.nn.sigmoid(jnp.matmul(h, bw["moe.gate.w"],
                                      precision="highest"))
        even = h.shape[0] * k / float(experts)

        def one(bias, _):
            _, idx = jax.lax.top_k(s + bias, k)
            got = jnp.zeros((experts,), F32).at[idx.reshape(-1)].add(1.0)
            return bias + BALANCE_STEP * jnp.sign(even - got), None

        bias, _ = jax.lax.scan(one, bw["moe.gate.bias"], None,
                               length=BALANCE_ROUNDS)
        bw = dict(bw, **{"moe.gate.bias": bias})
        return x + held_experts(h, bw, m, same)[0], bias

    low = min(1000, m["vocab_size"] // 2)
    key = jax.random.fold_in(blocks.mask_key(seed), 1 << 20)
    ids = jax.random.randint(key, (PROBE_TOKENS,), low, m["vocab_size"])
    out = {}
    with jax.default_matmul_precision("highest"):
        x = jnp.take(w["lfm2.emb"], ids, axis=0)
        for i, kind in enumerate(m["layer_types"]):
            dense = i < dense_blocks
            x, bias = jax.jit(functools.partial(
                block, kind=kind, dense=dense))(x, block_weights(w, i))
            if bias is not None:
                out["lfm2.l%d.moe.gate.bias" % i] = bias
    return out


def block_weights(w, i):
    n = "lfm2.l%d." % i
    return {k[len(n):]: v for k, v in w.items() if k.startswith(n)}


def sequence_loss(w, ids, labels, m, rnd):
    """ids, labels (T,) -> (sum of the cross-entropies of the labelled
    positions (label >= 0), {block: the tokens that chose each of its
    router's experts}). Each block is recomputed in the backward pass."""
    x, chosen = jnp.take(w["lfm2.emb"], ids, axis=0), {}
    for i, kind in enumerate(m["layer_types"]):
        x, got = jax.checkpoint(functools.partial(
            layer, m=m, rnd=rnd, kind=kind,
            dense=i < m["num_dense_layers"]))(x, block_weights(w, i))
        if got is not None:
            chosen[i] = got
    x = rms_norm(x, w["lfm2.norm_f.w"], m["norm_eps"])
    logp = jax.nn.log_softmax(blocks.matmul(x, w["lfm2.emb"].T, rnd), -1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None],
                                 -1)[:, 0]
    return -jnp.sum(jnp.where(labels >= 0, picked, 0.0)), chosen


def loss_sum(w, ids, labels, m, rnd):
    """ids, labels (rows, T) -> both sums over the rows."""
    per_row = jax.lax.map(
        lambda a: sequence_loss(w, a[0], a[1], m, rnd), (ids, labels))
    return jax.tree_util.tree_map(lambda x: jnp.sum(x, 0), per_row)


def next_token_labels(ids):
    """The label of position t is the id at t + 1; the last position of a
    row carries -1 (ignored)."""
    ids = np.asarray(ids)
    return np.concatenate(
        [ids[:, 1:], np.full((ids.shape[0], 1), -1, ids.dtype)], axis=1)


def adam(w, g, mom, vel, step, o, router=1.0):
    """One Adam step (Kingma & Ba, the bias correction folded into the
    step size); `step` counts from 1. Buffers (no gradient kept) stay. The
    routers' weights (`*.moe.gate.w`) take `router` times the step: 0 on a
    share, moments kept, nothing moved."""
    b1, b2 = o["beta1"], o["beta2"]
    lr_t = o["learning_rate"] * jnp.sqrt(1 - b2 ** step) / (1 - b1 ** step)

    def one(p, g_, m_, v_, scale):
        m_ = b1 * m_ + (1 - b1) * g_
        v_ = b2 * v_ + (1 - b2) * g_ * g_
        return p - scale * lr_t * m_ / (jnp.sqrt(v_) + o["epsilon"]), m_, v_

    out = {n: one(w[n], g[n], mom[n], vel[n],
                  router if n.endswith(".moe.gate.w") else 1.0) for n in g}
    return (dict(w, **{n: t[0] for n, t in out.items()}),
            {n: t[1] for n, t in out.items()},
            {n: t[2] for n, t in out.items()})


def updated_expert_bias(fixed, chosen, m, rate):
    """The routers' score corrections after one step of the auxiliary-loss-
    free balancing rule (Wang et al. 2024, arXiv:2408.15664): over the
    step's whole batch an expert that fewer tokens chose than the even share
    has its correction raised by `rate`, one that more chose has it lowered.
    `chosen` {block: (router_experts,) counts}; the step itself chose with
    the corrections as they were."""
    out = dict(fixed)
    for i, got in chosen.items():
        name = "lfm2.l%d.moe.gate.bias" % i
        out[name] = fixed[name] + rate * jnp.sign(jnp.sum(got) / got.shape[0]
                                                  - got)
    return out


def leaf_norms(tree):
    return {n: jnp.sqrt(jnp.sum(jnp.square(x))) for n, x in tree.items()}


def follow(m, seed, batches, optimizer, precision="float32", block_rows=1):
    """Train from the seeded weights over `batches` [(ids, labels), ...],
    the gradient of each batch accumulated over blocks of `block_rows` rows
    so that it fits beside nothing else on one chip. Returns the readings
    `correct` compares: each step's loss, the per-leaf norm of the first
    gradient, the per-leaf norm of the parameters' change (trained leaves
    only). `optimizer["expert_bias_update_rate"]` moves the routers' score
    corrections after every step (`updated_expert_bias`); on a share the
    gradient through the weights on each assignment stays out of the hidden
    states and out of the routers' matrices (`router_trains`). `precision`
    other than float32 rounds the operands of every matrix product but the
    router's: the control."""
    rnd = blocks.rounder(precision)
    names = trained(m)

    def split(w):
        return ({n: w[n] for n in names},
                {n: v for n, v in w.items() if n not in names})

    def loss_of(train, fixed, ids, labels):
        return loss_sum(dict(train, **fixed), ids, labels, m, rnd)

    @functools.partial(jax.jit, donate_argnums=(4, 5))
    def block_grad(train, fixed, ids, labels, acc_l, acc_g, acc_c):
        (l, c), g = jax.value_and_grad(loss_of, has_aux=True)(
            train, fixed, ids, labels)
        add = functools.partial(jax.tree_util.tree_map, jnp.add)
        return acc_l + l, add(acc_g, g), add(acc_c, c)

    rate = float(optimizer.get("expert_bias_update_rate", 0.0))
    new_bias = jax.jit(functools.partial(updated_expert_bias, m=m, rate=rate))

    adam_step = jax.jit(functools.partial(
        adam, o=optimizer, router=float(router_trains(m))),
        donate_argnums=(0, 2, 3))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t))
    norms = jax.jit(leaf_norms)
    scaled = jax.jit(lambda t, k: jax.tree_util.tree_map(
        lambda g: g * k, t), donate_argnums=(0,))
    delta_norms = jax.jit(lambda a, b: leaf_norms(
        {n: a[n] - b[n] for n in names}))

    with jax.default_matmul_precision("highest"):
        w = make_weights(m, seed)
        train, fixed = split(w)
        del w
        mom, vel = zeros(train), zeros(train)
        losses, grad_norms = [], None
        for step, (ids, labels) in enumerate(batches, start=1):
            if ids.shape[0] % block_rows:
                raise ValueError("batch of %d rows is not a multiple of %d"
                                 % (ids.shape[0], block_rows))
            labelled = int((np.asarray(labels) >= 0).sum())
            acc_l, acc_g = jnp.zeros((), F32), zeros(train)
            acc_c = {i: jnp.zeros((m["router_experts"],), F32)
                     for i in range(m["num_dense_layers"],
                                    len(m["layer_types"]))}
            for r in range(0, ids.shape[0], block_rows):
                acc_l, acc_g, acc_c = block_grad(
                    train, fixed,
                    jnp.asarray(ids[r:r + block_rows].astype(np.int32)),
                    jnp.asarray(labels[r:r + block_rows].astype(np.int32)),
                    acc_l, acc_g, acc_c)
            if rate and acc_c:
                fixed = new_bias(fixed, acc_c)
            acc_g = scaled(acc_g, jnp.float32(1.0 / labelled))
            losses.append(float(acc_l) / labelled)
            if step == 1:
                grad_norms = {n: float(v) for n, v in norms(acc_g).items()}
            train, mom, vel = adam_step(train, acc_g, mom, vel,
                                        jnp.float32(step))
            del acc_g
        del mom, vel
        # the seeded weights again, to measure the change from
        w0, _ = split(make_weights(m, seed))
        change = {n: float(v) for n, v in delta_norms(train, w0).items()}
    return {"loss": losses, "grad_norm": grad_norms, "change_norm": change}
