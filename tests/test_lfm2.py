"""LFM2-MoE on the training path, at small sizes on the CPU: every op the
model adds or changes against the plain reference's function (forward and
gradient), the whole program against the reference over three steps, the
four shares of an expert layer against the uncut layer, and what must not
move for the served hybrid."""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

import paddle_tpu.fluid as fluid
from benchmark.reference import lfm2_moe_lm as ref
from paddle_tpu.fluid import executor, framework, unique_name
from paddle_tpu.fluid.contrib.mixed_precision import decorate
from paddle_tpu.models import lfm2
from paddle_tpu.ops import hybrid_ops
from paddle_tpu.ops.pallas_attention import flash_attention
from paddle_tpu.ops.registry import LowerContext, get_lowering

M = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         intermediate_size=96, moe_intermediate_size=48,
         layer_types=["conv", "full_attention", "conv"], num_dense_layers=1,
         num_experts=4, num_experts_per_tok=2, conv_L_cache=3,
         conv_bias=False, rope_theta=1e6, routed_scaling_factor=1,
         norm_topk_prob=True, norm_eps=1e-5, vocab_size=128,
         router_experts=8, first_expert=2, initializer_range=0.08)
WHOLE = dict(M, num_experts=8, first_expert=0)   # every expert held
OPT = dict(learning_rate=1e-3, beta1=0.9, beta2=0.95, epsilon=1e-8)
NONE = lambda x: x  # noqa: E731 — the reference's "no rounding"
CTX = LowerContext(platform="cpu")


def lower(op, ins, attrs=None, ctx=CTX):
    return get_lowering(op)(ctx, ins, attrs or {})


def close(a, b, tol=2e-5):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert a.shape == b.shape
    assert np.abs(a - b).max() <= tol * max(1.0, np.abs(b).max()), (
        np.abs(a - b).max(), np.abs(b).max())


@pytest.fixture
def fresh_programs():
    framework.switch_main_program(framework.Program())
    framework.switch_startup_program(framework.Program())
    unique_name.switch()
    executor._scope_stack[:] = [executor.Scope()]
    yield
    executor._scope_stack[:] = [executor.Scope()]


def rand(seed, *shape, scale=1.0):
    return scale * jax.random.normal(jax.random.PRNGKey(seed), shape,
                                     jnp.float32)


# -- attention: QK-norm + rotary + causal GQA ----------------------------
def op_attention(x, bw, t):
    """The model's chain of ops over one sequence (1, T, H)."""
    nq, nkv, dh = 4, 2, 16

    def heads(v, n, gain):
        v = v.reshape(1, t, n, dh)
        v = lower("rms_norm", {"X": [v], "Scale": [gain]},
                  {"epsilon": 1e-5})["Y"][0]
        v = lower("rotary_embedding", {"X": [v]}, {"theta": 1e6})["Out"][0]
        return v.reshape(1, t, n * dh)

    q = heads(x @ bw["attn.q.w"], nq, bw["attn.q_norm.w"])
    k = heads(x @ bw["attn.k.w"], nkv, bw["attn.k_norm.w"])
    a = lower("gqa_attention", {"Q": [q], "K": [k], "V": [x @ bw["attn.v.w"]]},
              {"heads": nq, "kv_heads": nkv})["Out"][0]
    return a @ bw["attn.o.w"]


def attention_weights():
    return {"attn.q.w": rand(1, 64, 64, scale=0.1),
            "attn.k.w": rand(2, 64, 32, scale=0.1),
            "attn.v.w": rand(3, 64, 32, scale=0.1),
            "attn.o.w": rand(4, 64, 64, scale=0.1),
            "attn.q_norm.w": 1 + rand(5, 16, scale=0.1),
            "attn.k_norm.w": 1 + rand(6, 16, scale=0.1)}


def test_rotary_qk_norm_attention_against_the_reference():
    t, bw = 24, attention_weights()
    x = rand(7, t, 64)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(op_attention(x[None], bw, t))),
            (0, 1))(x, bw)
        want, want_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(ref.attention(x, bw, M, NONE))),
            (0, 1))(x, bw)
    close(got, want)
    close(got_g[0], want_g[0])
    for n in bw:
        close(got_g[1][n], want_g[1][n])


def test_rotary_turns_pairs_by_position_and_keeps_the_norm():
    x = rand(8, 2, 5, 3, 16)
    out = lower("rotary_embedding", {"X": [x]}, {"theta": 1e4})["Out"][0]
    close(out[:, 0], x[:, 0])                       # position 0: unturned
    close(jnp.sum(out ** 2, -1), jnp.sum(x ** 2, -1))
    close(out[0], jnp.stack([ref.rotary(x[0][:, h:h + 1], 1e4)[:, 0]
                             for h in range(3)], 1))


def test_flash_kernels_agree_with_the_xla_path_forward_and_backward():
    """The path the TPU takes from FLASH_MIN_SEQ on, in interpret mode:
    repeated key/value heads through the three flash kernels against the
    grouped einsum of the XLA path, values and all three gradients."""
    b, t, nq, nkv, dh = 2, 64, 4, 2, 16
    q, k, v = (rand(9, b, t, nq * dh), rand(10, b, t, nkv * dh),
               rand(11, b, t, nkv * dh))

    def xla(q, k, v):
        return lower("gqa_attention", {"Q": [q], "K": [k], "V": [v]},
                     {"heads": nq, "kv_heads": nkv})["Out"][0]

    def flash(q, k, v):
        def hf(x, n):
            return jnp.swapaxes(x.reshape(b, t, n, dh), 1, 2)
        out = flash_attention(
            hf(q, nq), jnp.repeat(hf(k, nkv), nq // nkv, 1),
            jnp.repeat(hf(v, nkv), nq // nkv, 1), causal=True, block_q=32,
            block_k=32, interpret=True)
        return jnp.swapaxes(out, 1, 2).reshape(b, t, nq * dh)

    close(flash(q, k, v), xla(q, k, v), 1e-4)
    w = rand(12, b, t, nq * dh)
    g_flash = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    g_xla = jax.grad(lambda *a: jnp.sum(xla(*a) * w), (0, 1, 2))(q, k, v)
    for a, e in zip(g_flash, g_xla):
        close(a, e, 1e-4)


def test_flash_is_taken_on_the_tpu_from_the_ops_own_length_on():
    """The op chooses from what it sees: causal (no `Pos`), at least
    FLASH_MIN_SEQ positions, a TPU, no mesh. No caller sets anything."""
    def text(platform, t, mesh_axes=None, pos=False):
        q = jax.ShapeDtypeStruct((1, t, 64), jnp.bfloat16)
        kv = jax.ShapeDtypeStruct((1, t, 32), jnp.bfloat16)
        p = jax.ShapeDtypeStruct((1, 1), jnp.int32)
        ctx = LowerContext(platform=platform)
        ctx.mesh_axes = mesh_axes

        def f(q, k, v, p):
            ins = {"Q": [q[:, :1] if pos else q], "K": [k], "V": [v]}
            if pos:
                ins["Pos"] = [p]
            return lower("gqa_attention", ins, {"heads": 4, "kv_heads": 2},
                         ctx)["Out"][0]
        return jax.jit(f).trace(q, kv, kv, p).lower(
            lowering_platforms=(platform,)).as_text()

    long, short = hybrid_ops.FLASH_MIN_SEQ, hybrid_ops.FLASH_MIN_SEQ // 2
    assert "flash_fwd" in text("tpu", long)
    assert "tpu_custom_call" not in text("tpu", short)
    assert "tpu_custom_call" not in text("cpu", long)
    assert "tpu_custom_call" not in text("tpu", long, mesh_axes=("dp",))
    assert "tpu_custom_call" not in text("tpu", long, pos=True)


# -- the gated short convolution -----------------------------------------
def test_causal_conv_k3_without_bias_or_activation_against_the_reference():
    t = 20
    bw = {"conv.in.w": rand(13, 64, 192, scale=0.1),
          "conv.k.w": rand(14, 64, 3), "conv.out.w": rand(15, 64, 64,
                                                          scale=0.1)}

    def op(x, bw):
        b, c, u = jnp.split(x @ bw["conv.in.w"], 3, -1)
        outs = lower("causal_conv1d",
                     {"X": [(b * u)[None]], "Weight": [bw["conv.k.w"]]},
                     {"activation": ""})
        assert outs["StateOut"][0].shape == (1, 2, 64)
        return (c * outs["Out"][0][0]) @ bw["conv.out.w"]

    x = rand(16, t, 64)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(op(x, bw))), (0, 1))(x, bw)
        want, want_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(ref.short_conv(x, bw, M, NONE))),
            (0, 1))(x, bw)
    close(got, want)
    close(got_g[0], want_g[0])
    for n in bw:
        close(got_g[1][n], want_g[1][n])


# -- router and gated held experts ---------------------------------------
def expert_weights(held=4, experts=8, seed=20):
    return {"moe.gate.w": rand(seed, 64, experts, scale=0.3),
            "moe.gate.bias": rand(seed + 1, experts, scale=0.3),
            "moe.experts.w1": rand(seed + 2, held, 64, 48, scale=0.1),
            "moe.experts.w3": rand(seed + 3, held, 64, 48, scale=0.1),
            "moe.experts.w2": rand(seed + 4, held, 48, 64, scale=0.1)}


def op_experts(x, bw, first, k=2, share=False):
    r = lower("moe_route_topk", {"X": [x], "Gate": [bw["moe.gate.w"]],
                                 "Bias": [bw["moe.gate.bias"]]},
              {"k": k, "scale": 1.0, "norm_eps": 1e-6,
               "detach_input": share})
    out = lower("held_experts_ffn", {
        "X": [x], "Index": [r["Index"][0]], "Weight": [r["Weight"][0]],
        "W1": [bw["moe.experts.w1"]], "W3": [bw["moe.experts.w3"]],
        "W2": [bw["moe.experts.w2"]]}, {"first_expert": first})
    return out["Out"][0], out["Counts"][0]


@pytest.mark.parametrize("share", [True, False],
                         ids=["a_share", "every_expert_held"])
def test_gated_held_experts_and_their_gradients_against_the_reference(share):
    """Values and gradients to the tokens, the three matrices and, through
    the weights on each assignment, the router's matrix; none to the score
    correction, which only chooses. On a share (experts 2..5 of 8) that
    gradient stops at the router's matrix (`detach_input`); with every
    expert held it goes on into the tokens."""
    m = M if share else WHOLE
    first, held = m["first_expert"], m["num_experts"]
    bw, x = expert_weights(held), rand(30, 40, 64)
    with jax.default_matmul_precision("highest"):
        got, got_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(
                op_experts(x, bw, first, share=share)[0])), (0, 1))(x, bw)
        want, want_g = jax.value_and_grad(
            lambda x, bw: jnp.sum(jnp.sin(
                ref.held_experts(x, bw, m, NONE)[0])), (0, 1))(x, bw)
    close(got, want)
    close(got_g[0], want_g[0])
    for n in bw:
        close(got_g[1][n], want_g[1][n])
    assert float(jnp.abs(got_g[1]["moe.gate.w"]).max()) > 1e-4
    assert float(jnp.abs(got_g[1]["moe.gate.bias"]).max()) == 0.0
    # what the layer counts: assignments on experts 2..5, the fullest, any
    weights = np.asarray(ref.route(x, bw, m)[0])[:, first:first + held]
    counts = np.asarray(op_experts(x, bw, first)[1])
    per_expert = (weights > 0).sum(0)
    assert counts.tolist() == [per_expert.sum(), per_expert.max(),
                               (per_expert > 0).sum(), 40 * 2]


def _gated_by_the_book(x, idx, wt, w1, w3, w2, first):
    """Every assignment on its own, no sort: the expert's matrices gathered
    per (token, slot), zero weight where the expert is held elsewhere."""
    e = idx - first
    here = (e >= 0) & (e < w1.shape[0])
    e = jnp.clip(e, 0, w1.shape[0] - 1)
    a = jnp.einsum("td,tkdf->tkf", x, w1[e])
    b = jnp.einsum("td,tkdf->tkf", x, w3[e])
    out = jnp.einsum("tkf,tkfd->tkd", jax.nn.silu(a) * b, w2[e])
    return jnp.sum(jnp.where(here, wt, 0.0)[:, :, None] * out, 1), None


def _gated_operands(rng, tokens, k, held, dtype=jnp.float32, d=64, f=48):
    """(x, wt, w1, w3, w2): rows and matrices in `dtype`, float32 weights
    on the assignments."""
    x = jnp.asarray(rng.normal(size=(tokens, d)), dtype)
    wt = jnp.asarray(0.2 + rng.random((tokens, k)), jnp.float32)
    w1, w3 = (jnp.asarray(0.1 * rng.normal(size=(held, d, f)), dtype)
              for _ in range(2))
    w2 = jnp.asarray(0.1 * rng.normal(size=(held, f, d)), dtype)
    return x, wt, w1, w3, w2


def _gated_loss(fn, idx, first, operands):
    """((sum(sin(out)), (out, counts)), the five gradients) of `fn`, a
    `gated_experts_sum`, over `operands`."""
    def f_(x, wt, w1, w3, w2):
        out, counts = fn(x, idx, wt, w1, w3, w2, first)
        return jnp.sum(jnp.sin(out)), (out, counts)
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(f_, (0, 1, 2, 3, 4), has_aux=True)(
            *operands)


CHUNK, TOKENS, SLOTS = 8, 12, 2     # 24 assignments: up to three trips


@pytest.mark.parametrize("held_rows", [0, 1, CHUNK - 1, CHUNK, CHUNK + 1,
                                       TOKENS * SLOTS])
def test_the_gated_loops_follow_the_held_rows_at_every_load(monkeypatch,
                                                            held_rows):
    """The loops of `gated_experts_sum` with a chunk of 8 sorted rows, at
    loads that end before, on and after a chunk's edge, none and ALL (every
    chosen expert held: three full trips): output and the gradients in x,
    wt and the three matrices against the unsorted sum; the rows covered
    are whole chunks over the held rows and no more; a token whose two
    experts are both held gets both."""
    monkeypatch.setattr(hybrid_ops, "GATED_CHUNK_ROWS", CHUNK)
    first, held = 2, 4
    rng = np.random.default_rng(held_rows)
    # `held_rows` of the 24 assignments on experts 2..5, the rest elsewhere;
    # the first tokens hold both of theirs
    idx = np.empty((TOKENS * SLOTS,), np.int64)
    idx[:held_rows] = first + np.arange(held_rows) % held
    idx[held_rows:] = np.where(np.arange(TOKENS * SLOTS - held_rows) % 2,
                               0, 7)
    idx = jnp.asarray(idx.reshape(TOKENS, SLOTS), jnp.int32)
    operands = x, wt, w1, w3, w2 = _gated_operands(rng, TOKENS, SLOTS, held)
    (got, (out, counts)), got_g = _gated_loss(
        hybrid_ops.gated_experts_sum, idx, first, operands)
    (want, (ref_out, _)), want_g = _gated_loss(
        _gated_by_the_book, idx, first, operands)
    close(got, want)
    close(out, ref_out)
    for a, e in zip(got_g, want_g):
        close(a, e)
    assert int(counts[0]) == held_rows
    assert int(counts[3]) == -(-held_rows // CHUNK) * CHUNK
    if held_rows >= 2:      # token 0 holds experts 2 and 3: both are in it
        alone = [_gated_by_the_book(x[:1], idx[:1, j:j + 1], wt[:1, j:j + 1],
                                    w1, w3, w2, first)[0] for j in range(2)]
        close(out[0], (alone[0] + alone[1])[0])
        assert float(jnp.abs(alone[1]).max()) > 1e-4


# what the sum back into the tokens must get right one token at a time, 8
# sorted rows a chunk (two tokens a trip): tokens, k, experts, first, held
SUM_BACK_CASES = {
    "every_choice_held_beside_none_held": (12, 4, 8, 2, 4),
    "ten_choices_with_dead_rows": (9, 10, 32, 8, 8),     # Laguna's, in small
    "tokens_no_multiple_of_the_chunk": (11, 2, 8, 2, 4),
    "three_choices_over_odd_tokens": (13, 3, 8, 2, 4),
    "bfloat16_rows": (16, 2, 8, 2, 4),
}


def _batch(case):
    """(operands, idx, first, live) of a named case: distinct experts a
    token, drawn evenly, but token 1's, which are all held elsewhere."""
    tokens, k, experts, first, held = SUM_BACK_CASES[case]
    rng = np.random.default_rng(tokens)
    idx = np.argsort(rng.random((tokens, experts)), axis=1)[:, :k]
    idx[1] = np.delete(np.arange(experts), first + np.arange(held))[:k]
    live = None
    if case.startswith("every"):
        idx[0] = first + np.arange(k)
    elif case.startswith("ten"):
        live = np.asarray([1, 0, 1, 1, 0, 1, 1, 1, 0], bool)
    dtype = jnp.bfloat16 if case.startswith("bfloat16") else jnp.float32
    return (_gated_operands(rng, tokens, k, held, dtype),
            jnp.asarray(idx, jnp.int32), first, live)


@pytest.mark.parametrize("case", list(SUM_BACK_CASES))
def test_the_sum_back_reads_each_tokens_rows_through_the_sorts_inverse(
        monkeypatch, case):
    """`gated_experts_sum`'s way back into the tokens is a read a choice
    through the inverse of the sort, a chunk of tokens a trip: output, the
    five gradients and the counts against the unsorted sum where a token
    holds all of its choices beside one that holds none, with ten choices
    and dead rows (`Live`, as the op masks them), with token counts that
    the chunk of tokens does not divide, and with bfloat16 rows against
    the float32 sum within bfloat16's rounding."""
    monkeypatch.setattr(hybrid_ops, "GATED_CHUNK_ROWS", CHUNK)
    operands, idx, first, live = _batch(case)
    x, wt, w1, w3, w2 = operands
    tokens, k = idx.shape
    if "tokens" in case:        # two tokens a trip at a chunk of 8
        assert tokens % 2
    masked = idx if live is None else jnp.where(live[:, None], idx, -1)
    (_, (out, counts)), got_g = _gated_loss(
        hybrid_ops.gated_experts_sum, masked, first, operands)
    (_, (ref_out, _)), want_g = _gated_loss(
        _gated_by_the_book, masked, first,
        [v.astype(jnp.float32) for v in operands])
    # bfloat16 keeps 8 bits: three roundings on the way (the two products,
    # the gate, the experts' outputs) and one of each gradient
    tol = 2e-5 if x.dtype == jnp.float32 else 3e-2
    assert out.dtype == jnp.float32
    close(out, ref_out, tol)
    for a, e in zip(got_g, want_g):
        close(a, e, tol)
    here = np.asarray((masked >= first) & (masked < first + w1.shape[0]))
    assert int(counts[0]) == here.sum()
    assert int(counts[3]) == -(-here.sum() // CHUNK) * CHUNK
    none = ~here.any(1)
    assert none.any() and not np.asarray(out)[none].any()
    assert not np.asarray(got_g[0], np.float32)[none].any()
    if case.startswith("every"):
        assert here[0].all() and none[1]
        alone = [_gated_by_the_book(x[:1], idx[:1, j:j + 1], wt[:1, j:j + 1],
                                    w1, w3, w2, first)[0] for j in range(k)]
        close(out[0], sum(alone)[0])
    if live is not None:        # the op's own mask does the same
        op = lower("held_experts_ffn", {
            "X": [x], "Index": [idx], "Weight": [wt], "W1": [w1],
            "W3": [w3], "W2": [w2],
            "Live": [jnp.asarray(live[:, None], jnp.float32)]},
            {"first_expert": first})
        close(op["Out"][0], out)
        assert none[~live].all() and op["Counts"][0].tolist() == \
            counts.tolist()


@pytest.mark.parametrize("weighted", [True, False],
                         ids=["forward_weighted", "backward_unweighted"])
def test_undefined_rows_past_the_held_ones_never_reach_a_token(weighted):
    """The grouped kernels leave the sorted rows past the n held ones
    undefined. `_sum_into_tokens` reads a row for every choice, held or
    not, and SELECTS: with those rows all NaN (times a zero weight they
    would stay NaN) the sums are finite and the held rows' own."""
    rng = np.random.default_rng(int(weighted))
    tokens, k, d, n = 10, 3, 16, 11
    here = np.zeros((tokens * k,), bool)
    here[rng.permutation(tokens * k)[:n]] = True
    order = np.argsort(~here, kind="stable")       # the held rows first
    inv = np.argsort(order).reshape(tokens, k)
    rows = rng.normal(size=(tokens * k, d)).astype(np.float32)
    rows[n:] = np.nan
    here = here.reshape(tokens, k)
    wt = np.where(here, rng.random((tokens, k)), 0.0).astype(np.float32)
    got = hybrid_ops._sum_into_tokens(
        jnp.asarray(rows), jnp.asarray(inv, jnp.int32), jnp.asarray(here),
        jnp.asarray(wt) if weighted else None)
    want = np.zeros((tokens, d), np.float32)
    for t_, j in zip(*np.nonzero(here)):
        want[t_] += rows[inv[t_, j]] * (wt[t_, j] if weighted else 1.0)
    assert np.isfinite(np.asarray(got)).all()
    close(got, want)


def test_poisoned_products_leave_output_and_gradients_finite(monkeypatch):
    """The whole function with every grouped product's rows past the held
    ones set to NaN, forward and backward: what it returns is finite and
    what it returned without the poison."""
    monkeypatch.setattr(hybrid_ops, "GATED_CHUNK_ROWS", CHUNK)
    operands, idx, first, _ = _batch("tokens_no_multiple_of_the_chunk")

    def grads():
        return _gated_loss(hybrid_ops.gated_experts_sum, idx, first,
                           operands)

    (_, (clean, _)), clean_g = grads()
    plain = hybrid_ops.grouped_dot

    def poisoned(xs, w, sizes, platform=None, out_dtype=jnp.float32):
        held = (jnp.arange(xs.shape[0]) < jnp.sum(sizes))[:, None]
        out = plain(jnp.where(held, xs, 0.0), w, sizes, platform, out_dtype)
        return jnp.where(held, out, jnp.nan)

    monkeypatch.setattr(hybrid_ops, "grouped_dot", poisoned)
    (_, (out, _)), got_g = grads()
    assert np.isfinite(np.asarray(out)).all()
    close(out, clean)
    for a, e in zip(got_g, clean_g):
        assert np.isfinite(np.asarray(a)).all()
        close(a, e)


def test_four_shares_of_two_experts_add_up_to_the_uncut_layer():
    """8 experts over 4 shares of 2 (`model-configs` section 4): each share
    routes over all 8 and computes its own two; their outputs add up to the
    reference's layer over all 8, each token's terms counted once."""
    whole = expert_weights(held=8, seed=40)
    x = rand(50, 33, 64)
    with jax.default_matmul_precision("highest"):
        uncut = ref.experts(x, ref.route(x, whole,
                                         dict(M, router_experts=8))[0],
                            whole["moe.experts.w1"], whole["moe.experts.w3"],
                            whole["moe.experts.w2"], NONE)
        total, held = jnp.zeros_like(x), 0
        for first in (0, 2, 4, 6):
            share = dict(whole, **{n: whole[n][first:first + 2] for n in (
                "moe.experts.w1", "moe.experts.w3", "moe.experts.w2")})
            out, counts = op_experts(x, share, first)
            close(out, ref.held_experts(x, share, dict(
                M, num_experts=2, first_expert=first), NONE)[0])
            total, held = total + out, held + int(counts[0])
    close(total, uncut)
    assert held == 33 * 2            # every assignment landed on one share


def test_router_epsilon_is_an_attribute_with_the_old_default():
    x, bw = rand(60, 5, 64), expert_weights()
    ins = {"X": [x], "Gate": [bw["moe.gate.w"]],
           "Bias": [bw["moe.gate.bias"]]}
    s = jax.nn.sigmoid(jnp.matmul(x, bw["moe.gate.w"], precision="highest"))
    _, idx = lax.top_k(s + bw["moe.gate.bias"], 2)
    chosen = jnp.take_along_axis(s, idx, -1)
    for attrs, eps in (({}, 1e-20), ({"norm_eps": 0.5}, 0.5)):
        w = lower("moe_route_topk", ins, dict(attrs, k=2))["Weight"][0]
        close(w, chosen / (chosen.sum(-1, keepdims=True) + eps), 1e-6)
    # the layer writes the attribute only when asked: the served hybrid's
    # programs are the ones they were
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        h = fluid.data("h", shape=[4, 64], dtype="float32")
        fluid.layers.moe_route_topk(h, 8, 2, "r0")
        fluid.layers.moe_route_topk(h, 8, 2, "r1", norm_eps=1e-6)
        ops = [o for o in fluid.default_main_program().global_block().ops
               if o.type == "moe_route_topk"]
        bias = fluid.default_main_program().global_block().var("r0.bias")
    assert "norm_eps" not in ops[0].attrs
    assert ops[1].attrs["norm_eps"] == 1e-6
    assert bias.trainable is False


def test_the_score_correction_is_balanced_at_the_seeded_weights():
    """`make_weights` hands over a score correction balanced by the
    family's rule: on fresh tokens the first expert layer's loads are
    nearer even than under the raw draw, and the same buffer comes out of
    the leaves a second time (what the system's adapter does with its own
    arrays)."""
    raw = dict(ref.iter_weights(M, 11))
    w = ref.make_weights(M, 11)
    bias = "lfm2.l1.moe.gate.bias"
    assert float(jnp.abs(w[bias] - raw[bias]).max()) > 0.01
    again = ref.balanced_expert_bias(raw, M, 11)
    assert sorted(again) == [bias, "lfm2.l2.moe.gate.bias"]
    close(again[bias], w[bias], 0)
    ids = jnp.asarray(np.random.default_rng(5).integers(64, 128, 2048))

    def spread(weights):
        with jax.default_matmul_precision("highest"):
            x = jnp.take(weights["lfm2.emb"], ids, axis=0)
            x, _ = ref.layer(x, ref.block_weights(weights, 0), M, NONE,
                             "conv", True)
            bw = ref.block_weights(weights, 1)
            x = ref.mixed(x, bw, M, NONE, "full_attention")
            h = ref.rms_norm(x, bw["ffn_norm.w"], M["norm_eps"])
            loads = np.asarray(ref.route(h, bw, M)[1])
        assert loads.sum() == 2048 * 2
        return loads.max() / loads.mean()

    even, uneven = spread(w), spread(raw)
    assert even < 1.15 and uneven > 1.3 * even, (even, uneven)


# -- what must not move for the served hybrid ------------------------------
def _held_experts_sum_of_pr27(x, idx, wt, w1, w2, first, live, platform):
    """`held_experts_sum` as PR 27 wrote it, kept here to compare with."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    def grouped(xs, w, sizes):
        if platform != "tpu":
            return lax.ragged_dot(xs, w, sizes,
                                  preferred_element_type=jnp.float32)
        m, k = xs.shape
        pad = (-m) % 128
        if pad:
            xs = jnp.pad(xs, ((0, pad), (0, 0)))
        return gmm(xs, w, sizes, jnp.float32, (128, k, w.shape[2]))[:m]

    t, k = idx.shape
    held_n = w1.shape[0]
    e = idx.reshape(-1) - jnp.int32(first)
    here = (e >= 0) & (e < held_n)
    here = here & jnp.repeat(live.reshape(-1).astype(bool), k)
    key = jnp.where(here, e, held_n)
    order = jnp.argsort(key)
    sizes = jnp.zeros((held_n + 1,), jnp.int32).at[key].add(1)[:held_n]
    xs = jnp.take(x, order // k, axis=0)
    hid = grouped(xs, w1, sizes)
    hid = jnp.square(jnp.maximum(hid, 0)).astype(x.dtype)
    out = grouped(hid, w2, sizes)
    keep = jnp.take(here, order)
    out = jnp.where(keep[:, None],
                    out * jnp.take(wt.reshape(-1), order)[:, None], 0.0)
    back = jnp.argsort(order)
    out = jnp.take(out, back, axis=0).reshape(t, k, -1).sum(1)
    counts = jnp.stack([jnp.sum(here.astype(jnp.int32)), jnp.max(sizes),
                        jnp.sum((sizes > 0).astype(jnp.int32))])
    return out, counts.astype(jnp.int32)


@pytest.mark.parametrize("platform", ["cpu", "tpu"])
def test_the_relu2_path_lowers_to_the_program_it_lowered_to(platform):
    """The served hybrid's expert call, lowered for the CPU and for the
    TPU: the same StableHLO as PR 27's function gives, once source
    locations (and the kernel's serialised body, which carries them) are
    taken out."""
    t, k, d, f, held = 16, 4, 128, 256, 4
    shapes = [jax.ShapeDtypeStruct(s, dt) for s, dt in (
        ((t, d), jnp.bfloat16), ((t, k), jnp.int32), ((t, k), jnp.float32),
        ((held, d, f), jnp.bfloat16), ((held, f, d), jnp.bfloat16),
        ((t, 1), jnp.bfloat16))]

    def text(fn):
        s = jax.jit(fn).trace(*shapes).lower(
            lowering_platforms=(platform,)).as_text()
        s = re.sub(r"loc\(.*?\)", "", s)
        return re.sub(r'\\22body\\22: \\22[^\\]*\\22', "BODY", s)

    new = text(lambda x, i, w, w1, w2, live: hybrid_ops.held_experts_sum(
        x, i, w, w1, w2, 2, live, platform=platform))
    old = text(lambda x, i, w, w1, w2, live: _held_experts_sum_of_pr27(
        x, i, w, w1, w2, 2, live, platform))
    assert new == old
    assert ("tpu_custom_call" in new) == (platform == "tpu")


def test_the_grouped_tile_follows_the_shape():
    tile = hybrid_ops.gmm_tiling
    # the served hybrid: a handful of rows an expert, its matrix whole
    assert tile(128 * 22, 1024, 2688, 128) == (128, 1024, 2688)
    assert tile(512 * 22, 2688, 1024, 128) == (128, 2688, 1024)
    # this model's matrix does not fit whole (2048 x 1792 > 3 Mi elements)
    assert 2048 * 1792 > hybrid_ops.GMM_TILE_ELEMENTS
    for k, n in ((2048, 1792), (1792, 2048)):
        rows, tk, tn = tile(4 * 4096 * 4, k, n, 8)      # the training step
        assert rows == hybrid_ops.GMM_TRAIN_ROWS
        assert tk == k                       # the contracted width whole
        assert n % tn == 0 and tn % 128 == 0
        assert n // 2 <= tn <= hybrid_ops.GMM_TRAIN_WIDTH
        rows, tk, tn = tile(64, k, n, 8)                # a decode step
        assert rows == 128 and k % tk == 0 and n % tn == 0
    with pytest.raises(ValueError, match="multiples of 128"):
        tile(1024, 2000, 1792, 8)


@pytest.fixture
def interpreted_kernels(monkeypatch):
    """The TPU branch of `grouped_dot` with its three Pallas kernels run
    in interpret mode (the chip runs them compiled)."""
    import functools

    package, kernels = hybrid_ops._megablox()
    monkeypatch.setattr(package, "gmm",
                        functools.partial(package.gmm, interpret=True))
    for name in ("gmm", "tgmm"):
        monkeypatch.setattr(kernels, name, functools.partial(
            getattr(kernels, name), interpret=True))


def test_the_tpu_branch_of_the_gated_experts_and_its_backward_kernels(
        interpreted_kernels):
    """`gmm`, `gmm` transposed and `tgmm` behind `grouped_dot`'s own
    backward rule, and the masking of the rows the kernels leave
    undefined: values and every gradient equal the ragged-dot path's."""
    t, k, held, d, f = 48, 4, 4, 128, 256
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(t, d)), jnp.float32)
    idx = jnp.asarray(np.stack([rng.permutation(16)[:k] for _ in range(t)]),
                      jnp.int32)
    wt = jnp.asarray(rng.random((t, k)), jnp.float32)
    w1, w3 = (jnp.asarray(0.1 * rng.normal(size=(held, d, f)), jnp.float32)
              for _ in range(2))
    w2 = jnp.asarray(0.1 * rng.normal(size=(held, f, d)), jnp.float32)

    def run(platform):
        def f_(x, wt, w1, w3, w2):
            out, counts = hybrid_ops.gated_experts_sum(
                x, idx, wt, w1, w3, w2, 4, platform)
            return jnp.sum(jnp.sin(out)), counts
        with jax.default_matmul_precision("highest"):
            return jax.value_and_grad(f_, (0, 1, 2, 3, 4), has_aux=True)(
                x, wt, w1, w3, w2)

    (got, n_got), g_got = run("tpu")
    (want, n_want), g_want = run(None)
    close(got, want, 1e-5)
    assert n_got.tolist() == n_want.tolist() and int(n_want[0]) > 0
    for a, e in zip(g_got, g_want):
        assert np.isfinite(np.asarray(a)).all()
        close(a, e, 1e-4)


# -- the whole program ------------------------------------------------------
RATE = 0.05    # of the balancing rule, large enough to move a choice here


def build(amp, recompute=(), m=M):
    cfg = lfm2.Lfm2Config.from_hf(m, router_experts=8,
                                  first_expert=m["first_expert"],
                                  bias_update_rate=RATE)
    vs = lfm2.build_lfm2_pretrain(cfg, 32)
    opt = fluid.optimizer.Adam(
        learning_rate=OPT["learning_rate"], beta1=OPT["beta1"],
        beta2=OPT["beta2"], epsilon=OPT["epsilon"])
    if recompute:
        opt = fluid.optimizer.RecomputeOptimizer(opt)
        opt._set_checkpoints([vs["block_outputs"][i] for i in recompute])
    (decorate(opt, use_bf16=True) if amp else opt).minimize(vs["loss"])
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    scope = fluid.global_scope()
    w = ref.make_weights(m, 7)
    assert set(w) == set(lfm2.param_shapes(cfg))
    for n, v in w.items():
        assert tuple(v.shape) == tuple(lfm2.param_shapes(cfg)[n])
        scope.update(n, v)
    # the executor donates what the scope holds: a second copy to compare
    return cfg, vs, exe, scope, ref.make_weights(m, 7)


def batches(n=3, rows=4, t=32):
    rng = np.random.default_rng(0)
    out = []
    for _ in range(n):
        ids = rng.integers(1, 128, (rows, t)).astype(np.int64)
        out.append((ids, ref.next_token_labels(ids)))
    return out


@pytest.fixture(scope="module")
def followed():
    """The reference's three steps over `batches()`, computed once a
    configuration: the float32, recompute and AMP programs are held to the
    same reference run (nothing of the program under test enters it)."""
    done = {}

    def follow(m):
        if id(m) not in done:
            done[id(m)] = ref.follow(
                m, 7, batches(), dict(OPT, expert_bias_update_rate=RATE),
                block_rows=2)
        return done[id(m)]

    return follow


@pytest.mark.parametrize("amp,recompute,tol,m", [
    (False, (), 2e-4, M), (False, (0, 1), 2e-4, M),
    (True, (), 0.05, M), (False, (), 2e-4, WHOLE)],
    ids=["float32", "recompute", "amp", "every_expert_held"])
def test_three_steps_of_the_program_against_the_reference(
        fresh_programs, followed, amp, recompute, tol, m):
    """Loss, per-leaf gradient norm and per-leaf change over three steps
    through `minimize` and `Executor.run`: to rounding in float32 (with and
    without recomputation), to bfloat16's in the AMP program. On one chip's
    share (4 of 8 experts) the gradient through the assignments' weights
    still reaches the routers' moments and is compared, their matrices
    stay, and nothing of it enters the hidden states; with every expert
    held it is whole and the routers train."""
    cfg, vs, exe, scope, w0 = build(amp, recompute, m)
    applied = cfg.router_trains
    assert applied == (m is WHOLE) == ref.router_trains(m)
    data = batches()
    losses, grad = [], None
    for ids, lab in data:
        out = exe.run(feed={"input_ids": ids, "labels": lab},
                      fetch_list=[vs["loss"], vs["moe_counts"],
                                  vs["head_rows"]])
        losses.append(float(out[0]))
        assert out[1].shape == (2, 4) and int(out[2]) == 4 * 31
        if grad is None:
            grad = {n: float(jnp.linalg.norm(scope.find_value(
                n + "_moment1_0"))) / (1 - OPT["beta1"])
                    for n in ref.trained(m)}
    want = followed(m)
    change = {n: float(jnp.linalg.norm(scope.find_value(n) - w0[n]))
              for n in ref.trained(m)}
    floor_g = float(np.median(list(want["grad_norm"].values())))
    floor_c = float(np.median(list(want["change_norm"].values())))
    for got, ref_loss in zip(losses, want["loss"]):
        assert abs(got - ref_loss) <= tol * 0.1 * abs(ref_loss)
    for n in ref.trained(m):
        assert abs(grad[n] - want["grad_norm"][n]) <= tol * max(
            want["grad_norm"][n], floor_g), n
        assert abs(change[n] - want["change_norm"][n]) <= tol * max(
            want["change_norm"][n], floor_c), n
    # the router's score correction is a buffer no optimizer trains (no
    # moment); the balancing rule moved it by the rate three times
    bias = "lfm2.l1.moe.gate.bias"
    assert scope.find_value(bias + "_moment1_0") is None
    moved = np.asarray(scope.find_value(bias) - w0[bias]) / RATE
    close(moved, np.round(moved), 1e-4)
    assert np.abs(moved).max() <= 3 and np.abs(moved).sum() > 0
    gate = "lfm2.l1.moe.gate.w"
    assert grad[gate] > 0 and want["grad_norm"][gate] > 0
    assert (change[gate] == 0) == (not applied)
    assert (want["change_norm"][gate] == 0) == (not applied)


def test_the_balancing_rule_moves_the_score_correction_by_the_calls_counts():
    """`moe_route_topk(bias_update_rate)`: BiasOut is Bias raised by the
    rate for an expert under the even share of this call's assignments,
    lowered for one over it, left where it got exactly that; the call's
    own choice used Bias as it was, and without the rate there is no such
    output (the served hybrid's program)."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
    gate = jnp.asarray(rng.normal(size=(16, 8)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.normal(size=(8,)), jnp.float32)
    ins = {"X": [x], "Gate": [gate], "Bias": [bias]}
    plain = lower("moe_route_topk", ins, {"k": 2}, LowerContext())
    assert "BiasOut" not in plain
    out = lower("moe_route_topk", ins, {"k": 2, "bias_update_rate": 0.01},
                LowerContext())
    assert np.array_equal(out["Index"][0], plain["Index"][0])
    got = np.bincount(np.asarray(out["Index"][0]).reshape(-1), minlength=8)
    assert got.max() > 16 > got.min()            # 64 x 2 over 8: even is 16
    close(out["BiasOut"][0], np.asarray(bias) + np.float32(0.01) * np.sign(
        16 - got).astype(np.float32), 0)
    # run to a standstill on one batch, the rule evens the load out
    for _ in range(400):
        out = lower("moe_route_topk", dict(ins, Bias=out["BiasOut"]),
                    {"k": 2, "bias_update_rate": 0.01}, LowerContext())
    got = np.bincount(np.asarray(out["Index"][0]).reshape(-1), minlength=8)
    assert got.max() - got.min() <= 6, got


def test_amp_gives_the_experts_bfloat16_operands_and_leaves_the_router(
        fresh_programs):
    build(True)
    block = fluid.default_main_program().global_block()
    experts = [o for o in block.ops if o.type == "held_experts_ffn"]
    routers = [o for o in block.ops if o.type == "moe_route_topk"]
    assert len(experts) == len(routers) == 2
    for op in experts:
        for slot in ("X", "W1", "W2", "W3"):
            assert block.vars[op.input(slot)[0]].dtype == "bfloat16", slot
        assert block.vars[op.input("Weight")[0]].dtype == "float32"
    for op in routers:
        for slot in ("X", "Gate", "Bias"):
            assert block.vars[op.input(slot)[0]].dtype == "float32", slot


def test_blocks_carry_their_name_scope_into_the_lowered_program(
        fresh_programs):
    cfg = lfm2.Lfm2Config.from_hf(M, router_experts=8, first_expert=2)
    vs = lfm2.build_lfm2_pretrain(cfg, 32)
    from paddle_tpu.fluid.lowering import build_step_fn

    prog = fluid.default_main_program()
    scopes = {o.attrs.get("op_namescope") for o in prog.global_block().ops}
    assert scopes == {None, "/lfm2.conv/", "/lfm2.attn/", "/lfm2.mlp/",
                      "/lfm2.moe.route/", "/lfm2.moe.experts/",
                      "/lfm2.head/"}
    exe = fluid.Executor()
    exe.run(fluid.default_startup_program())
    state = {n: fluid.global_scope().find_value(n)
             for n in lfm2.param_shapes(cfg)}
    step = build_step_fn(prog, ["input_ids", "labels"], [vs["loss"].name],
                         platform="cpu")
    ids = np.ones((2, 32), np.int32)
    text = jax.jit(step).lower(
        state, {"input_ids": ids, "labels": ids},
        jax.random.PRNGKey(0)).as_text(debug_info=True)
    for name in ("lfm2.conv/mul", "lfm2.attn/gqa_attention",
                 "lfm2.moe.route/moe_route_topk",
                 "lfm2.moe.experts/held_experts_ffn",
                 "lfm2.head/linear_softmax_with_cross_entropy"):
        assert name in text, name
    # an op outside any scope is named as before
    assert re.search(r'"[^"]*jit\(step\)/rms_norm/', text)


@pytest.mark.parametrize("columns", [3, 4])
def test_step_counters_carry_the_engines_names_to_the_hub(columns):
    """Three columns are what the relu² layer counts (and a program from
    before the gated loops); the gated layer's fourth is the sorted rows
    its loops covered."""
    from paddle_tpu import observability as obs

    obs.reset()
    moe = np.array([[[10, 4, 3, 16], [12, 5, 4, 16]],
                    [[11, 6, 4, 16], [9, 3, 3, 8]]])[:, :, :columns]
    out = lfm2.step_counters(moe, np.array([124, 124]), np.array([1, 1]),
                             steps=2)
    want = {"steps": 2, "moe_assignments_held": 42,
            "moe_expert_load_max_sum": 18, "moe_experts_touched_sum": 14,
            "head_rows": 248, "head_chunks": 2}
    if columns == 4:
        want["moe_rows_covered"] = 56
    assert out == want
    assert obs.counter("lfm2.moe_assignments_held") == 42
    assert obs.counter("lfm2.steps") == 2
    assert obs.counter("lfm2.moe_rows_covered") == (56 if columns == 4 else 0)
