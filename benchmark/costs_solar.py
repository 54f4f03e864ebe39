"""Operations and bytes the Solar-Open2 decoder's programs need, from shapes
alone, whatever implements them: weights read once (of the held experts those
that were touched), each live slot's delta-rule state and windows read once
and written once, the K/V rows of every live position read once. The delta
rule is counted as its recurrence (three products of a head's 128 x 128 state
a position: the least work; a chunked form does more and reads a lower
share). `m` holds the configuration file's published keys
(`n_routed_experts` the experts held here) plus `linear_attn_config`,
`gqa_layers`, `router_experts` and `first_expert` (`sizes`). Nothing here
reads the program."""
from benchmark.costs_glm5 import (  # noqa: F401 — the same counts, same keys
    experts_touched, grouped_products_min_seconds)

W = 2          # bytes of a bfloat16 weight, window or K/V element
STATE = 4      # bytes of an element of the delta rule's float32 state


def sizes(config):
    """`m` of a configuration file, as the reference, `SolarOpen2Config.
    from_hf` and the cost functions take it: its published keys, the linear
    layers' group and the softmax layers of the cut (`gqa_layers`; the depth
    read is each of them and the `gqa_interval` delta-rule layers after it,
    `num_hidden_layers` stays the published 48 there), plus the router's
    width and where the held range starts."""
    return dict(config["model"],
                linear_attn_config=config["linear_attn_config"],
                gqa_layers=config["gqa_layers"],
                router_experts=config["reduced_from"]["n_routed_experts"],
                first_expert=config["share"]["first_expert"])


def layers(m):
    """(softmax layers, delta-rule layers) of the cut."""
    n = len(m["gqa_layers"])
    return n, n * m["gqa_interval"]


def kda_dims(m):
    """(heads, head size, width, rank of the low-rank pairs, taps)."""
    lin = m["linear_attn_config"]
    heads, d = lin["num_heads"], lin["head_dim"]
    return (heads, d, heads * d, m.get("kda_rank", d),
            lin["short_conv_kernel_size"])


def gqa_params(m):
    """One softmax layer's mixer: q, k, v, the elementwise gate, o; and the
    layer's two norms."""
    h, dh = m["hidden_size"], m["head_dim"]
    qw, kvw = m["num_attention_heads"] * dh, m["num_key_value_heads"] * dh
    return h * (3 * qw + 2 * kvw) + 2 * h


def kda_params(m):
    """One delta-rule layer's mixer: q, k, v, o; the decay's and the gate's
    low-rank pairs; beta; the three convolutions; A_log, dt_bias, the
    per-head norm's gain; and the layer's two norms."""
    h = m["hidden_size"]
    heads, d, width, rank, taps = kda_dims(m)
    return (4 * h * width + 2 * (h * rank + rank * width) + h * heads
            + 3 * width * taps + heads + width + d + 2 * h)


def ffn_params(m):
    """{"outside", "expert"}: a layer's router (with its score correction)
    and shared expert; one routed expert's three matrices."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return {"outside": ((h + 1) * m["router_experts"]
                        + 3 * h * f * m["n_shared_experts"]),
            "expert": 3 * h * f}


def mixer_params(m):
    n_gqa, n_kda = layers(m)
    return n_gqa * gqa_params(m) + n_kda * kda_params(m)


def held_params(m):
    """Every parameter of the cut."""
    h, f = m["hidden_size"], ffn_params(m)
    return (2 * m["vocab_size"] * h + h + mixer_params(m)
            + sum(layers(m)) * (f["outside"]
                                + m["n_routed_experts"] * f["expert"]))


def weight_bytes(m, tokens, touched=None):
    """Weight bytes one pass over `tokens` tokens has to read: everything
    outside the routed experts, the held experts that are touched (`touched`
    a layer where the program counted them, else the expectation under even
    routing), the final norm and the head; of the embedding one row a
    token."""
    h, f = m["hidden_size"], ffn_params(m)
    if touched is None:
        touched = experts_touched(m, tokens)
    params = (h + h * m["vocab_size"] + tokens * h + mixer_params(m)
              + sum(layers(m)) * (f["outside"] + touched * f["expert"]))
    return params * W


def slot_bytes(m):
    """{"state", "windows"}: one slot's delta-rule state and its three
    convolution windows, in ONE delta-rule layer."""
    heads, d, width, _, taps = kda_dims(m)
    return {"state": heads * d * d * STATE,
            "windows": 3 * (taps - 1) * width * W}


def kv_row_bytes(m):
    """One position's K and V in one softmax layer."""
    return 2 * m["num_key_value_heads"] * m["head_dim"] * W


def state_bytes(m, slots, cache_len):
    """{"rows", "fixed"}: the slot state of each kind."""
    n_gqa, n_kda = layers(m)
    return {"rows": slots * n_gqa * cache_len * kv_row_bytes(m),
            "fixed": slots * n_kda * sum(slot_bytes(m).values())}


def step_state_bytes(m, live_slots, live_rows):
    """The least a step moves of the slots' state when `live_slots`
    sequences' positions sum to `live_rows`: each live slot's delta-rule
    state and windows once in and once out, every live position's K and V
    once."""
    n_gqa, n_kda = layers(m)
    return (live_slots * n_kda * 2 * sum(slot_bytes(m).values())
            + live_rows * n_gqa * kv_row_bytes(m))


def flops_per_token(m, context):
    """2 FLOPs per multiply-add of one token's pass with `context`
    positions behind it: the matrices it meets (its own k experts of each
    layer, of which the share held here is n_routed_experts /
    router_experts), softmax attention over the context in each softmax
    layer (scores and values), the recurrence's three products of a head's
    state in each delta-rule layer (S'^T k, the update, S^T q), the head."""
    h, f = m["hidden_size"], ffn_params(m)
    n_gqa, n_kda = layers(m)
    heads, d, _, _, _ = kda_dims(m)
    held_share = m["n_routed_experts"] / float(m["router_experts"])
    macs = (h * m["vocab_size"] + mixer_params(m)
            + n_gqa * context * m["num_attention_heads"] * 2 * m["head_dim"]
            + n_kda * 3 * heads * d * d
            + sum(layers(m)) * (f["outside"] + m["num_experts_per_tok"]
                                * held_share * f["expert"]))
    return 2 * macs


def step_bytes(m, live_slots, live_rows, touched=None):
    return (weight_bytes(m, live_slots, touched)
            + step_state_bytes(m, live_slots, live_rows))


def step_min_seconds(m, live_slots, live_rows, peaks, touched=None):
    """Least time of one decode step with `live_slots` sequences whose
    positions sum to `live_rows`: the larger of its bytes at the memory
    bandwidth and its FLOPs at the bf16 peak (the bytes, by far)."""
    context = live_rows / max(live_slots, 1)
    return max(step_bytes(m, live_slots, live_rows, touched)
               / peaks["hbm_bytes_per_s"],
               live_slots * flops_per_token(m, context)
               / peaks["bf16_flops_per_s"])


def prefill_flops(m, prompt_len):
    """FLOPs of one batch-1 prefill of `prompt_len` real tokens: every
    token's matrices and recurrence, softmax attention over the causal
    half, the head for the last token alone."""
    head = 2 * m["hidden_size"] * m["vocab_size"]
    n_gqa, _ = layers(m)
    pairs = prompt_len * (prompt_len + 1) / 2.0
    return (head + 2 * n_gqa * pairs * m["num_attention_heads"] * 2
            * m["head_dim"] + prompt_len * (flops_per_token(m, 0) - head))


def prefill_min_seconds(m, prompt_len, peaks):
    """Least time of one batch-1 prefill of `prompt_len` real tokens: the
    larger of its FLOPs at the bf16 peak and its bytes (weights once, the
    state written once)."""
    n_gqa, n_kda = layers(m)
    nbytes = (weight_bytes(m, prompt_len)
              + n_gqa * prompt_len * kv_row_bytes(m)
              + n_kda * sum(slot_bytes(m).values()))
    return max(prefill_flops(m, prompt_len) / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
