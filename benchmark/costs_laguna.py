"""Operations and bytes the Laguna decoder's programs need, from shapes
alone: weights read once (of the held experts those that were touched),
every live K/V row read once: a full layer's rows grow with the sequence, a
window layer's ring stops at `sliding_window`. `m` holds the configuration
file's published keys, its lists among them (`num_experts` the experts held
here) plus `router_experts` (`sizes`). Nothing here reads the program."""
W = 2          # bytes of a bfloat16 weight or K/V element
LISTS = ("layer_types", "mlp_layer_types", "gating_types",
         "num_attention_heads_per_layer", "rope_parameters")


def sizes(config):
    """`m` of a configuration file, as the reference, `LagunaConfig.from_hf`
    and the cost functions take it: its published keys, the lists and the
    rotary group among them, plus the router's width and where the held
    range starts."""
    m = dict(config["model"], **{k: config[k] for k in LISTS})
    return dict(m, router_experts=config["reduced_from"]["num_experts"],
                first_expert=config["share"]["first_expert"])


def _kv_width(m):
    return m["num_key_value_heads"] * m["head_dim"]


def attention_params(m, heads):
    """Matrices of one attention block with `heads` query heads: q, k, v,
    the gate, o, and the layer's two norms."""
    h, qw = m["hidden_size"], heads * m["head_dim"]
    return h * qw + 2 * h * _kv_width(m) + h * heads + qw * h + 2 * h


def ffn_params(m):
    """{"dense", "sparse_outside", "expert"}: a dense layer's MLP; a sparse
    layer's router and shared expert; one routed expert's three matrices."""
    h = m["hidden_size"]
    return {"dense": 3 * h * m["intermediate_size"],
            "sparse_outside": (h * m["router_experts"]
                               + 3 * h * m["shared_expert_intermediate_size"]),
            "expert": 3 * h * m["moe_intermediate_size"]}


def _layers(m):
    return list(zip(m["layer_types"], m["mlp_layer_types"],
                    m["num_attention_heads_per_layer"]))


def sparse_layers(m):
    return m["mlp_layer_types"].count("sparse")


def held_params(m):
    """Every parameter of the cut."""
    h, f = m["hidden_size"], ffn_params(m)
    total = 2 * m["vocab_size"] * h + h
    for _, mlp, heads in _layers(m):
        total += attention_params(m, heads)
        total += f["dense"] if mlp == "dense" else (
            f["sparse_outside"] + m["num_experts"] * f["expert"])
    return total


def experts_touched(m, tokens):
    """Expected number of one layer's held experts that at least one of
    `tokens` tokens chose, under even routing."""
    p = m["num_experts_per_tok"] / float(m["router_experts"])
    return m["num_experts"] * (1.0 - (1.0 - p) ** tokens)


def weight_bytes(m, tokens, touched=None):
    """Weight bytes one pass over `tokens` tokens has to read: everything
    outside the routed experts, the held experts that are touched (`touched`
    a layer where the program counted them, else the expectation under even
    routing), the final norm and the head; of the embedding one row a
    token."""
    h, f = m["hidden_size"], ffn_params(m)
    if touched is None:
        touched = experts_touched(m, tokens)
    params = h + h * m["vocab_size"] + tokens * h
    for _, mlp, heads in _layers(m):
        params += attention_params(m, heads)
        params += f["dense"] if mlp == "dense" else (
            f["sparse_outside"] + touched * f["expert"])
    return params * W


def kv_row_bytes(m):
    """One position's K and V in one layer."""
    return 2 * _kv_width(m) * W


def state_bytes(m, slots, cache_len):
    """{"rows", "ring"}: the slot state of each kind."""
    full = m["layer_types"].count("full_attention")
    ring = len(m["layer_types"]) - full
    return {"rows": slots * full * cache_len * kv_row_bytes(m),
            "ring": slots * ring * m["sliding_window"] * kv_row_bytes(m)}


def live_kv_rows(m, live_slots, live_rows):
    """K/V rows (layer x position) that hold a position when `live_slots`
    sequences' positions sum to `live_rows`: all of them in a full layer,
    at most `sliding_window` a sequence in a window layer."""
    full = m["layer_types"].count("full_attention")
    ring = len(m["layer_types"]) - full
    context = live_rows / max(live_slots, 1e-9)
    return (full * live_rows
            + ring * live_slots * min(context, m["sliding_window"]))


def flops_per_token(m, context, causal_share=1.0):
    """2 FLOPs per multiply-add of one token's pass with `context`
    positions behind it: the matrices it meets (its own k experts of each
    sparse layer, of which the share held here is num_experts /
    router_experts), attention over `context` positions in a full layer
    (`causal_share` of them: 0.5 for the mean token of a prompt) and over at
    most `sliding_window` in a window layer, the head."""
    h, f, dh = m["hidden_size"], ffn_params(m), m["head_dim"]
    held_share = m["num_experts"] / float(m["router_experts"])
    macs = h * m["vocab_size"]
    for kind, mlp, heads in _layers(m):
        seen = (context * causal_share if kind == "full_attention"
                else min(context * causal_share, m["sliding_window"]))
        macs += attention_params(m, heads) + 2 * heads * dh * seen
        macs += f["dense"] if mlp == "dense" else (
            f["sparse_outside"]
            + m["num_experts_per_tok"] * held_share * f["expert"])
    return 2 * macs


def step_bytes(m, live_slots, live_rows, touched=None):
    return (weight_bytes(m, live_slots, touched)
            + live_kv_rows(m, live_slots, live_rows) * kv_row_bytes(m))


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
    """FLOPs of one batch-1 prefill of `prompt_len` real tokens: the full
    layers at the causal half, the window layers at the window's cost (the
    mean token of a long prompt sees nearly all of its window), the head
    for the last token alone."""
    head = 2 * m["hidden_size"] * m["vocab_size"]
    w = m["sliding_window"]
    seen = (prompt_len / 2.0 if prompt_len <= w
            else w - w * (w - 1.0) / (2.0 * prompt_len))
    dh = m["head_dim"]
    flops = head
    for kind, _, heads in _layers(m):
        mean_seen = prompt_len / 2.0 if kind == "full_attention" else seen
        flops += prompt_len * 2 * 2 * heads * dh * mean_seen
    return flops + prompt_len * (flops_per_token(m, 0) - head)


def prefill_min_seconds(m, prompt_len, peaks):
    """Least time of one batch-1 prefill of `prompt_len` real tokens: the
    larger of its FLOPs at the bf16 peak and its bytes (weights once, the
    state written once)."""
    nbytes = (weight_bytes(m, prompt_len)
              + live_kv_rows(m, 1, prompt_len) * kv_row_bytes(m))
    return max(prefill_flops(m, prompt_len) / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def grouped_products_min_seconds(m, tokens, peaks, touched=None):
    """Least time of ONE sparse layer's three grouped products (the kernel
    `gmm`, called three times) over `tokens` tokens: the touched experts'
    three matrices once, the rows that land here in and out, against the
    FLOPs of those rows."""
    if touched is None:
        touched = experts_touched(m, tokens)
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    rows = (tokens * m["num_experts_per_tok"] * m["num_experts"]
            / float(m["router_experts"]))
    nbytes = (touched * 3 * h * f * W
              + 2 * rows * (h + f) * W + rows * (f + h) * W)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               3 * rows * 2 * h * f / peaks["bf16_flops_per_s"])
