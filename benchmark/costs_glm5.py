"""Operations and bytes the GLM-5 decoder's programs need, from shapes alone,
whatever implements them: weights read once (of the held experts those that
were touched), the indexer's row of every live position read once, and of
the latent rows only those a query keeps: `index_topk` a sequence and layer,
all of them while the sequence is shorter. A prompt's attention is counted
over the kept keys too (the least work: a program that masks a causal square
does more and reads a lower share). `m` holds the configuration file's
published keys (`n_routed_experts` the experts held here) plus
`rope_parameters`, `router_experts` and `first_expert` (`sizes`). Nothing
here reads the program."""
W = 2          # bytes of a bfloat16 weight or cache element


def sizes(config):
    """`m` of a configuration file, as the reference, `GlmMoeDsaConfig.
    from_hf` and the cost functions take it: its published keys and the
    rotary group, the depth of the cut (the length of the file's
    `mlp_layer_types`; `num_hidden_layers` stays the published 78 there),
    plus the router's width and where the held range starts."""
    return dict(config["model"], rope_parameters=config["rope_parameters"],
                num_hidden_layers=len(config["mlp_layer_types"]),
                router_experts=config["reduced_from"]["n_routed_experts"],
                first_expert=config["share"]["first_expert"])


def _q_width(m):
    return m["num_attention_heads"] * (m["qk_nope_head_dim"]
                                       + m["qk_rope_head_dim"])


def latent_width(m):
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m):
    """Matrices of one latent-attention block: the two query projections,
    the latent projection, the two expansions, the output projection; and
    its norms (the layer's two, the query's and the latent's)."""
    h, heads, rank = (m["hidden_size"], m["num_attention_heads"],
                      m["kv_lora_rank"])
    return (h * m["q_lora_rank"] + m["q_lora_rank"] * _q_width(m)
            + h * latent_width(m)
            + rank * heads * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * h
            + 2 * h + m["q_lora_rank"] + rank)


def indexer_params(m):
    """The indexer of one layer: its queries' and keys' projections, the
    heads' weights, the key norm's gain and bias."""
    width = m["index_n_heads"] * m["index_head_dim"]
    return (m["q_lora_rank"] * width + m["hidden_size"]
            * (m["index_head_dim"] + m["index_n_heads"])
            + 2 * m["index_head_dim"])


def ffn_params(m):
    """{"dense", "sparse_outside", "expert"}: a dense layer's MLP; a sparse
    layer's router (with its score correction) and shared expert; one routed
    expert's three matrices."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return {"dense": 3 * h * m["intermediate_size"],
            "sparse_outside": ((h + 1) * m["router_experts"]
                               + 3 * h * f * m["n_shared_experts"]),
            "expert": 3 * h * f}


def sparse_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def held_params(m):
    """Every parameter of the cut."""
    h, f = m["hidden_size"], ffn_params(m)
    dense = m["first_k_dense_replace"]
    return (2 * m["vocab_size"] * h + h
            + m["num_hidden_layers"] * (attention_params(m)
                                        + indexer_params(m))
            + dense * f["dense"]
            + sparse_layers(m) * (f["sparse_outside"]
                                  + m["n_routed_experts"] * f["expert"]))


def experts_touched(m, tokens):
    """Expected number of one layer's held experts that at least one of
    `tokens` tokens chose, under even routing."""
    p = m["num_experts_per_tok"] / float(m["router_experts"])
    return m["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def weight_bytes(m, tokens, touched=None):
    """Weight bytes one pass over `tokens` tokens has to read: everything
    outside the routed experts, the held experts that are touched (`touched`
    a layer where the program counted them, else the expectation under even
    routing), the final norm and the head; of the embedding one row a
    token."""
    h, f = m["hidden_size"], ffn_params(m)
    if touched is None:
        touched = experts_touched(m, tokens)
    params = (h + h * m["vocab_size"] + tokens * h
              + m["num_hidden_layers"] * (attention_params(m)
                                          + indexer_params(m))
              + m["first_k_dense_replace"] * f["dense"]
              + sparse_layers(m) * (f["sparse_outside"]
                                    + touched * f["expert"]))
    return params * W


def row_bytes(m):
    """{"latent", "indexer"}: one position's rows in one layer."""
    return {"latent": latent_width(m) * W, "indexer": m["index_head_dim"] * W}


def state_bytes(m, slots, cache_len):
    """{"latent", "indexer"}: the slot state of each entry."""
    return {k: slots * m["num_hidden_layers"] * cache_len * v
            for k, v in row_bytes(m).items()}


def kept_rows(m, context):
    """Positions a query with `context` positions behind and at it keeps."""
    return min(context, m["index_topk"])


def step_cache_bytes(m, live_slots, live_rows):
    """The least a step reads of the two caches when `live_slots` sequences'
    positions sum to `live_rows`: the indexer's row of every live position
    (each is scored) and the latent rows each sequence keeps."""
    rb = row_bytes(m)
    context = live_rows / max(live_slots, 1e-9)
    return m["num_hidden_layers"] * (
        live_rows * rb["indexer"]
        + live_slots * kept_rows(m, context) * rb["latent"])


def flops_per_token(m, context, kept=None):
    """2 FLOPs per multiply-add of one token's pass with `context` positions
    behind it: the matrices it meets (its own k experts of each sparse
    layer, of which the share held here is n_routed_experts /
    router_experts), the indexer's score of every one of the `context`
    positions, attention over the `kept` of them (default: what it keeps at
    that context) at a head's query-key and value widths, the head."""
    h, f = m["hidden_size"], ffn_params(m)
    heads = m["num_attention_heads"]
    if kept is None:
        kept = kept_rows(m, context)
    held_share = m["n_routed_experts"] / float(m["router_experts"])
    per_layer = (attention_params(m) + indexer_params(m)
                 + context * m["index_n_heads"] * m["index_head_dim"]
                 + kept * heads * (m["qk_nope_head_dim"]
                                   + m["qk_rope_head_dim"] + m["v_head_dim"]))
    macs = (h * m["vocab_size"] + m["num_hidden_layers"] * per_layer
            + m["first_k_dense_replace"] * f["dense"]
            + sparse_layers(m) * (f["sparse_outside"]
                                  + m["num_experts_per_tok"] * held_share
                                  * f["expert"]))
    return 2 * macs


def step_bytes(m, live_slots, live_rows, touched=None):
    return (weight_bytes(m, live_slots, touched)
            + step_cache_bytes(m, live_slots, live_rows))


def step_min_seconds(m, live_slots, live_rows, peaks, touched=None):
    """Least time of one decode step with `live_slots` sequences whose
    positions sum to `live_rows`: the larger of its bytes at the memory
    bandwidth and its FLOPs at the bf16 peak (the bytes, by far)."""
    context = live_rows / max(live_slots, 1)
    return max(step_bytes(m, live_slots, live_rows, touched)
               / peaks["hbm_bytes_per_s"],
               live_slots * flops_per_token(m, context)
               / peaks["bf16_flops_per_s"])


def kept_pairs(m, prompt_len):
    """(query, key) pairs of a prompt's attention in one layer: every token
    with all its predecessors and itself up to `index_topk`, that many
    after."""
    k = min(prompt_len, m["index_topk"])
    return k * (k + 1) / 2.0 + (prompt_len - k) * k


def kept_attention_min_seconds(m, prompt_len, peaks):
    """Least time of ONE layer's attention over a prompt of `prompt_len`
    real tokens, whatever computes it: the larger of the FLOPs of the kept
    pairs (a head's query-key and value widths, every head) at the bf16
    peak and its bytes (the heads' queries in, their outputs out, the
    latent rows once)."""
    heads = m["num_attention_heads"]
    widths = (m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
              + m["v_head_dim"])
    nbytes = prompt_len * (heads * widths + latent_width(m)) * W
    return max(2 * kept_pairs(m, prompt_len) * heads * widths
               / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def prefill_flops(m, prompt_len):
    """FLOPs of one batch-1 prefill of `prompt_len` real tokens: every
    token's matrices, the indexer's scores over the causal half, attention
    over what each token keeps (all its predecessors up to `index_topk`,
    that many after), the head for the last token alone."""
    head = 2 * m["hidden_size"] * m["vocab_size"]
    kept_total = kept_pairs(m, prompt_len)
    seen_total = prompt_len * (prompt_len + 1) / 2.0
    pair_flops = 2 * m["num_hidden_layers"] * (
        seen_total * m["index_n_heads"] * m["index_head_dim"]
        + kept_total * m["num_attention_heads"]
        * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"]))
    return (head + pair_flops
            + prompt_len * (flops_per_token(m, 0, kept=0) - head))


def prefill_min_seconds(m, prompt_len, peaks):
    """Least time of one batch-1 prefill of `prompt_len` real tokens: the
    larger of its FLOPs at the bf16 peak and its bytes (weights once, the
    state written once)."""
    rb = row_bytes(m)
    nbytes = (weight_bytes(m, prompt_len) + m["num_hidden_layers"]
              * prompt_len * (rb["latent"] + rb["indexer"]))
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
    rows = (tokens * m["num_experts_per_tok"] * m["n_routed_experts"]
            / float(m["router_experts"]))
    nbytes = (touched * 3 * h * f * W
              + 2 * rows * (h + f) * W + rows * (f + h) * W)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               3 * rows * 2 * h * f / peaks["bf16_flops_per_s"])
