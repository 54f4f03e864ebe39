"""Operations and bytes the Kimi-VL programs need, from shapes alone,
whatever implements them: a decode step's least bytes (weights once, of the
routed experts those that were touched; every LIVE latent row once), a
chunk's and a tower unit's USEFUL operations (attention at a head's own
widths, 192 / 192 / 128 and 72: what a kernel pads is not counted, so
padding reads as a lower share). `m` holds the configuration file's
published keys plus `vision_config`, `media_placeholder_token_id` and the
depth of the cut (`sizes`). Nothing here reads the program."""
W = 2          # bytes of a bfloat16 weight or cache element


def sizes(config):
    """`m` of a configuration file, as the reference, `KimiVlConfig.from_hf`
    and the cost functions take it: its published keys, the vision group,
    the depth of the cut (the length of the file's `mlp_layer_types`;
    `num_hidden_layers` stays the published 27 there)."""
    return dict(config["model"], vision_config=config["vision_config"],
                num_hidden_layers=len(config["mlp_layer_types"]))


def latent_width(m):
    return m["kv_lora_rank"] + m["qk_rope_head_dim"]


def attention_params(m):
    """One latent-attention block: the query's matrix, the latent
    projection, the two expansions, the output projection, the latent's
    norm."""
    h, heads, rank = (m["hidden_size"], m["num_attention_heads"],
                      m["kv_lora_rank"])
    return (h * heads * (m["qk_nope_head_dim"] + m["qk_rope_head_dim"])
            + h * latent_width(m)
            + rank * heads * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * h + rank)


def ffn_params(m):
    """{"dense", "sparse_outside", "expert"}: a dense layer's MLP; a sparse
    layer's router (with its score correction) and shared expert; one routed
    expert's three matrices."""
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    return {"dense": 3 * h * m["intermediate_size"],
            "sparse_outside": ((h + 1) * m["n_routed_experts"]
                               + 3 * h * f * m["n_shared_experts"]),
            "expert": 3 * h * f}


def sparse_layers(m):
    return m["num_hidden_layers"] - m["first_k_dense_replace"]


def tower_params(m):
    """{"block", "tower", "projector"}: one tower block's matrices and
    biases and norms; the whole tower (blocks, patch embedding, table, final
    norm); the projector."""
    v = m["vision_config"]
    d, f = v["hidden_size"], v["intermediate_size"]
    block = 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d
    merged = d * v["merge_kernel_size"][0] * v["merge_kernel_size"][1]
    patch = 3 * v["patch_size"] ** 2 * d + d
    table = v["init_pos_emb_height"] * v["init_pos_emb_width"] * d
    return {"block": block,
            "tower": v["num_hidden_layers"] * block + patch + table + 2 * d,
            "projector": (2 * d + merged * merged + merged
                          + merged * m["hidden_size"] + m["hidden_size"])}


def decoder_params(m):
    h, f = m["hidden_size"], ffn_params(m)
    return (2 * m["vocab_size"] * h + h
            + m["num_hidden_layers"] * (attention_params(m) + 2 * h)
            + m["first_k_dense_replace"] * f["dense"]
            + sparse_layers(m) * (f["sparse_outside"]
                                  + m["n_routed_experts"] * f["expert"]))


def held_params(m):
    """Every parameter of the cut: the decoder's five layers, embedding and
    head, and the tower with its projector."""
    t = tower_params(m)
    return decoder_params(m) + t["tower"] + t["projector"]


def experts_touched(m, tokens):
    """Expected number of one layer's experts that at least one of `tokens`
    tokens chose, under even routing."""
    p = m["num_experts_per_tok"] / float(m["n_routed_experts"])
    return m["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def weight_bytes(m, tokens, touched=None):
    """Weight bytes one decoder pass over `tokens` tokens has to read:
    everything outside the routed experts, the experts that are touched
    (`touched` a layer where the program counted them), the final norm and
    the head; of the embedding one row a token."""
    h, f = m["hidden_size"], ffn_params(m)
    if touched is None:
        touched = experts_touched(m, tokens)
    params = (h + h * m["vocab_size"] + tokens * h
              + m["num_hidden_layers"] * (attention_params(m) + 2 * h)
              + m["first_k_dense_replace"] * f["dense"]
              + sparse_layers(m) * (f["sparse_outside"]
                                    + touched * f["expert"]))
    return params * W


def state_bytes(m, slots, cache_len, row_width=None):
    """The slots' latent rows as the cache lays them out (`row_width`: a
    row's values and its padding; default: the values alone)."""
    return (slots * m["num_hidden_layers"] * cache_len
            * (row_width or latent_width(m)) * W)


def step_bytes(m, live_slots, live_rows, touched=None):
    """The least a step reads: the weights and every live position's latent
    row once a layer."""
    return (weight_bytes(m, live_slots, touched)
            + m["num_hidden_layers"] * live_rows * latent_width(m) * W)


def attention_flops_per_pair(m):
    """2 FLOPs a multiply-add of one (query, key) pair over every head: the
    query-key width and the value width."""
    return 2 * m["num_attention_heads"] * (
        m["qk_nope_head_dim"] + m["qk_rope_head_dim"] + m["v_head_dim"])


def token_flops(m, head=True):
    """2 FLOPs a multiply-add of the matrices one token meets (its own k
    experts of each sparse layer), without attention's pairs."""
    h, f = m["hidden_size"], ffn_params(m)
    macs = (m["num_hidden_layers"] * attention_params(m)
            + m["first_k_dense_replace"] * f["dense"]
            + sparse_layers(m) * (f["sparse_outside"]
                                  + m["num_experts_per_tok"] * f["expert"]))
    return 2 * (macs + (h * m["vocab_size"] if head else 0))


def step_min_seconds(m, live_slots, live_rows, peaks, touched=None):
    """Least time of one decode step with `live_slots` sequences whose
    positions sum to `live_rows`: the larger of its bytes at the memory
    bandwidth and its FLOPs (absorbed: a pair costs the latent's width
    twice a head) at the bf16 peak."""
    absorbed = 2 * m["num_attention_heads"] * (
        latent_width(m) + m["kv_lora_rank"])
    flops = (live_slots * token_flops(m)
             + m["num_hidden_layers"] * live_rows * absorbed)
    return max(step_bytes(m, live_slots, live_rows, touched)
               / peaks["hbm_bytes_per_s"], flops / peaks["bf16_flops_per_s"])


def chunk_flops(m, rows, start):
    """USEFUL FLOPs of one chunk of `rows` real positions that starts at row
    `start`: every token's matrices, the expansion of the rows so far to
    keys and values, the causal pairs (each query with the `start` rows
    before the chunk and its own predecessors), the head for one token."""
    pairs = rows * start + rows * (rows + 1) / 2.0
    expand = 2 * (start + rows) * m["kv_lora_rank"] * m[
        "num_attention_heads"] * (m["qk_nope_head_dim"] + m["v_head_dim"])
    return (rows * token_flops(m, head=False)
            + 2 * m["hidden_size"] * m["vocab_size"]
            + m["num_hidden_layers"] * (pairs * attention_flops_per_pair(m)
                                        + expand))


def chunk_min_seconds(m, rows, start, peaks):
    """Least time of one chunk: the larger of its useful FLOPs at the bf16
    peak and its bytes (weights once, every expert; the rows so far read,
    the chunk's written)."""
    nbytes = (weight_bytes(m, rows, m["n_routed_experts"])
              + m["num_hidden_layers"] * (start + 2 * rows)
              * latent_width(m) * W)
    return max(chunk_flops(m, rows, start) / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def tower_flops(m, patches):
    """USEFUL FLOPs of the tower and the projector over one image of
    `patches` real patches: every patch's matrices, attention of every patch
    over every patch at a head's own width, the projector a merged row."""
    v, t = m["vision_config"], tower_params(m)
    d = v["hidden_size"]
    per_patch = (v["num_hidden_layers"] * (4 * d * d
                                           + 2 * d * v["intermediate_size"])
                 + 3 * v["patch_size"] ** 2 * d)
    merged = v["merge_kernel_size"][0] * v["merge_kernel_size"][1]
    attention = v["num_hidden_layers"] * 2 * patches * patches * d
    return 2 * (patches * per_patch + attention
                + patches / merged * (t["projector"] - 2 * d))


def tower_min_seconds(m, patches, peaks):
    """Least time of one tower unit: the larger of its useful FLOPs at the
    bf16 peak and its weights' bytes."""
    t = tower_params(m)
    return max(tower_flops(m, patches) / peaks["bf16_flops_per_s"],
               (t["tower"] + t["projector"]) * W / peaks["hbm_bytes_per_s"])


def grouped_products_min_seconds(m, tokens, peaks, touched=None):
    """Least time of ONE sparse layer's three grouped products (the kernel
    `gmm`, called three times) over `tokens` tokens: the touched experts'
    three matrices once, the rows in and out, against the FLOPs of those
    rows."""
    if touched is None:
        touched = experts_touched(m, tokens)
    h, f = m["hidden_size"], m["moe_intermediate_size"]
    rows = tokens * m["num_experts_per_tok"]
    nbytes = (touched * 3 * h * f * W
              + 2 * rows * (h + f) * W + rows * (f + h) * W)
    return max(nbytes / peaks["hbm_bytes_per_s"],
               3 * rows * 2 * h * f / peaks["bf16_flops_per_s"])
