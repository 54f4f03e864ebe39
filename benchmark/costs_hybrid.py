"""Operations and bytes the hybrid decoder's programs need, from shapes
alone: weights read once, every live slot's `fixed` state (convolution
window, state-space state) read and written once, every live `rows` row
(K and V of the attention block) read once. `m` holds the configuration
file's published keys (`n_routed_experts` the experts held here) plus
`router_experts`. Nothing here reads the program."""
W = 2          # bytes of a bfloat16 weight, K/V element or window element
STATE = 4      # bytes of a float32 state-space state element


def _sizes(m):
    h = m["hidden_size"]
    d_inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    conv_dim = d_inner + 2 * m["n_groups"] * m["ssm_state_size"]
    pattern = m["hybrid_override_pattern"]
    return h, d_inner, conv_dim, pattern


def block_params(m):
    """{"M", "*", "E_outside", "expert"}: matrix parameters of one block of
    each kind, the routed experts apart (one expert's two matrices)."""
    h, d_inner, conv_dim, _ = _sizes(m)
    qw = m["num_attention_heads"] * m["head_dim"]
    kvw = m["num_key_value_heads"] * m["head_dim"]
    lat = m["moe_latent_size"]
    return {
        "M": (h * (d_inner + conv_dim + m["mamba_num_heads"]) + d_inner * h
              + conv_dim * (m["conv_kernel"] + 1) + d_inner + h),
        "*": h * qw + 2 * h * kvw + qw * h + h,
        "E_outside": (h * m["router_experts"] + 2 * h * lat
                      + 2 * h * m["moe_shared_expert_intermediate_size"]
                      + h),
        "expert": 2 * lat * m["moe_intermediate_size"]}


def experts_touched(m, tokens):
    """Expected number of one layer's held experts that at least one of
    `tokens` tokens chose, under even routing."""
    p = m["num_experts_per_tok"] / float(m["router_experts"])
    return m["n_routed_experts"] * (1.0 - (1.0 - p) ** tokens)


def weight_bytes(m, tokens, touched=None):
    """Weight bytes one pass over `tokens` tokens has to read: every block
    outside the routed experts, the held experts that are touched (`touched`
    a layer where the program counted them: routing is not even, and an
    expert no token chose is not read; else the expectation under even
    routing), the final norm and the head; of the embedding one row per
    token."""
    h, _, _, pattern = _sizes(m)
    p = block_params(m)
    n = {k: pattern.count(k) for k in "M*E"}
    if touched is None:
        touched = experts_touched(m, tokens)
    params = (n["M"] * p["M"] + n["*"] * p["*"]
              + n["E"] * (p["E_outside"] + touched * p["expert"])
              + h + h * m["vocab_size"] + tokens * h)
    return params * W


def grouped_products_min_seconds(m, tokens, peaks, touched=None):
    """Least time of ONE expert layer's two grouped products (the kernel
    `gmm`, called twice) over `tokens` tokens: the touched experts' two
    matrices once, the rows that land here in and out, against the FLOPs of
    those rows."""
    if touched is None:
        touched = experts_touched(m, tokens)
    lat, f = m["moe_latent_size"], m["moe_intermediate_size"]
    rows = (tokens * m["num_experts_per_tok"] * m["n_routed_experts"]
            / float(m["router_experts"]))
    nbytes = (touched * 2 * lat * f * W
              + rows * (lat * W + f * 4) + rows * (f * W + lat * 4))
    return max(nbytes / peaks["hbm_bytes_per_s"],
               2 * rows * 2 * lat * f / peaks["bf16_flops_per_s"])


def fixed_state_bytes(m):
    """One slot's convolution windows and state-space states."""
    _, d_inner, conv_dim, pattern = _sizes(m)
    per_layer = ((m["conv_kernel"] - 1) * conv_dim * W
                 + d_inner * m["ssm_state_size"] * STATE)
    return pattern.count("M") * per_layer


def row_bytes(m):
    """One position's K and V over the attention blocks."""
    _, _, _, pattern = _sizes(m)
    return (pattern.count("*") * 2 * m["num_key_value_heads"]
            * m["head_dim"] * W)


def flops_per_token(m, context):
    """2 FLOPs per multiply-add of one token's pass: the matrices it meets
    (its own k experts of each layer, of which the share held here is
    n_routed_experts / router_experts), the state-space update and
    read-out, attention over `context` positions, the head."""
    h, d_inner, _, pattern = _sizes(m)
    p = block_params(m)
    held_share = m["n_routed_experts"] / float(m["router_experts"])
    ssm = 3 * d_inner * m["ssm_state_size"]
    attn = 2 * m["num_attention_heads"] * m["head_dim"] * context
    macs = (pattern.count("M") * (p["M"] + ssm)
            + pattern.count("*") * (p["*"] + attn)
            + pattern.count("E") * (
                p["E_outside"]
                + m["num_experts_per_tok"] * held_share * p["expert"])
            + h * m["vocab_size"])
    return 2 * macs


def step_bytes(m, live_slots, live_rows, touched=None):
    return (weight_bytes(m, live_slots, touched)
            + 2 * live_slots * fixed_state_bytes(m)
            + live_rows * row_bytes(m))


def step_min_seconds(m, live_slots, live_rows, peaks, touched=None):
    """Least time of one decode step with `live_slots` sequences whose
    positions sum to `live_rows`: the larger of its bytes at the memory
    bandwidth and its FLOPs at the bf16 peak (the bytes, by far)."""
    context = live_rows / max(live_slots, 1)
    return max(step_bytes(m, live_slots, live_rows, touched)
               / peaks["hbm_bytes_per_s"],
               live_slots * flops_per_token(m, context)
               / peaks["bf16_flops_per_s"])


def prefill_min_seconds(m, prompt_len, peaks):
    """Least time of one batch-1 prefill of `prompt_len` real tokens: the
    larger of its FLOPs at the bf16 peak and its bytes (weights once, the
    state written once)."""
    flops = prompt_len * flops_per_token(m, prompt_len / 2.0)
    nbytes = (weight_bytes(m, prompt_len) + fixed_state_bytes(m)
              + prompt_len * row_bytes(m))
    return max(flops / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])
