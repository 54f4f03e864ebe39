"""Operations and bytes the LFM2-MoE training step needs, from shapes and
from the assignments the program counted: forward + backward = 3 x forward,
2 FLOPs a multiply-add, recomputation never counted. `m` holds the
configuration file's published keys (`num_experts` the experts held here)
plus `layer_types`, `router_experts` and `first_expert` (`sizes`). Nothing
here reads the program."""
W = 2          # bytes of a bfloat16 operand


def sizes(config):
    """`m` of a configuration file: its published keys plus the router's
    width and where the held range starts."""
    return dict(config["model"], layer_types=list(config["layer_types"]),
                router_experts=config["reduced_from"]["num_experts"],
                first_expert=config["share"]["first_expert"])


def _widths(m):
    h = m["hidden_size"]
    kvw = m["num_key_value_heads"] * (h // m["num_attention_heads"])
    return h, kvw, m["intermediate_size"], m["moe_intermediate_size"]


def block_params(m):
    """Matrix parameters of one mixer or feed-forward of each kind; the
    experts apart (`expert`: one expert's three matrices)."""
    h, kvw, f, fm = _widths(m)
    return {"conv": h * 3 * h + h * m["conv_L_cache"] + h * h,
            "full_attention": 2 * h * h + 2 * h * kvw,
            "mlp": 3 * h * f, "router": h * m["router_experts"],
            "expert": 3 * h * fm}


def held_params(m):
    """Every parameter of the cut: the tied embedding's held rows, the
    norms, mixers, the dense MLPs, routers and held experts."""
    h = m["hidden_size"]
    p, types = block_params(m), m["layer_types"]
    dense = m["num_dense_layers"]
    total = m["vocab_size"] * h + h
    for i, kind in enumerate(types):
        total += p[kind] + 2 * h
        if kind == "full_attention":
            total += 2 * (h // m["num_attention_heads"])
        total += p["mlp"] if i < dense else (
            p["router"] + m["router_experts"]
            + m["num_experts"] * p["expert"])
    return total


def expert_flops(m, assignments):
    """Forward + backward of `assignments` token-expert pairs through one
    expert's three matrices: nine products of 2 * H * F FLOPs a pair (three
    forward, three for the rows' gradient, three for the matrices')."""
    h, _, _, fm = _widths(m)
    return 3 * assignments * 2 * 3 * h * fm


def attention_flops(m, seq_len, tokens):
    """Forward + backward of the scores and the context of ONE attention
    layer at the causal half: a token meets seq_len / 2 positions."""
    return 3 * tokens * 2 * 2 * m["hidden_size"] * seq_len / 2.0


def step_flops(m, seq_len, tokens, assignments_held):
    """Model FLOPs of one training step over `tokens` tokens in sequences
    of `seq_len`: every matrix a token meets, the attention layers at the
    causal half, the head over the labelled positions (all but a row's
    last), and of the experts the assignments that were COUNTED on held
    experts, summed over the expert layers."""
    h = m["hidden_size"]
    p, types = block_params(m), m["layer_types"]
    dense = m["num_dense_layers"]
    macs = sum(p[kind] for kind in types)
    macs += dense * p["mlp"] + (len(types) - dense) * p["router"]
    macs += h * m["vocab_size"] * (seq_len - 1.0) / seq_len
    return (3 * tokens * 2 * macs + expert_flops(m, assignments_held)
            + types.count("full_attention")
            * attention_flops(m, seq_len, tokens))


def grouped_products_min_seconds(m, assignments, peaks):
    """Least time of ONE expert layer's grouped products in one step over
    `assignments` rows that land here: the larger of their FLOPs at the
    bf16 peak and their bytes at the memory bandwidth (each of the nine
    products reads its rows and the held matrices once and writes its
    result: rows in and out for the six `gmm`, two row operands in and the
    matrices out for the three `tgmm`)."""
    h, _, _, fm = _widths(m)
    matrices = m["num_experts"] * h * fm * W
    rows = assignments * (h + fm) * W
    nbytes = 9 * (matrices + rows)
    return max(expert_flops(m, assignments) / peaks["bf16_flops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def flash_min_seconds(m, seq_len, tokens, peaks):
    """Least time of ONE attention layer's three flash kernels (forward,
    dq, dk/dv) in one step: their causal FLOPs at the bf16 peak. The
    backward kernels form the scores and the probabilities' gradient again,
    which is the algorithm's own arithmetic (no score array is kept): the
    forward's two products, and five in the backward pass."""
    one = tokens * 2 * m["hidden_size"] * seq_len / 2.0
    return 7 * one / peaks["bf16_flops_per_s"]
