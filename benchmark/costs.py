"""Operations and bytes the algorithms need, from shapes alone. Recomputed
operations do not count. Kept with the benchmark so that no PR that claims
a gain can change the denominator of its own utilisation."""


def bert_train_flops_per_token(m, seq_len):
    """Forward + backward (3x forward) of one token of BERT MLM pretraining:
    2 FLOPs per multiply-add. Per layer the four projections and the two
    FFN matrices (12 h^2 with ffn = 4h, written out), attention scores and
    context (2 * seq * h each way), plus the tied vocabulary projection.
    Same arithmetic as paddle_tpu/analysis/costs.py
    bert_train_flops_per_token at PR 21."""
    h, f, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    per_layer = 2 * (4 * h * h + 2 * h * f) + 2 * 2 * seq_len * h
    forward = m["num_hidden_layers"] * per_layer + 2 * h * v
    return 3 * forward


def gpt_weight_bytes(m, bytes_per_weight=4):
    h, f, v = m["n_embd"], m["n_inner"], m["vocab_size"]
    layer = 4 * (h * h + h) + 2 * h * f + f + h + 4 * h
    # a decode step reads every layer, the output head, and one row of
    # each embedding per slot (negligible, left out)
    return (m["n_layer"] * layer + h * v + v) * bytes_per_weight


def gpt_step_min_seconds(m, live_rows, peaks, kv_bytes=4):
    """Least time for one decode step: the weights once and each live
    slot's written K and V rows once, at the chip's memory bandwidth.
    `live_rows` is the sum over running requests of their positions."""
    kv = 2 * m["n_layer"] * m["n_embd"] * kv_bytes * live_rows
    return (gpt_weight_bytes(m) + kv) / peaks["hbm_bytes_per_s"]


def gpt_prefill_min_seconds(m, prompt_len, peaks):
    """Least time for one batch-1 prefill of `prompt_len` real tokens: the
    larger of its FLOPs at the bf16 peak and its bytes (weights once)."""
    h, f, v = m["n_embd"], m["n_inner"], m["vocab_size"]
    per_tok = m["n_layer"] * (2 * (4 * h * h + 2 * h * f)
                              + 2 * 2 * prompt_len / 2 * h)
    flops = prompt_len * per_tok + 2 * h * v
    return max(flops / peaks["bf16_flops_per_s"],
               gpt_weight_bytes(m) / peaks["hbm_bytes_per_s"])
