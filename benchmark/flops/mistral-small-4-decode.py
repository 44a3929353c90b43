"""Operations and bytes that serving ``mistral-small-4-decode`` NEEDS, from the
configuration's shapes and the REAL lengths: needed work only.

- Prefill runs the EXPANDED form: every weight outside the routed experts
  once a position, the held experts' pairs at the expected share (4 choices a
  token, 16 of 128 experts held: half an expert a token), causal attention
  over per-head keys of ``nope + rope`` and values of ``v`` counted as the
  half it is; no padding to the bucket; no head (prefill makes no logits).
- A decode step runs the ABSORBED form: the same weights a token (carrying
  the query into the latent space and the output back costs ``W_ukv`` once,
  as rebuilding K and V of one position would), the head, and attention over
  each sequence's own cached latent rows: 32 heads x (2 (256 + 64) + 2 x 256)
  = 36,864 operations a cached position a layer, over its 640 bytes: 57.6 a
  byte.
- Bytes a decode step, the least any program under this configuration moves:
  every weight OUTSIDE the routed experts once (bfloat16), the EXPECTED number
  of held experts hit by the running sequences once (a token picks 4 of 128,
  so a held expert is missed by ``n`` tokens with probability (1 - 4/128)^n:
  10.2 of 16 at 32 sequences), the latent rows at real lengths once.

The embedding lookup and the norms' gains are not counted.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def attention_params(c):
    """Matmul weights of one layer's latent attention (its two norms' 1,280 gains apart)."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return d * ql + ql * H * (nope + rope) + d * (kl + rope) + kl * H * (nope + v) + H * v * d


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["n_routed_experts"]


def head_params(c):
    return c["hidden_size"] * c["vocab"]


def layer_params(c):
    """Every parameter of one layer as held here: attention with its norms,
    the two layer norms, the router, the shared expert, the held experts."""
    norms = c["q_lora_rank"] + c["kv_lora_rank"] + 2 * c["hidden_size"]
    return (attention_params(c) + norms + router_params(c)
            + (1 + c["num_experts_held"]) * expert_params(c))


def model_params(c):
    """As ``model.init`` makes them: the layers, embedding, head, final norm."""
    return c["n_layers"] * layer_params(c) + 2 * head_params(c) + c["hidden_size"]


def experts_per_token_here(c):
    """Expected routed experts a token computes here: its choices times the share held."""
    return c["num_experts_per_tok"] * c["num_experts_held"] / c["n_routed_experts"]


def experts_hit(c, n_tokens):
    """Expected number of the held experts that ``n_tokens`` tokens choose at least once."""
    miss = (1.0 - c["num_experts_per_tok"] / c["n_routed_experts"]) ** n_tokens
    return c["num_experts_held"] * (1.0 - miss)


def dense_params_per_token(c):
    """Weights outside the routed experts that every token multiplies, a layer."""
    return attention_params(c) + router_params(c) + expert_params(c)


def cache_bytes_per_position(c):
    """One position over all layers: the normed latent and the rotated shared key."""
    return c["n_layers"] * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * DTYPE_BYTES[c["kv_dtype"]]


def expanded_flops_per_pair(c):
    """QK^T and PV of one (query, key) pair over all heads, a layer, on per-head K and V."""
    return c["num_attention_heads"] * 2 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def absorbed_flops_per_position(c):
    """One query against one cached position over all heads, a layer, in the latent space."""
    return c["num_attention_heads"] * 2 * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def prefill_flops(c, lengths):
    """Forward over ``n`` cached positions a prompt (its last token rides the decode step)."""
    per_token = 2 * c["n_layers"] * (dense_params_per_token(c) + experts_per_token_here(c) * expert_params(c))
    return sum(per_token * n + c["n_layers"] * expanded_flops_per_pair(c) * (n * (n + 1) // 2) for n in lengths)


def decode_flops(c, n_seqs, sum_context):
    """One token a running sequence (``sum_context``: the positions attended
    over, the new one included, summed over the running sequences)."""
    per_token = 2 * (c["n_layers"] * (dense_params_per_token(c) + experts_per_token_here(c) * expert_params(c))
                     + head_params(c))
    return per_token * n_seqs + mla_decode_flops(c, sum_context)


def decode_bytes(c, n_seqs, sum_context):
    w = DTYPE_BYTES[c["compute_dtype"]]
    dense = c["n_layers"] * dense_params_per_token(c) + head_params(c)
    routed = c["n_layers"] * experts_hit(c, n_seqs) * expert_params(c)
    return (dense + routed) * w + mla_decode_bytes(c, sum_context)


def decode_least_seconds(c, peaks, n_seqs, sum_context):
    """The least time the chip needs for one decode step: the larger of the
    operations' and the bytes' bound. -> (seconds, which bound)."""
    by_flops = decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"]
    by_bytes = decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (by_bytes, "bytes")


# -- the two kernels' own counts (metrics/mla_decode_roofline.decode.py, routed_moe_gmm_roofline.decode.py)
def mla_decode_flops(c, sum_context):
    return c["n_layers"] * absorbed_flops_per_position(c) * sum_context


def mla_decode_bytes(c, sum_context):
    """The latent rows of the running sequences at their real lengths, read once
    (the step's own rows, written once, are among ``sum_context``)."""
    return cache_bytes_per_position(c) * sum_context


def mla_decode_least_seconds(c, peaks, sum_context):
    return max(mla_decode_flops(c, sum_context) / peaks["bf16_flops"],
               mla_decode_bytes(c, sum_context) / peaks["hbm_bytes_per_s"])


def gmm_flops(c, n_tokens):
    """Gate, up and down of the expected pairs computed here, all layers."""
    return 2 * c["n_layers"] * n_tokens * experts_per_token_here(c) * expert_params(c)


def gmm_bytes(c, n_tokens):
    """The three grouped products of every layer: the weights of the experts
    expected to be hit once, each product's rows in and out once."""
    w, d, f = DTYPE_BYTES[c["compute_dtype"]], c["hidden_size"], c["moe_intermediate_size"]
    rows = n_tokens * experts_per_token_here(c)
    return c["n_layers"] * w * (experts_hit(c, n_tokens) * expert_params(c) + 3 * rows * (d + f))


def gmm_least_seconds(c, peaks, n_tokens):
    """One program's routed products over ``n_tokens`` tokens (a prefill's
    positions, or a decode step's running sequences)."""
    return max(gmm_flops(c, n_tokens) / peaks["bf16_flops"],
               gmm_bytes(c, n_tokens) / peaks["hbm_bytes_per_s"])


def step_flops(c):
    """The harness's common name: one decode step with every slot running at
    the cell's mean context (a size for tables, not a measurement)."""
    s = c["engine"]["max_seqs"]
    return decode_flops(c, s, s * (c["seq_len"] - c["engine"]["max_new_tokens"] // 2))
