"""Operations and bytes that serving ``gigachat35-decode`` NEEDS, from the
configuration's shapes and the REAL lengths: needed work only.

- Every matmul weight outside the routed experts once a position, the held
  experts' pairs at the expected share (8 choices a token, 8 of 256 experts
  held: a quarter of an expert a token), the head for a decode step's token
  only (prefill makes no logits). A prefill keeps the latent layer's rows and
  each delta-rule layer's window and state: the last layer's MLP and the
  part of its mixer that feeds only its output (``W_z``, ``W_out``) are not
  counted.
- Prefill runs the latent attention EXPANDED: causal pairs over per-head keys
  of ``nope + rope`` and values of ``v``, counted as the half they are. A
  decode step runs it ABSORBED: 64 heads x (2 (512 + 64) + 2 x 512) = 139,264
  operations a cached position, over its 1,152 bytes.
- The delta rule: a decode step's ``M^T k``, rank-1 update and ``M^T q``, 6
  D^2 operations a value head (elementwise work on the vector units, counted
  all the same: 0.7 % of a token's); a prompt's chunk form as its
  products (:func:`gdn_chunk_flops`).
- Bytes a decode step, the least any program under this configuration
  moves: every weight OUTSIDE the routed experts once (bfloat16), the
  EXPECTED number of held experts hit by the running sequences once (a
  token picks 8 of 256, so a held expert is missed by ``n`` tokens with
  probability (1 - 8/256)^n: 7.7 of 8 at 128 sequences), the latent rows at
  real lengths once, each running slot's delta-rule state read once and
  written once (float32) and its conv window read once and written once.

The embedding lookup and the norms' gains are not counted.
"""

import math

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kinds(c):
    """-> [(full attention?, dense MLP?)] of the layers held, in order."""
    return [(i in c["full_attention_layers"], i < c["first_k_dense_replace"]) for i in c["layer_ids"]]


def n_full(c):
    return sum(full for full, _ in kinds(c))


def n_linear(c):
    return sum(not full for full, _ in kinds(c))


def conv_width(c):
    return (2 * c["linear_num_key_heads"] + c["linear_num_value_heads"]) * c["linear_key_head_dim"]


def gdn_matmul_params(c):
    """W_q, W_k (key heads), W_v, W_z (value heads), W_b, W_a, W_out."""
    d, D = c["hidden_size"], c["linear_key_head_dim"]
    Hk, H = c["linear_num_key_heads"], c["linear_num_value_heads"]
    return d * (2 * Hk * D + 2 * H * D + 2 * H) + H * D * d


def gdn_mixer_params(c):
    """The matmuls, the convolution's taps, A_log, dt_bias and the output norm's gain."""
    H = c["linear_num_value_heads"]
    return gdn_matmul_params(c) + c["linear_conv_kernel_dim"] * conv_width(c) + 2 * H + c["linear_value_head_dim"]


def mla_matmul_params(c):
    """W_dq, W_uq, W_dkv, W_ukv, W_o and the output gate W_g."""
    d, H = c["hidden_size"], c["num_attention_heads"]
    ql, kl = c["q_lora_rank"], c["kv_lora_rank"]
    nope, rope, v = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    return d * ql + ql * H * (nope + rope) + d * (kl + rope) + kl * H * (nope + v) + 2 * H * v * d


def mla_mixer_params(c):
    return mla_matmul_params(c) + c["q_lora_rank"] + c["kv_lora_rank"]


def expert_params(c):
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def dense_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def router_params(c):
    return c["hidden_size"] * c["n_routed_experts"]


def head_params(c):
    return c["hidden_size"] * c["vocab"]


def layer_params(c, full, dense):
    """Every parameter of one layer as held here: the mixer, the four norms,
    the dense SwiGLU or the router with its selection bias, the shared expert
    and the held experts."""
    mixer = mla_mixer_params(c) if full else gdn_mixer_params(c)
    mlp = dense_params(c) if dense else (
        router_params(c) + c["n_routed_experts"] + (1 + c["num_experts_held"]) * expert_params(c))
    return mixer + mlp + 4 * c["hidden_size"]


def model_params(c):
    """As ``model.init`` makes them: the layers, embedding, head, final norm."""
    return sum(layer_params(c, f, d) for f, d in kinds(c)) + 2 * head_params(c) + c["hidden_size"]


def experts_per_token_here(c):
    """Expected routed experts a token computes here: its choices times the share held."""
    return c["num_experts_per_tok"] * c["num_experts_held"] / c["n_routed_experts"]


def experts_hit(c, n_tokens):
    """Expected number of the held experts that ``n_tokens`` tokens choose at least once."""
    miss = (1.0 - c["num_experts_per_tok"] / c["n_routed_experts"]) ** n_tokens
    return c["num_experts_held"] * (1.0 - miss)


def mlp_per_token(c, dense):
    """The MLP's weights a token multiplies: the dense SwiGLU, or the router,
    the shared expert and the expected held experts."""
    return dense_params(c) if dense else router_params(c) + (1 + experts_per_token_here(c)) * expert_params(c)


def mixer_matmul_params(c, full):
    return mla_matmul_params(c) if full else gdn_matmul_params(c)


def outside_experts_per_token(c):
    """Weights outside the routed experts that every token multiplies, all layers (the head apart)."""
    return sum(mixer_matmul_params(c, full) + (dense_params(c) if dense else router_params(c) + expert_params(c))
               for full, dense in kinds(c))


def routed_layers(c):
    return sum(not dense for _, dense in kinds(c))


def matmul_flops_per_token(c):
    """A decode step's matmuls a token: the mixers, the MLPs (the expected held
    experts among them), the head."""
    return 2 * (sum(mixer_matmul_params(c, f) + mlp_per_token(c, d) for f, d in kinds(c)) + head_params(c))


def prefill_matmul_params(c):
    """What a prefill needs a position: the last layer's MLP and ``W_z``,
    ``W_out`` of its mixer (a delta-rule layer: its output feeds nothing kept)
    are dead."""
    full, dense = kinds(c)[-1]
    if full:
        raise ValueError("a prefill whose last layer is latent attention keeps that layer's rows only")
    every = sum(mixer_matmul_params(c, f) + mlp_per_token(c, d) for f, d in kinds(c))
    return every - mlp_per_token(c, dense) - 2 * c["hidden_size"] * c["linear_num_value_heads"] * c["linear_value_head_dim"]


def expanded_flops_per_pair(c):
    """QK^T and PV of one (query, key) pair over all heads, a latent layer, on per-head K and V."""
    return c["num_attention_heads"] * 2 * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"] + c["v_head_dim"])


def absorbed_flops_per_position(c):
    """One query against one cached position over all heads, a latent layer, in the latent space."""
    return c["num_attention_heads"] * 2 * (2 * c["kv_lora_rank"] + c["qk_rope_head_dim"])


def prefill_flops(c, lengths):
    """Forward over ``n`` cached positions a prompt (its last token rides the
    decode step): the matmuls, the causal attention products, the delta
    rule's chunk form."""
    return float(sum(2 * prefill_matmul_params(c) * n + n_full(c) * expanded_flops_per_pair(c) * (n * (n + 1) // 2)
                     + gdn_chunk_flops(c, n) for n in lengths))


def gdn_step_flops(c, n_seqs):
    """``M^T k``, the rank-1 update and ``M^T q``: 6 D^2 a value head a layer a slot."""
    D = c["linear_key_head_dim"]
    return n_linear(c) * c["linear_num_value_heads"] * 6 * D * D * n_seqs


def decode_flops(c, n_seqs, sum_context):
    """One token a running sequence (``sum_context``: the positions attended
    over, the new one included, summed over the running sequences)."""
    return (matmul_flops_per_token(c) * n_seqs + n_full(c) * absorbed_flops_per_position(c) * sum_context
            + gdn_step_flops(c, n_seqs))


def latent_bytes_per_position(c):
    """One position over the latent layers: the normed latent and the rotated shared key."""
    return n_full(c) * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * DTYPE_BYTES[c["kv_dtype"]]


def state_bytes_per_slot(c):
    """One slot's delta-rule state (float32) and conv window (compute dtype), all delta-rule layers."""
    H, D = c["linear_num_value_heads"], c["linear_key_head_dim"]
    window = (c["linear_conv_kernel_dim"] - 1) * conv_width(c) * DTYPE_BYTES[c["compute_dtype"]]
    return n_linear(c) * (H * D * D * 4 + window)


def decode_bytes(c, n_seqs, sum_context):
    w = DTYPE_BYTES[c["compute_dtype"]]
    weights = outside_experts_per_token(c) + head_params(c) + routed_layers(c) * experts_hit(c, n_seqs) * expert_params(c)
    return weights * w + latent_bytes_per_position(c) * sum_context + 2 * n_seqs * state_bytes_per_slot(c)


def decode_least_seconds(c, peaks, n_seqs, sum_context):
    """The least time the chip needs for one decode step: the larger of the
    operations' and the bytes' bound. -> (seconds, which bound)."""
    by_flops = decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"]
    by_bytes = decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (by_bytes, "bytes")


# -- the two kernels' own counts (metrics/gdn_step_roofline.decode.py, gdn_chunk_roofline.decode.py)
def gdn_step_bytes(c, n_seqs):
    """The float32 state of ``n_seqs`` slots read once and written once in
    every delta-rule layer; q, k (key heads) and v (value heads) in the
    compute dtype, beta and g (fp32) read once, o (fp32) written once."""
    Hk, H, D = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"]
    w = DTYPE_BYTES[c["compute_dtype"]]
    return n_linear(c) * n_seqs * (2 * H * D * D * 4 + (2 * Hk + H) * D * w + 2 * H * 4 + H * D * 4)


def gdn_step_least_seconds(c, peaks, n_seqs):
    return max(gdn_step_flops(c, n_seqs) / peaks["bf16_flops"], gdn_step_bytes(c, n_seqs) / peaks["hbm_bytes_per_s"])


def gdn_chunk_flops(c, n):
    """The chunk form's products over a prompt of ``n`` real rows, all
    delta-rule layers: a chunk of ``C`` rows a value head takes ``K K^T``,
    ``Q K^T``, ``W = T (beta e^G K)`` and ``T (beta V)`` (``C^2 D`` each), ``W
    M``, ``(e^G Q) M`` and the state's ``K^T U`` (``C D^2`` each), the
    intra-chunk ``O`` (``C^2 D``) and the triangular inverse (``C^3 / 3``):
    multiply-adds, twice. The chunk the prompt ends in counts whole."""
    C, D, H = c["gdn_chunk"], c["linear_key_head_dim"], c["linear_num_value_heads"]
    per_chunk = 5 * C * C * D + 3 * C * D * D + C ** 3 / 3
    return 2 * n_linear(c) * H * math.ceil(n / C) * per_chunk


def gdn_chunk_bytes(c, n):
    """A prompt of ``n`` real rows, all delta-rule layers: q, k and v in the
    compute dtype, beta and g (fp32) read once a row, o (fp32) written once a
    row, the state (fp32) written once."""
    Hk, H, D = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"]
    w = DTYPE_BYTES[c["compute_dtype"]]
    return n_linear(c) * (n * ((2 * Hk + H) * D * w + 2 * H * 4 + H * D * 4) + H * D * D * 4)


def gdn_chunk_least_seconds(c, peaks, n):
    return max(gdn_chunk_flops(c, n) / peaks["bf16_flops"], gdn_chunk_bytes(c, n) / peaks["hbm_bytes_per_s"])


def step_flops(c):
    """The harness's common name: one decode step with every slot running at
    the cell's mean context (a size for tables, not a measurement)."""
    s = c["engine"]["max_seqs"]
    return decode_flops(c, s, s * (c["seq_len"] - c["engine"]["max_new_tokens"] // 2))
