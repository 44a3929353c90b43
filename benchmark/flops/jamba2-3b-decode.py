"""Operations and bytes that serving ``jamba2-3b-decode`` NEEDS, from the
configuration's shapes and the REAL lengths: needed work only.

- Every matmul weight once a position (2.86 GFLOP a position over the 14
  layers; the tied head for a decode step's token only: prefill makes no
  logits). A prefill keeps the attention layer's K and V and each Mamba
  layer's window and state: the last layer's SwiGLU, ``W_out`` and the ``z``
  half of ``W_in`` feed nothing it keeps, and are not counted.
- The attention layer's products over the keys each query sees (all of its
  context, causally), 4 x 128 operations a query head a (query, key) pair.
- The Mamba layers' scan and convolution are elementwise work (exponentials
  and multiply-adds on the vector units, no matmul): they are not counted as
  operations against the bf16 peak. Their least time is counted in bytes
  (:func:`mamba_scan_least_seconds`), and their elementwise work is given
  beside it (:func:`mamba_scan_elementwise`).
- Bytes a decode step, the least any program under this configuration moves:
  every matmul weight and the tied head once (bfloat16, 3.19 GB), K and V of
  the running sequences' contexts once, each running slot's Mamba state read
  once and written once (float32) and its conv window read once and written
  once.

The embedding lookup and the norms' gains are not counted.
"""

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def kinds(c):
    n, period, offset = c["num_hidden_layers"], c["attn_layer_period"], c["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba" for i in range(n)]


def n_attention(c):
    return kinds(c).count("attention")


def n_mamba(c):
    return kinds(c).count("mamba")


def d_inner(c):
    return c["mamba_expand"] * c["hidden_size"]


def mlp_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def attention_mixer_params(c):
    """q and o over the query heads, k and v over the K/V heads."""
    d, D = c["hidden_size"], c["head_dim"]
    return 2 * d * c["num_attention_heads"] * D + 2 * d * c["num_key_value_heads"] * D


def mamba_matmul_params(c):
    """W_in (x and z), W_x (dt_r, B, C), W_dt and W_out."""
    d, E, N, R = c["hidden_size"], d_inner(c), c["mamba_d_state"], c["mamba_dt_rank"]
    return 2 * d * E + E * (R + 2 * N) + R * E + E * d


def mamba_mixer_params(c):
    """The matmuls, the convolution's taps and bias, b_dt, A_log, D and the
    three inner norms' gains."""
    E, N, R, K = d_inner(c), c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    return mamba_matmul_params(c) + K * E + E + E + E * N + E + R + 2 * N


def head_params(c):
    """The tied embedding: the head's weights are the embedding's."""
    return c["hidden_size"] * c["vocab_size"]


def layer_params(c, kind):
    mixer = attention_mixer_params(c) if kind == "attention" else mamba_mixer_params(c)
    return mixer + mlp_params(c) + 2 * c["hidden_size"]


def model_params(c):
    """As ``model.init`` makes them: the layers, the tied embedding, the final norm."""
    return sum(layer_params(c, k) for k in kinds(c)) + head_params(c) + c["hidden_size"]


def matmul_params_per_token(c):
    """Matmul weights every decoded token multiplies, all layers (the head apart)."""
    return sum((attention_mixer_params(c) if k == "attention" else mamba_matmul_params(c)) + mlp_params(c)
               for k in kinds(c))


def prefill_matmul_params(c):
    """What a prefill needs of them: the last layer's SwiGLU and the part of
    its mixer that feeds only the layer's output are dead."""
    d, E, D = c["hidden_size"], d_inner(c), c["head_dim"]
    last = kinds(c)[-1]
    dead = mlp_params(c) + (2 * d * c["num_attention_heads"] * D if last == "attention" else d * E + E * d)
    return matmul_params_per_token(c) - dead


def attention_flops_per_pair(c):
    """QK^T and PV of one (query, key) pair over all query heads, one layer."""
    return c["num_attention_heads"] * 4 * c["head_dim"]


def prefill_flops(c, lengths):
    """Forward over ``n`` cached positions a prompt (its last token rides the
    decode step): the matmuls and the causal attention products."""
    return float(sum(2 * prefill_matmul_params(c) * n + n_attention(c) * attention_flops_per_pair(c) * n * (n + 1) // 2
                     for n in lengths))


def kv_bytes_per_position(c):
    """K and V of one position of one attention layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * DTYPE_BYTES[c["kv_dtype"]]


def state_bytes_per_slot(c):
    """One slot's Mamba state (float32) and conv window (compute dtype), all
    Mamba layers."""
    E = d_inner(c)
    return n_mamba(c) * (c["mamba_d_state"] * E * 4 + (c["mamba_d_conv"] - 1) * E * DTYPE_BYTES[c["compute_dtype"]])


def decode_flops(c, n_seqs, sum_context):
    """One token a running sequence (``sum_context``: the positions attended
    over, the new one included, summed over the running sequences)."""
    return (2 * (matmul_params_per_token(c) + head_params(c)) * n_seqs
            + n_attention(c) * attention_flops_per_pair(c) * sum_context)


def decode_bytes(c, n_seqs, sum_context):
    w = DTYPE_BYTES[c["compute_dtype"]]
    return ((matmul_params_per_token(c) + head_params(c)) * w + n_attention(c) * kv_bytes_per_position(c) * sum_context
            + 2 * n_seqs * state_bytes_per_slot(c))


def decode_least_seconds(c, peaks, n_seqs, sum_context):
    """The least time the chip needs for one decode step: the larger of the
    operations' and the bytes' bound. -> (seconds, which bound)."""
    by_flops = decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"]
    by_bytes = decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (by_bytes, "bytes")


# -- the kernel's own count (metrics/mamba_scan_roofline.decode.py): what the scans of a prompt need at its
# -- real length, whatever a kernel reads
def mamba_scan_bytes_per_row(c):
    """x, z, dt and y, each ``d_inner`` wide in the compute dtype, and B and C
    of ``d_state``: each read or written once a row and Mamba layer."""
    b = DTYPE_BYTES[c["compute_dtype"]]
    return 4 * d_inner(c) * b + 2 * c["mamba_d_state"] * b


def mamba_scan_least_seconds(c, peaks, n):
    """The Mamba layers' scans of one prompt of ``n`` cached positions, bound
    by the bytes above over the peak bandwidth. A floor: the scan's
    exponentials and multiply-adds (:func:`mamba_scan_elementwise`) run on
    the vector and transcendental units, for which the peaks have no entry."""
    return n_mamba(c) * n * mamba_scan_bytes_per_row(c) / peaks["hbm_bytes_per_s"]


def mamba_scan_elementwise(c, n):
    """-> (exponentials, multiply-adds) of the scans of one prompt of ``n``
    positions: ``exp(dt A)`` a (state, channel), the update's two and the
    read-out's one multiply-add there."""
    cells = n_mamba(c) * n * c["mamba_d_state"] * d_inner(c)
    return cells, 3 * cells


def step_flops(c):
    """The harness's common name: one decode step with every slot running at
    the cell's mean context (a size for tables, not a measurement)."""
    s = c["engine"]["max_seqs"]
    return decode_flops(c, s, s * (c["seq_len"] - c["engine"]["max_new_tokens"] // 2))

