"""Operations and bytes the ``lm136m`` step NEEDS, from the configuration's
shapes: no recompute counted, causal attention counted as the half it is,
the embedding lookup not counted."""


def matmul_params(c):
    d, f, L, V = c["d_model"], c["d_ff"], c["n_layers"], c["vocab"]
    return L * (4 * d * d + 2 * d * f) + d * V


def matmul_flops(c):
    """Forward and backward over every weight matrix: 6 per parameter per token."""
    return 6 * matmul_params(c) * c["batch_size"] * c["seq_len"]


def attention_flops(c):
    """QK^T and PV forward (2 matmuls), four in backward; causal: half of T^2."""
    B, T, d, L = c["batch_size"], c["seq_len"], c["d_model"], c["n_layers"]
    return 3 * L * B * 2 * T * T * d


def step_flops(c):
    return matmul_flops(c) + attention_flops(c)
