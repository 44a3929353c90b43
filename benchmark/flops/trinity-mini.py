"""Operations and bytes the ``trinity-mini`` step NEEDS, from the
configuration's shapes: no recompute counted (the program recomputes every
layer in its backward pass), attention counted by the exact number of
(query, key) pairs each kind of layer sees, the routed experts at the EXPECTED
load of this chip's share (``num_experts_per_tok * held / num_experts`` experts
a token: 1 at 8 of 128 with 16 held; the kernels' roofline takes the pairs the
step really computed, from the program's counter), the embedding lookup not
counted."""


def tokens(c):
    return c["batch_size"] * c["seq_len"]


def attention_params(c):
    """q, k, v, output gate and output projection of one layer."""
    d, hd = c["hidden_size"], c["head_dim"]
    H, Hk = c["num_attention_heads"], c["num_key_value_heads"]
    return d * hd * (3 * H + 2 * Hk)


def expert_params(c):
    """One expert, routed or shared: three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def experts_per_token_here(c):
    return c["num_experts_per_tok"] * c["num_experts_held"] / c["num_experts"]


def matmul_params_per_token(c):
    """Weights a token passes through, the routed experts at their expected load."""
    d = c["hidden_size"]
    total = d * c["vocab"]  # the head
    for _, _, ffn in c["layers_run"]:
        total += attention_params(c)
        if ffn == "dense":
            total += 3 * d * c["intermediate_size"]
        else:
            total += d * c["num_experts"]  # the router, all of it
            total += expert_params(c) * (c["num_shared_experts"] + experts_per_token_here(c))
    return total


def matmul_flops(c):
    """Forward and backward over every weight matrix: 6 per parameter per token."""
    return 6 * matmul_params_per_token(c) * tokens(c)


def attended_pairs(T, window):
    """(query, key) pairs of one sequence: query t sees min(t + 1, window) keys."""
    if window is None or window >= T:
        return T * (T + 1) // 2
    return window * (window + 1) // 2 + (T - window) * window


def flops_per_pair(c):
    """QK^T and PV forward (2 flops x 2 matmuls), twice that backward, over
    every query head and the head dimension."""
    return 3 * 4 * c["head_dim"] * c["num_attention_heads"]


def attention_flops(c):
    pairs = sum(attended_pairs(c["seq_len"], window) for window, _, _ in c["layers_run"])
    return c["batch_size"] * pairs * flops_per_pair(c)


def step_flops(c):
    return matmul_flops(c) + attention_flops(c)


def n_routed(c):
    return sum(ffn == "routed" for _, _, ffn in c["layers_run"])


def routed_rows(c):
    """Rows of one routed layer's grouped products at the expected load."""
    return tokens(c) * experts_per_token_here(c)


def routed_flops(c, rows=None):
    """The grouped products alone (gate, up, down of the held experts):
    forward, dX and dW. ``rows``: the token-expert pairs a step really
    computed here, all routed layers together (the recorder's
    ``moe_pairs_here``); the expected load where not given."""
    rows = routed_rows(c) * n_routed(c) if rows is None else rows
    return 6 * expert_params(c) * rows


def routed_bytes(c, rows=None, bytes_per=2):
    """HBM traffic the nine grouped products of a routed layer need in the
    compute dtype: each reads its two operands and writes its result once
    (x and the weights for gate, up, down; dY and the weights for the three
    dX; x and dY for the three dW). The weights move whatever the load."""
    d, f, G = c["hidden_size"], c["moe_intermediate_size"], c["num_experts_held"]
    rows = routed_rows(c) * n_routed(c) if rows is None else rows
    return int(9 * bytes_per * (n_routed(c) * G * d * f + rows * (d + f)))
