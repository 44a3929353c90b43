"""Operations and bytes that serving ``evabyte-decode`` NEEDS, from the
configuration's shapes and the REAL lengths: needed work only.

- Every matmul weight once a position (q, k, v, o and the SwiGLU: 0.81
  GFLOP a position over the 4 layers; for a decode step's byte the first
  prediction head too, the one it decodes from). A prefill makes no logits,
  so the last layer's queries, attention, output and SwiGLU are not needed
  (the compiler drops them): its keys and values are.
- EVA attention over the rows the query READS, not its context
  (:func:`window_positions`: its window block's exact keys up to itself;
  :func:`compressed_rows`: one row a chunk of every earlier block), 4 x 128
  operations a head a row read; the compression of a chunk (two softmaxes
  over its 16 positions and two weighted sums, about 8 x 128 operations a
  head a position) once a chunk.
- Bytes a decode step, the least any program under this configuration moves:
  every matmul weight once (bfloat16, 1.62 GB with the first head), the ring
  rows and compressed rows the running sequences' queries read once, the new
  ring row written once and a completed chunk's row once in 16 steps.

The embedding lookup and the norms' gains are not counted.
"""

import numpy as np

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}


def width(c):
    return c["num_attention_heads"] * c["head_dim"]


def mlp_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def attn_params(c):
    """q, k, v and o at full width (32 K/V heads: MHA)."""
    return 4 * c["hidden_size"] * width(c)


def layer_params(c):
    """Every parameter of one layer: the four projections, the SwiGLU, two
    norms and the two EVA vectors of each head."""
    return attn_params(c) + mlp_params(c) + 2 * c["hidden_size"] + 2 * width(c)


def head_params(c):
    """ONE prediction head."""
    return c["hidden_size"] * c["vocab_size"]


def model_params(c):
    """As ``model.init`` makes them: the layers, embedding, every prediction head, final norm."""
    return (c["num_hidden_layers"] * layer_params(c) + head_params(c) * (1 + c["num_pred_heads"])
            + c["hidden_size"])


def matmul_params_per_token(c):
    """Matmul weights every position multiplies, all layers (the heads apart)."""
    return c["num_hidden_layers"] * (attn_params(c) + mlp_params(c))


def window_positions(c, context):
    """Exact keys the query at the LAST of ``context`` positions reads: its
    window block's, itself included; ``context`` a number or an array."""
    t = np.maximum(np.asarray(context, np.int64) - 1, 0)
    return t % c["window_size"] + 1


def compressed_rows(c, context):
    """Compressed rows it reads: one a chunk of every earlier window block."""
    t = np.maximum(np.asarray(context, np.int64) - 1, 0)
    W = c["window_size"]
    return (t // W) * (W // c["chunk_size"])


def rows_read(c, context):
    return window_positions(c, context) + compressed_rows(c, context)


def row_bytes(c):
    """K and V of one position (or one compressed pair) of one layer."""
    return 2 * width(c) * DTYPE_BYTES[c["kv_dtype"]]


def cache_bytes_per_position(c):
    """What one more position adds to the cache: its share of a compressed
    pair in every layer (the ring is held a slot, whatever the length)."""
    return c["num_hidden_layers"] * row_bytes(c) // c["chunk_size"]


def ring_bytes_per_slot(c):
    return c["num_hidden_layers"] * c["window_size"] * row_bytes(c)


def attention_flops_per_row(c):
    """QK^T and PV of one (query, row read) pair over all heads, a layer."""
    return c["num_attention_heads"] * 4 * c["head_dim"]


def compress_flops_per_position(c):
    """Two logits, two exponentials' share and two weighted sums a head."""
    return c["num_attention_heads"] * 8 * c["head_dim"]


def mixing_flops(c, contexts):
    """EVA attention and compression for the queries at the LAST of each of
    ``contexts`` positions (an array), all layers."""
    contexts = np.asarray(contexts, np.int64)
    return float(c["num_hidden_layers"] * (attention_flops_per_row(c) * np.sum(rows_read(c, contexts))
                                           + compress_flops_per_position(c) * contexts.size))


def prefill_pairs(c, n):
    """(query, row read) pairs of a prompt of ``n`` cached positions."""
    return float(np.sum(rows_read(c, np.arange(1, n + 1))))


def prefill_matmul_params(c):
    """Matmul weights a cached position of a prompt NEEDS: every layer but
    the last whole, the last layer's keys and values alone (its queries,
    attention, output and SwiGLU feed nothing a prefill keeps: no logits)."""
    L = c["num_hidden_layers"]
    return (L - 1) * (attn_params(c) + mlp_params(c)) + 2 * c["hidden_size"] * width(c)


def prefill_flops(c, lengths):
    """Forward over ``n`` cached positions a prompt (its last byte rides the
    decode step): what the cache needs (:func:`prefill_matmul_params`), the
    attention of every layer but the last, every layer's compression."""
    L = c["num_hidden_layers"]
    return sum(2 * prefill_matmul_params(c) * n + (L - 1) * attention_flops_per_row(c) * prefill_pairs(c, n)
               + L * compress_flops_per_position(c) * n for n in lengths)


def _mean_contexts(n_seqs, sum_context):
    """The readers know a step's running sequences and the sum of their
    contexts: every sequence at the mean. What a query reads moves with its
    place in its window block (a saw of 2,048), so this is an estimate for
    tables; the kernel's reader takes the program's own counts."""
    return np.full((int(n_seqs),), sum_context / max(n_seqs, 1))


def decode_flops(c, n_seqs, sum_context):
    """One byte a running sequence (``sum_context``: the positions attended
    over, the new one included, summed over the running sequences)."""
    per_token = 2 * (matmul_params_per_token(c) + head_params(c))
    return per_token * n_seqs + mixing_flops(c, _mean_contexts(n_seqs, sum_context))


def decode_bytes(c, n_seqs, sum_context):
    """The weights once, and what the EVA layers move: the cached ring rows
    and compressed rows read, a new ring row written a sequence and a
    compressed row once in ``C`` steps."""
    w = DTYPE_BYTES[c["compute_dtype"]]
    contexts = _mean_contexts(n_seqs, sum_context)
    rows = float(np.sum(window_positions(c, contexts) - 1 + compressed_rows(c, contexts)))
    return (matmul_params_per_token(c) + head_params(c)) * w + c["num_hidden_layers"] * row_bytes(c) * (
        rows + n_seqs * (1 + 1 / c["chunk_size"]))


def decode_least_seconds(c, peaks, n_seqs, sum_context):
    """The least time the chip needs for one decode step: the larger of the
    operations' and the bytes' bound. -> (seconds, which bound)."""
    by_flops = decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"]
    by_bytes = decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (by_bytes, "bytes")


# -- the kernels' own counts (metrics/eva_decode_roofline.decode.py, eva_prefill_roofline.decode.py):
# -- what the operations of that NAME need, at real lengths
def eva_decode_least_seconds(c, peaks, n_seqs, window_positions_sum, compressed_rows_sum):
    """One step of ``eva_decode`` over every layer, from the program's own
    counts of what its queries read (the sums over the running slots of
    ``tmpi_decode_eva_window_positions`` and ``..._compressed_rows``): the
    cached ring rows (the window's positions less the query's own, which
    comes as an operand) and the compressed rows, K and V once each, against
    4 x 128 operations a head a row: bytes bound it (16 operations a byte)."""
    L = c["num_hidden_layers"]
    rows = float(window_positions_sum - n_seqs + compressed_rows_sum)
    return max(L * attention_flops_per_row(c) * rows / peaks["bf16_flops"],
               L * row_bytes(c) * rows / peaks["hbm_bytes_per_s"])


def eva_prefill_least_seconds(c, peaks, n):
    """The flash passes of one prompt of ``n`` cached positions, every layer
    but the last (whose attention feeds nothing a prefill keeps): 4 x 128
    operations a head a (query, row read) pair; q, k, v, the output and the
    compressed rows move 34 KB a position a layer."""
    L = c["num_hidden_layers"] - 1
    moved = L * n * width(c) * DTYPE_BYTES[c["compute_dtype"]] * (4 + 2 / c["chunk_size"])
    return max(L * attention_flops_per_row(c) * prefill_pairs(c, n) / peaks["bf16_flops"],
               moved / peaks["hbm_bytes_per_s"])


def step_flops(c):
    """The harness's common name: one decode step with every slot running at
    the cell's mean context (a size for tables, not a measurement)."""
    s = c["engine"]["max_seqs"]
    return decode_flops(c, s, s * (c["seq_len"] - c["engine"]["max_new_tokens"] // 2))
