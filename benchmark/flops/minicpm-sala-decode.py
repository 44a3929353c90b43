"""Operations and bytes that serving ``minicpm-sala-decode`` NEEDS, from the
configuration's shapes and the REAL lengths: needed work only.

- Every matmul weight once a position (4.44 GFLOP a position over the 8
  layers; the head for a decode step's token only: prefill makes no logits).
- A ``minicpm4`` layer's attention over the positions the query SEES, not its
  context (:func:`visible_positions`: block 0, the window's blocks, 64 chosen
  blocks, to the query's own position: the whole context up to 6,208
  positions, about 6.2k of any longer one), 4 x 128 operations a query head a
  seen position; the selection's scores over the compressed keys usable at
  the position (2 x 128 a head a key).
- A ``lightning-attn`` layer's recurrence: the state's update and its
  read-out, 4 x 128 x 128 operations a head a position, whatever the context.
- Bytes a decode step, the least any program under this configuration moves:
  every matmul weight once (bfloat16, 5.04 GB), K and V of the SEEN positions
  of the running sequences once, their usable compressed keys once, each
  running slot's lightning state read once and written once (float32).

The embedding lookup and the norms' gains are not counted.
"""

import numpy as np

DTYPE_BYTES = {"float32": 4, "bfloat16": 2, "float16": 2, "int8": 1}
SPARSE = "minicpm4"


def n_sparse(c):
    return sum(k == SPARSE for k in c["mixer_types"])


def n_lightning(c):
    return len(c["mixer_types"]) - n_sparse(c)


def mlp_params(c):
    return 3 * c["hidden_size"] * c["intermediate_size"]


def sparse_mixer_params(c):
    """q, gate and o at full width, k and v over the K/V heads."""
    d, hd = c["hidden_size"], c["num_attention_heads"] * c["head_dim"]
    return 3 * d * hd + 2 * d * c["num_key_value_heads"] * c["head_dim"]


def lightning_mixer_params(c):
    """q, k, v, gate and o, each at full width."""
    return 5 * c["hidden_size"] * c["lightning_nh"] * c["lightning_head_dim"]


def head_params(c):
    return c["hidden_size"] * c["vocab"]


def layer_params(c, kind):
    """Every parameter of one layer: the mixer's matrices and norms (two head
    norms; a lightning layer also its output norm), the SwiGLU, two layer norms."""
    d, D = c["hidden_size"], c["head_dim"]
    if kind == SPARSE:
        return sparse_mixer_params(c) + 2 * D + mlp_params(c) + 2 * d
    return lightning_mixer_params(c) + 2 * D + c["lightning_nh"] * c["lightning_head_dim"] + mlp_params(c) + 2 * d


def model_params(c):
    """As ``model.init`` makes them: the layers, embedding, head, final norm."""
    return sum(layer_params(c, k) for k in c["mixer_types"]) + 2 * head_params(c) + c["hidden_size"]


def matmul_params_per_token(c):
    """Matmul weights every token multiplies, all layers (the head apart)."""
    return (n_sparse(c) * (sparse_mixer_params(c) + mlp_params(c))
            + n_lightning(c) * (lightning_mixer_params(c) + mlp_params(c)))


def visible_positions(c, context):
    """Positions the query at the LAST of ``context`` positions sees in a
    minicpm4 layer (itself included); ``context`` a number or an array."""
    sp = c["sparse_config"]
    B = sp["block_size"]
    t = np.maximum(np.asarray(context, np.int64) - 1, 0)
    last, first_local = t // B, np.maximum(t - (sp["window_size"] - 1), 0) // B
    blocks = np.minimum(last + 1, np.minimum(first_local, sp["init_blocks"]) + (last - first_local + 1)
                        + np.minimum(np.maximum(first_local - sp["init_blocks"], 0), sp["topk"]))
    return blocks * B - (B - 1 - t % B)


def usable_compressed(c, context):
    """Compressed keys whose window ends inside ``context`` positions."""
    sp = c["sparse_config"]
    return np.maximum((np.asarray(context, np.int64) - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)


def kv_bytes_per_position(c):
    """K and V of one position of one minicpm4 layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * DTYPE_BYTES[c["kv_dtype"]]


def state_bytes_per_slot(c):
    """One slot's recurrent state over the lightning layers (float32)."""
    return n_lightning(c) * c["lightning_nh"] * c["lightning_head_dim"] ** 2 * 4


def cache_bytes_per_position(c):
    """What one more position adds to the cache: K and V in the minicpm4
    layers and its share of a compressed key."""
    sp = c["sparse_config"]
    return n_sparse(c) * (kv_bytes_per_position(c) + kv_bytes_per_position(c) // 2 // sp["kernel_stride"])


def attention_flops_per_seen(c):
    """QK^T and PV of one (query, seen key) pair over all heads, a minicpm4 layer."""
    return c["num_attention_heads"] * 4 * c["head_dim"]


def selection_flops_per_key(c):
    """One query against one compressed key over all heads, a minicpm4 layer."""
    return c["num_attention_heads"] * 2 * c["head_dim"]


def recurrence_flops_per_position(c):
    """The state's update and read-out over all heads, a lightning layer."""
    return c["lightning_nh"] * 4 * c["lightning_head_dim"] ** 2


def mixing_flops(c, contexts):
    """Attention, selection and recurrence for the queries at the LAST of each
    of ``contexts`` positions (an array), all layers."""
    contexts = np.asarray(contexts, np.int64)
    sparse = (attention_flops_per_seen(c) * visible_positions(c, contexts)
              + selection_flops_per_key(c) * usable_compressed(c, contexts))
    return float(n_sparse(c) * np.sum(sparse) + n_lightning(c) * recurrence_flops_per_position(c) * contexts.size)


def prefill_flops(c, lengths):
    """Forward over ``n`` cached positions a prompt (its last token rides the decode step)."""
    return sum(2 * matmul_params_per_token(c) * n + mixing_flops(c, np.arange(1, n + 1)) for n in lengths)


def _mean_contexts(n_seqs, sum_context):
    """The readers know a step's running sequences and the sum of their
    contexts: every sequence at the mean (what a query sees hardly moves with
    its context past 6,208 positions)."""
    return np.full((int(n_seqs),), sum_context / max(n_seqs, 1))


def decode_flops(c, n_seqs, sum_context):
    """One token a running sequence (``sum_context``: the positions attended
    over, the new one included, summed over the running sequences)."""
    per_token = 2 * (matmul_params_per_token(c) + head_params(c))
    return per_token * n_seqs + mixing_flops(c, _mean_contexts(n_seqs, sum_context))


def decode_bytes(c, n_seqs, sum_context):
    w = DTYPE_BYTES[c["compute_dtype"]]
    contexts = _mean_contexts(n_seqs, sum_context)
    compressed = n_sparse(c) * (kv_bytes_per_position(c) // 2) * float(np.sum(usable_compressed(c, contexts)))
    return ((matmul_params_per_token(c) + head_params(c)) * w + sparse_decode_bytes(c, n_seqs, sum_context)
            + compressed + lightning_step_bytes(c, n_seqs))


def decode_least_seconds(c, peaks, n_seqs, sum_context):
    """The least time the chip needs for one decode step: the larger of the
    operations' and the bytes' bound. -> (seconds, which bound)."""
    by_flops = decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"]
    by_bytes = decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"]
    return (by_flops, "operations") if by_flops >= by_bytes else (by_bytes, "bytes")


# -- the kernels' own counts (metrics/sparse_decode_roofline.decode.py, lightning_step_roofline.decode.py,
# -- sparse_prefill_roofline.decode.py): what the operations of that NAME need, at real lengths
def sparse_decode_flops(c, n_seqs, sum_context):
    return float(n_sparse(c) * attention_flops_per_seen(c)
                 * np.sum(visible_positions(c, _mean_contexts(n_seqs, sum_context))))


def sparse_decode_bytes(c, n_seqs, sum_context):
    """K and V of the positions the running sequences' queries SEE (the chosen
    pages, not the contexts), read once. The compressed keys are read by the
    selection, whose operations are not the kernel's: they are in
    ``decode_bytes`` and not here."""
    return float(n_sparse(c) * kv_bytes_per_position(c)
                 * np.sum(visible_positions(c, _mean_contexts(n_seqs, sum_context))))


def sparse_decode_least_seconds(c, peaks, n_seqs, sum_context):
    return max(sparse_decode_flops(c, n_seqs, sum_context) / peaks["bf16_flops"],
               sparse_decode_bytes(c, n_seqs, sum_context) / peaks["hbm_bytes_per_s"])


def lightning_step_bytes(c, n_seqs):
    """One read and one write of the running slots' state."""
    return 2 * n_seqs * state_bytes_per_slot(c)


def lightning_step_least_seconds(c, peaks, n_seqs):
    return max(n_lightning(c) * recurrence_flops_per_position(c) * n_seqs / peaks["bf16_flops"],
               lightning_step_bytes(c, n_seqs) / peaks["hbm_bytes_per_s"])


def sparse_prefill_least_seconds(c, peaks, n):
    """The flash pass of one prompt of ``n`` cached positions, both minicpm4
    layers: bound by its operations over the seen pairs (q, k, v and the
    output move 8 KB a position a layer against 16,384 operations a seen pair)."""
    pairs = float(np.sum(visible_positions(c, np.arange(1, n + 1))))
    moved = n_sparse(c) * n * (2 * c["num_attention_heads"] * c["head_dim"] * DTYPE_BYTES[c["compute_dtype"]]
                               + kv_bytes_per_position(c))
    return max(n_sparse(c) * attention_flops_per_seen(c) * pairs / peaks["bf16_flops"],
               moved / peaks["hbm_bytes_per_s"])


def step_flops(c):
    """The harness's common name: one decode step with every slot running at
    the cell's mean context (a size for tables, not a measurement)."""
    s = c["engine"]["max_seqs"]
    return decode_flops(c, s, s * (c["seq_len"] - c["engine"]["max_new_tokens"] // 2))
