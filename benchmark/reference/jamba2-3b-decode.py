"""Plain reference for ``jamba2-3b-decode``: the ``jamba`` layer equations as
the configuration file states them, in ``jax.numpy``, float32 at ``highest``
matmul precision, over a request's whole token history: no cache, no paging,
no kernel, no batching. Imports nothing of the program; ``init`` makes the
same bfloat16 leaves from the seed, and the arithmetic runs on them in
float32.

    x = emb[token]
    x += mixer(norm_1(x));  x += mlp(norm_2(x))      mlp(h) = (silu(h W_g) * (h W_u)) W_d
    logits = norm_f(x) emb^T                        norm(x) = x / sqrt(mean(x^2) + 1e-6) * g

Layer ``i`` is attention where ``i % attn_layer_period == attn_layer_offset``,
else Mamba-1.

- attention: ``q = h W_q`` in 20 heads of 128, ``k = h W_k``, ``v = h W_v``
  ONE head of 128 shared by all 20; no position signal; a plain masked
  softmax over the keys ``<= t`` at scale ``128^-1/2``, in blocks of query
  rows; ``W_o``.
- Mamba-1: ``x, z = split(h W_in)``; ``x = silu(conv(x) + b)`` with ``conv``
  the causal depthwise convolution of 4 taps (noughts before position 0);
  ``dt_r, B, C = split(x W_x)``, each RMS-normed with its gain; ``dt =
  softplus(dt_r W_dt + b_dt)``; ``A = -exp(A_log)``; ONE POSITION AT A TIME
  (``lax.scan``) ``h_t = exp(dt_t A) h_(t-1) + (dt_t x_t) B_t`` with ``h [5120,
  16]``, ``y_t = h_t C_t + D x_t``; ``(y * silu(z)) W_out``.

It computes LAYER BY LAYER (one jitted program a kind), the projections and
the SwiGLU in blocks of rows, so that it fits beside the program's weights;
a history that is a prefix of another is not run again: its rows are read
from the longer one's (causality makes them the same).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402

ROWS = 512  # rows a block of the projections and of the SwiGLU
QUERY_ROWS = 128  # query rows a block of attention: [20, 128, T] scores
BF16 = jnp.bfloat16


def kinds(c):
    n, period, offset = c["num_hidden_layers"], c["attn_layer_period"], c["attn_layer_offset"]
    return ["attention" if i % period == offset else "mamba" for i in range(n)]


def init(c, seed):
    """normal(0, init_std) matrices, taps and bias cast to bfloat16, gains
    ones, ``A_log = log(1 .. 16)`` a channel, ``D`` ones, ``b_dt`` the inverse
    softplus of ``dt`` log-uniform in [1e-3, 1e-1] (floored at 1e-4); keys
    as the configuration's ``notes`` give them."""
    d, V, f, s = c["hidden_size"], c["vocab_size"], c["intermediate_size"], c["init_std"]
    H, G, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    E, N, R, K = c["mamba_expand"] * d, c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"]
    lo, hi = c["dt_init"]["min"], c["dt_init"]["max"]

    def w(k, *shape):
        return (s * jax.random.normal(k, shape)).astype(BF16)

    ks = jax.random.split(jax.random.PRNGKey(seed), 1 + c["num_hidden_layers"])
    params = {"tok_emb": w(ks[0], V, d), "norm_f": jnp.ones((d,), BF16), "layers": []}
    for kind, kl in zip(kinds(c), ks[1:]):
        k = jax.random.split(kl, 10)
        if kind == "attention":
            mixer = {"w_q": w(k[0], d, H * D), "w_k": w(k[1], d, G * D), "w_v": w(k[2], d, G * D),
                     "w_o": w(k[3], H * D, d)}
        else:
            u = jax.random.uniform(k[5], (E,))
            step = jnp.maximum(jnp.exp(u * (np.log(hi) - np.log(lo)) + np.log(lo)), 1e-4)
            mixer = {"w_in": w(k[0], d, 2 * E), "conv_w": w(k[1], K, E), "conv_b": w(k[2], E),
                     "w_x": w(k[3], E, R + 2 * N), "w_dt": w(k[4], R, E),
                     "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(BF16),
                     "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (E, N)).astype(BF16),
                     "D": jnp.ones((E,), BF16), "dt_norm": jnp.ones((R,), BF16), "b_norm": jnp.ones((N,), BF16),
                     "c_norm": jnp.ones((N,), BF16), "w_out": w(k[6], E, d)}
        params["layers"].append({
            "mixer": mixer, "norm_1": jnp.ones((d,), BF16), "norm_2": jnp.ones((d,), BF16),
            "mlp": {"w_g": w(k[7], d, f), "w_u": w(k[8], d, f), "w_d": w(k[9], f, d)},
        })
    return params


def f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def project(h, w, op):
    """``h @ w`` in blocks of rows."""
    w = op(f32(w))
    return jax.lax.map(lambda hb: jnp.einsum("td,dk->tk", op(hb), w),
                       h.reshape(-1, min(ROWS, h.shape[0]), h.shape[1])).reshape(h.shape[0], -1)


def attention(c, p, h, op):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T = h.shape[0]
    H, G, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    q = project(h, p["w_q"], op).reshape(T, G, H // G, D)
    k, v = project(h, p["w_k"], op).reshape(T, G, D), project(h, p["w_v"], op).reshape(T, G, D)
    rows = min(QUERY_ROWS, T)
    keys = jnp.arange(T)

    def some_rows(start):
        t = start + jnp.arange(rows)
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows)
        s = jnp.einsum("tgrd,sgd->tgrs", op(qb), op(k)) * D ** -0.5
        prob = jax.nn.softmax(jnp.where((keys[None, :] <= t[:, None])[:, None, None], s, -jnp.inf), axis=-1)
        return jnp.einsum("tgrs,sgd->tgrd", op(prob), op(v))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, H * D)
    return project(att, p["w_o"], op)


def recurrence(dt, x, B, C, A, D):
    """``h_t = exp(dt_t A) h_(t-1) + (dt_t x_t) B_t``, ``y_t = h_t C_t + D
    x_t``, one position at a time from ``h = 0``: ``dt, x [T, E]``, ``B, C
    [T, N]``, ``A [E, N]``, ``D [E]`` -> (``y [T, E]``, the last ``h [E, N]``)."""
    def one(h, row):
        dt_t, x_t, B_t, C_t = row
        h = jnp.exp(dt_t[:, None] * A) * h + (dt_t * x_t)[:, None] * B_t[None, :]
        return h, jnp.einsum("en,n->e", h, C_t) + D * x_t

    last, y = jax.lax.scan(one, jnp.zeros(A.shape, jnp.float32), (dt, x, B, C), unroll=8)
    return y, last


def mamba(c, p, h, op):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T, E = h.shape[0], c["mamba_expand"] * c["hidden_size"]
    N, R, K, eps = c["mamba_d_state"], c["mamba_dt_rank"], c["mamba_d_conv"], c["rms_norm_eps"]
    xz = project(h, p["w_in"], op)
    x, z = xz[:, :E], xz[:, E:]
    padded = jnp.concatenate([jnp.zeros((K - 1, E), jnp.float32), x])
    x = jax.nn.silu(sum(padded[j:j + T] * f32(p["conv_w"][j]) for j in range(K)) + f32(p["conv_b"]))
    dbc = project(x, p["w_x"], op)
    dt_r = _norm(dbc[:, :R], p["dt_norm"], eps)
    B, C = _norm(dbc[:, R:R + N], p["b_norm"], eps), _norm(dbc[:, R + N:], p["c_norm"], eps)
    dt = jax.nn.softplus(project(dt_r, p["w_dt"], op) + f32(p["b_dt"]))
    y, _ = recurrence(dt, x, B, C, -jnp.exp(f32(p["A_log"])), f32(p["D"]))
    return project(y * jax.nn.silu(z), p["w_out"], op)


def mlp(p, h, op):
    """In blocks of rows: the ``[T, intermediate]`` float32 arrays of a whole
    history would not fit beside two copies of the weights."""
    w_g, w_u, w_d = op(f32(p["w_g"])), op(f32(p["w_u"])), op(f32(p["w_d"]))

    def some_rows(hb):
        mid = jax.nn.silu(jnp.einsum("td,df->tf", op(hb), w_g)) * jnp.einsum("td,df->tf", op(hb), w_u)
        return jnp.einsum("tf,fd->td", op(mid), w_d)

    rows = min(ROWS, h.shape[0])
    return jax.lax.map(some_rows, h.reshape(-1, rows, h.shape[1])).reshape(h.shape)


class Layers:
    """The model's parts as jitted programs, one a kind of layer."""

    def __init__(self, c, precision):
        op = lambda a: common.operand(a, precision)  # noqa: E731
        eps = c["rms_norm_eps"]

        def layer(mixer):
            def run(p, x):
                x = x + mixer(c, p["mixer"], _norm(x, p["norm_1"], eps), op)
                return x + mlp(p["mlp"], _norm(x, p["norm_2"], eps), op)
            return jax.jit(run)

        self.kinds = kinds(c)
        self.layer = {"attention": layer(attention), "mamba": layer(mamba)}
        self.embed = jax.jit(lambda e, t: f32(e[t]))
        self.head = jax.jit(lambda g, e, x, pos: jnp.einsum(
            "pd,vd->pv", op(_norm(x[pos], g, eps)), op(f32(e))))

    def logits_at(self, params, tokens, positions):
        """``tokens [T]`` (padded at the end: causality keeps the padding out of
        every earlier position), ``positions [P]`` -> logits ``[P, V]``."""
        x = self.embed(params["tok_emb"], tokens)
        for kind, p in zip(self.kinds, params["layers"]):
            x = self.layer[kind](p, x)
        return self.head(params["norm_f"], params["tok_emb"], x, positions)


def _longest_holders(samples):
    """For each sample the index of the longest sample whose history starts
    with its history (itself where there is none)."""
    hold = []
    for hist, _ in samples:
        best = None
        for j, (other, _) in enumerate(samples):
            if len(other) >= len(hist) and np.array_equal(other[:len(hist)], hist):
                if best is None or len(other) > len(samples[best][0]):
                    best = j
        hold.append(best)
    return hold


def run(config, seed, samples, precision="float32", rows=None, params=None):
    """``samples``: a list of ``(history ids [n], positions [p])``. -> a list
    of float32 arrays ``[p, V]`` on the host, and the weights under ``"init"``
    for the exact compare. One history at a time, each padded to the longest
    context the configuration serves (whole blocks of rows): one program a
    kind of layer, whatever the lengths."""
    del rows
    longest = int(config["seq_len"])
    T = -(-longest // ROWS) * ROWS
    hold = _longest_holders(samples)
    with jax.default_matmul_precision("highest"):
        # one program, as the engine's weights are made: the same fusions, so the same bits
        params = jax.jit(lambda: init(config, seed))() if params is None else params
        layers = Layers(config, precision)
        out = [None] * len(samples)
        for root in sorted(set(hold)):
            mine = [i for i, h in enumerate(hold) if h == root]
            wanted = np.concatenate([samples[i][1] for i in mine]).astype(np.int32)
            tok = np.zeros((T,), np.int32)
            tok[:len(samples[root][0])] = samples[root][0]
            # positions padded to a power of two: few head programs for every width
            at = np.zeros((1 << (len(wanted) - 1).bit_length(),), np.int32)
            at[:len(wanted)] = wanted
            got = np.asarray(layers.logits_at(params, jnp.asarray(tok), jnp.asarray(at)))
            start = 0
            for i in mine:
                n = len(samples[i][1])
                out[i] = got[start:start + n]
                start += n
    return {"logits": out, "init": params}
