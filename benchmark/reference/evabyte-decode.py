"""Plain reference for ``evabyte-decode``: the ``evabyte`` layer equations as
the configuration file states them (ISSUE 39, "The block"), in
``jax.numpy``, float32 at ``highest`` matmul precision, over a request's whole
byte history: no cache, no paging, no kernel, no batching. Imports nothing of
the program; ``init`` makes the same bfloat16 leaves from the seed, and the
arithmetic runs on them in float32.

    x = emb[byte]
    x += attn(norm_1(x));  x += mlp(norm_2(x))           mlp(h) = (silu(h W_g) * (h W_u)) W_d
    logits_i = norm_f(x) W_head_i                        norm(x) = x / sqrt(mean(x^2) + 1e-5) * (1 + g)

EVA attention: ``q, k, v = h W_q, h W_k, h W_v`` in 32 heads of 128, ``q``
and ``k`` rotated at their position (pairs ``(j, j + 64)``, frequencies
``100000^(-j / 64)``). (1) Chunk ``c`` (positions ``16c .. 16c + 15``) has
``k~_c = sum_j softmax_j(mu . x_j) k_j`` and ``v~_c = sum_j softmax_j(phi .
x_j - |x_j|^2 / 2) v_j`` with ``x_j = 128^-1/4 k_j``, per head. (2) The query
at ``t`` sees its window block's keys ``2048 floor(t / 2048) .. t`` and the
compressed pairs of every chunk before that block's start, (3) in one
softmax at scale ``128^-1/2``; ``W_o``. ROW BY ROW, in blocks of query rows
inside one window block: the block's exact keys and every compressed row,
masked.

It computes LAYER BY LAYER (one jitted program a part) so that it fits beside
the program's weights.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402

ROWS = 256  # query rows a block of attention and of the SwiGLU
BF16 = jnp.bfloat16


def init(c, seed):
    """normal(0, init_std) matrices and normal(0, head_dim^-1/2) EVA vectors
    cast to bfloat16, gains ``g`` nought; keys as the configuration's
    ``notes`` give them."""
    d, V, f, s = c["hidden_size"], c["vocab_size"], c["intermediate_size"], c["init_std"]
    H, D = c["num_attention_heads"], c["head_dim"]

    def w(k, *shape, std=s):
        return (std * jax.random.normal(k, shape)).astype(BF16)

    ks = jax.random.split(jax.random.PRNGKey(seed), 2 + c["num_hidden_layers"])
    params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, c["num_pred_heads"] * V),
              "norm_f": jnp.zeros((d,), BF16), "layers": []}
    for kl in ks[2:]:
        k = jax.random.split(kl, 9)
        params["layers"].append({
            "attn": {"w_q": w(k[0], d, H * D), "w_k": w(k[1], d, H * D), "w_v": w(k[2], d, H * D),
                     "w_o": w(k[3], H * D, d), "mu": w(k[7], H, D, std=D ** -0.5),
                     "phi": w(k[8], H, D, std=D ** -0.5)},
            "norm_1": jnp.zeros((d,), BF16), "norm_2": jnp.zeros((d,), BF16),
            "mlp": {"w_g": w(k[4], d, f), "w_u": w(k[5], d, f), "w_d": w(k[6], f, d)},
        })
    return params


def f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + f32(g))


def rotate(c, x, positions):
    """``[T, H, D]`` by ``positions [T]``: the pair ``(j, j + D/2)`` turned by
    ``positions * theta^(-j / (D/2))``."""
    half = x.shape[-1] // 2
    freq = jnp.asarray(c["rope_theta"] ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    ang = f32(positions)[:, None, None] * freq[None, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def compress(c, k, v, mu, phi, op):
    """(1): ``k, v [T, H, D]`` -> ``[T / C, H, D]`` twice."""
    C, D = c["chunk_size"], c["head_dim"]
    k, v = k.reshape(-1, C, *k.shape[1:]), v.reshape(-1, C, *v.shape[1:])  # [nC, C, H, D]
    x = k * D ** -0.25
    a = jax.nn.softmax(jnp.einsum("nchd,hd->nch", x, f32(mu)), axis=1)
    b = jax.nn.softmax(jnp.einsum("nchd,hd->nch", x, f32(phi)) - 0.5 * jnp.sum(x * x, -1), axis=1)
    return jnp.einsum("nch,nchd->nhd", a, op(k)), jnp.einsum("nch,nchd->nhd", b, op(v))


def attention(c, p, h, op, rows):
    """``h [T, d]`` (positions 0 .. T - 1, ``T`` whole blocks of ``rows``) ->
    ``[T, d]``."""
    T = h.shape[0]
    H, D, W, C = c["num_attention_heads"], c["head_dim"], c["window_size"], c["chunk_size"]
    proj = lambda w: jnp.einsum("td,dk->tk", op(h), op(f32(w))).reshape(T, H, D)  # noqa: E731
    pos = jnp.arange(T)
    q, k, v = rotate(c, proj(p["w_q"]), pos), rotate(c, proj(p["w_k"]), pos), proj(p["w_v"])
    kc, vc = compress(c, k, v, p["mu"], p["phi"], op)
    kpad = jnp.concatenate([k, jnp.zeros((W, H, D), k.dtype)])  # a block's slice never runs past the end
    vpad = jnp.concatenate([v, jnp.zeros((W, H, D), v.dtype)])

    def some_rows(start):
        t = start + jnp.arange(rows)  # inside one window block
        base = (start // W) * W
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows)
        kb, vb = jax.lax.dynamic_slice_in_dim(kpad, base, W), jax.lax.dynamic_slice_in_dim(vpad, base, W)
        exact = (base + jnp.arange(W))[None, :] <= t[:, None]  # [rows, W]
        early = jnp.broadcast_to(jnp.arange(kc.shape[0])[None, :] < base // C, (rows, kc.shape[0]))
        s = jnp.concatenate([jnp.einsum("thd,shd->hts", op(qb), op(kc)),
                             jnp.einsum("thd,shd->hts", op(qb), op(kb))], -1) * D ** -0.5
        prob = jax.nn.softmax(jnp.where(jnp.concatenate([early, exact], -1)[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hts,shd->thd", op(prob), op(jnp.concatenate([vc, vb])))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, H * D)
    return jnp.einsum("tk,kd->td", op(att), op(f32(p["w_o"])))


def mlp(p, h, op, rows):
    """In blocks of rows: the ``[T, intermediate]`` float32 arrays of a whole
    history would not fit beside two copies of the weights."""
    w_g, w_u, w_d = op(f32(p["w_g"])), op(f32(p["w_u"])), op(f32(p["w_d"]))

    def some_rows(hb):
        mid = jax.nn.silu(jnp.einsum("td,df->tf", op(hb), w_g)) * jnp.einsum("td,df->tf", op(hb), w_u)
        return jnp.einsum("tf,fd->td", op(mid), w_d)

    return jax.lax.map(some_rows, h.reshape(-1, rows, h.shape[1])).reshape(h.shape)


class Layers:
    """The model's parts as jitted programs, one layer each."""

    def __init__(self, c, precision, rows):
        op = lambda a: common.operand(a, precision)  # noqa: E731
        eps, V = c["rms_norm_eps"], c["vocab_size"]

        def run(p, x):
            x = x + attention(c, p["attn"], _norm(x, p["norm_1"], eps), op, rows)
            return x + mlp(p["mlp"], _norm(x, p["norm_2"], eps), op, rows)

        self.layer = jax.jit(run)
        self.embed = jax.jit(lambda e, t: f32(e[t]))
        self.head = jax.jit(lambda g, w, x, pos: jnp.einsum(
            "pd,dv->pv", op(_norm(x[pos], g, eps)), op(f32(w))).reshape(pos.shape[0], -1, V))

    def logits_at(self, params, tokens, positions):
        """``tokens [T]`` (padded at the end: causality keeps the padding out of
        every earlier position), ``positions [P]`` -> logits ``[P, heads, V]``
        of every prediction head."""
        x = self.embed(params["tok_emb"], tokens)
        for p in params["layers"]:
            x = self.layer(p, x)
        return self.head(params["norm_f"], params["head"], x, positions)


def run(config, seed, samples, precision="float32", rows=None, params=None, heads=1):
    """``samples``: a list of ``(history ids [n], positions [p])``. -> a list
    of float32 arrays ``[p, V]`` on the host (head 0, the next byte: what the
    served step decodes from; ``heads`` > 1: ``[p, heads, V]``), and the
    weights under ``"init"`` for the exact compare. One history at a time,
    each padded to the longest context the configuration serves (whole
    blocks of rows): one program a part, whatever the lengths."""
    rows = min(rows or ROWS, config["window_size"])
    longest = int(config["seq_len"])
    T = -(-longest // rows) * rows
    with jax.default_matmul_precision("highest"):
        # one program, as the engine's weights are made: the same fusions, so the same bits
        params = jax.jit(lambda: init(config, seed))() if params is None else params
        layers = Layers(config, precision, rows)
        out = []
        for hist, pos in samples:
            tok = np.zeros((T,), np.int32)
            tok[:len(hist)] = hist
            # positions padded to a power of two: few head programs for every width
            at = np.zeros((1 << (len(pos) - 1).bit_length(),), np.int32)
            at[:len(pos)] = pos
            got = np.asarray(layers.logits_at(params, jnp.asarray(tok), jnp.asarray(at)))[:len(pos)]
            out.append(got[:, 0] if heads == 1 else got[:, :heads])
    return {"logits": out, "init": params}
