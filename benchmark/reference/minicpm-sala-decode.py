"""Plain reference for ``minicpm-sala-decode``: the ``minicpm_sala`` layer
equations as the configuration file states them (ISSUE 35, "The block"), in
``jax.numpy``, float32 at ``highest`` matmul precision, over a request's whole
token history: no cache, no paging, no kernel, no batching. Imports nothing of
the program; ``init`` makes the same bfloat16 leaves from the seed, and the
arithmetic runs on them in float32.

    x = 12 emb[token]
    x += (1.4 / sqrt(32)) mixer(norm_1(x));  x += (1.4 / sqrt(32)) mlp(norm_2(x))
    logits = (norm_f(x) / 16) head            mlp(h) = (silu(h W_g) * (h W_u)) W_d

- ``lightning-attn``: ``q, k, v = h W_q, h W_k, h W_v`` in 32 heads of 128;
  ``q, k`` RMS-normed over each head, then rotated at their position (pairs
  ``(j, j + 64)``, frequencies ``10000^(-j / 64)``); per head the recurrence
  ``S_t = l S_(t-1) + k_t^T v_t``, ``o_t = 128^-0.5 q_t S_t``, ``l = exp(-2^(-8
  (h + 1) / 32))``, run in SMALL CHUNKS of 64 positions (inside a chunk the
  sum over ``j <= i`` of ``l^(i-j) (q_i . k_j) v_j`` written out with the
  powers as a masked matrix, the state carried from chunk to chunk;
  ``recurrence`` below is the same thing one position at a time, and
  ``tests/test_minicpm_sala.py`` holds the two together); then an RMS norm
  over all 4096, the sigmoid gate ``h W_gate``, ``W_o``.
- ``minicpm4``: ``q`` in 32 heads, ``k, v`` in 2, ``q, k`` RMS-normed, no
  rotary. ROW BY ROW, in blocks of query rows: (1) compressed keys ``c_j =
  mean(k[16j : 16j + 32])``, visible to the query at ``t`` where ``16j + 32 <=
  t + 1``; (2) per query head the softmax over them of ``q . c_j 128^-0.5``,
  summed over the 16 heads of a K/V head; (3) a block's score = the largest
  of those over the windows that overlap the block (an index table, made
  once); (4) visible: block 0, every block that overlaps ``t - 2047 .. t``,
  and the 64 best-scored of the rest (a stable sort: ties to the lower
  block); (5) one softmax over the visible positions ``<= t``, the gate,
  ``W_o``.

It computes LAYER BY LAYER (one jitted program a part) so that it fits beside
the program's weights.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402

ROWS = 256  # query rows a block of sparse attention: [heads, ROWS, T] scores
CHUNK = 64  # positions a chunk of the lightning recurrence
BF16 = jnp.bfloat16
SPARSE = "minicpm4"


def init(c, seed):
    """normal(0, init_std) matrices cast to bfloat16, gains ones; keys as the
    configuration's ``notes`` give them."""
    d, V, f, s = c["hidden_size"], c["vocab"], c["intermediate_size"], c["init_std"]
    H, G, D = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]

    def w(k, *shape):
        return (s * jax.random.normal(k, shape)).astype(BF16)

    ks = jax.random.split(jax.random.PRNGKey(seed), 2 + c["n_layers"])
    params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
              "norm_f": jnp.ones((d,), BF16), "layers": []}
    for kind, kl in zip(c["mixer_types"], ks[2:]):
        k = jax.random.split(kl, 8)
        kv = (G if kind == SPARSE else c["lightning_nkv"]) * D
        mixer = {"w_q": w(k[0], d, H * D), "w_k": w(k[1], d, kv), "w_v": w(k[2], d, kv),
                 "w_gate": w(k[3], d, H * D), "w_o": w(k[4], H * D, d),
                 "q_norm": jnp.ones((D,), BF16), "k_norm": jnp.ones((D,), BF16)}
        if kind != SPARSE:
            mixer["o_norm"] = jnp.ones((H * D,), BF16)
        params["layers"].append({
            "mixer": mixer, "norm_1": jnp.ones((d,), BF16), "norm_2": jnp.ones((d,), BF16),
            "mlp": {"w_g": w(k[5], d, f), "w_u": w(k[6], d, f), "w_d": w(k[7], f, d)},
        })
    return params


def f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def rotate(c, x, positions):
    """``[T, H, D]`` by ``positions [T]``: the pair ``(j, j + D/2)`` turned by
    ``positions * theta^(-j / (D/2))``."""
    half = x.shape[-1] // 2
    freq = jnp.asarray(c["rope_theta"] ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    ang = f32(positions)[:, None, None] * freq[None, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], -1)


def decays(c):
    H = c["lightning_nh"]
    return jnp.exp(-jnp.asarray(2.0 ** (-8.0 * (np.arange(H) + 1.0) / H), jnp.float32))


def recurrence(q, k, v, decay):
    """``S_t = l S_(t-1) + k_t^T v_t``, ``o_t = q_t S_t``, one position at a
    time. ``q, k, v [T, H, D]`` -> ``[T, H, D]``."""
    def one(S, x):
        q_t, k_t, v_t = x
        S = decay[:, None, None] * S + k_t[:, :, None] * v_t[:, None, :]
        return S, jnp.einsum("hd,hde->he", q_t, S)

    H, D = q.shape[1:]
    return jax.lax.scan(one, jnp.zeros((H, D, D), jnp.float32), (q, k, v))[1]


def recurrence_in_chunks(q, k, v, decay, op, chunk=CHUNK):
    """The same in chunks of ``chunk`` positions (``T`` a multiple of it)."""
    T, H, D = q.shape
    i = jnp.arange(chunk)
    log_l = jnp.log(decay)[:, None, None]
    power = jnp.where(i[:, None] >= i[None, :], jnp.exp(log_l * jnp.maximum(i[:, None] - i[None, :], 0)), 0.0)
    to_end = jnp.exp(log_l[:, :, 0] * (chunk - 1 - i)[None, :])  # [H, C]: l^(C-1-j)
    from_start = jnp.exp(log_l[:, :, 0] * (i + 1)[None, :])  # [H, C]: l^(i+1)

    def one(S, x):
        q_c, k_c, v_c = x  # [C, H, D]
        inside = jnp.einsum("hij,jhd->ihd", op(jnp.einsum("ihd,jhd->hij", op(q_c), op(k_c)) * power), op(v_c))
        before = jnp.einsum("ihd,hde->ihe", op(q_c * from_start.T[:, :, None]), S)
        S = decay[:, None, None] ** chunk * S + jnp.einsum(
            "jhd,jhe->hde", op(k_c * to_end.T[:, :, None]), op(v_c))
        return S, inside + before

    split = lambda a: a.reshape(T // chunk, chunk, H, D)  # noqa: E731
    return jax.lax.scan(one, jnp.zeros((H, D, D), jnp.float32), (split(q), split(k), split(v)))[1].reshape(T, H, D)


def lightning(c, p, h, op):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T = h.shape[0]
    H, D, eps = c["lightning_nh"], c["lightning_head_dim"], c["rms_norm_eps"]
    pos = jnp.arange(T)
    proj = lambda w: jnp.einsum("td,dk->tk", op(h), op(f32(w)))  # noqa: E731
    q = rotate(c, _norm(proj(p["w_q"]).reshape(T, H, D), p["q_norm"], eps), pos)
    k = rotate(c, _norm(proj(p["w_k"]).reshape(T, H, D), p["k_norm"], eps), pos)
    v = proj(p["w_v"]).reshape(T, H, D)
    o = recurrence_in_chunks(q, k, v, decays(c), op) * D ** -0.5
    o = _norm(o.reshape(T, H * D), p["o_norm"], eps) * jax.nn.sigmoid(proj(p["w_gate"]))
    return jnp.einsum("tk,kd->td", op(o), op(f32(p["w_o"])))


def windows_over_blocks(c, n_blocks, n_windows):
    """``[n_blocks, most]`` int: the compressed keys whose window ``[stride j,
    stride j + kernel)`` overlaps block ``b``; -1 where there are fewer."""
    sp = c["sparse_config"]
    K, st, B = sp["kernel_size"], sp["kernel_stride"], sp["block_size"]
    rows = [[j for j in range(n_windows) if st * j < B * (b + 1) and st * j + K > B * b] for b in range(n_blocks)]
    most = max(1, max(len(r) for r in rows))
    return np.asarray([r + [-1] * (most - len(r)) for r in rows], np.int32)


def sparse(c, p, h, op, rows=ROWS):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T = h.shape[0]
    H, G, D, eps = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"]
    sp = c["sparse_config"]
    K, st, B, topk = sp["kernel_size"], sp["kernel_stride"], sp["block_size"], sp["topk"]
    R, scale = H // G, D ** -0.5
    proj = lambda w: jnp.einsum("td,dk->tk", op(h), op(f32(w)))  # noqa: E731
    q = _norm(proj(p["w_q"]).reshape(T, G, R, D), p["q_norm"], eps)
    k = _norm(proj(p["w_k"]).reshape(T, G, D), p["k_norm"], eps)
    v = proj(p["w_v"]).reshape(T, G, D)
    gate = jax.nn.sigmoid(proj(p["w_gate"]))
    n_windows, n_blocks = (T - K) // st + 1, -(-T // B)
    ck = jnp.mean(k[st * jnp.arange(n_windows)[:, None] + jnp.arange(K)[None, :]], axis=1)  # [nW, G, D]
    table = jnp.asarray(windows_over_blocks(c, n_blocks, n_windows))
    rows = min(rows, T)
    if T % rows:
        raise ValueError(f"{T} positions are no whole blocks of {rows} rows")
    blocks, keys = jnp.arange(n_blocks), jnp.arange(T)

    def some_rows(start):
        t = start + jnp.arange(rows)  # the rows' positions
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)  # [rows, G, R, D]
        # (1)-(2): the compressed keys a row may use, the softmax over them a head, summed over a K/V head's heads
        usable = st * jnp.arange(n_windows)[None, :] + K <= t[:, None] + 1  # [rows, nW]
        s = jnp.einsum("tgrd,jgd->tgrj", qb, ck) * scale
        prob = jax.nn.softmax(jnp.where(usable[:, None, None, :], s, -jnp.inf), axis=-1)
        prob = jnp.where(usable[:, None, None, :], prob, 0.0)  # a row with no usable key: nought, not NaN
        P = jnp.sum(prob, axis=2)  # [rows, G, nW]
        # (3): a block's score, the largest P over the usable windows that overlap it
        over = jnp.where((table >= 0)[None, None] & jnp.take(usable, jnp.maximum(table, 0), axis=1)[:, None],
                         jnp.take(P, jnp.maximum(table, 0), axis=2), -1.0)  # [rows, G, nB, most]
        score = jnp.max(over, axis=-1)
        # (4): the visible blocks
        first_local = jnp.maximum(t - (sp["window_size"] - 1), 0) // B
        forced = (blocks[None, :] < sp["init_blocks"]) | (
            (blocks[None, :] >= first_local[:, None]) & (blocks[None, :] <= (t // B)[:, None]))  # [rows, nB]
        rest = (blocks[None, :] >= sp["init_blocks"]) & (blocks[None, :] < first_local[:, None])
        ranked = jnp.argsort(jnp.where(rest[:, None, :], -score, jnp.inf), axis=-1, stable=True)
        place = jnp.argsort(ranked, axis=-1)  # a block's place in the ranking
        visible = forced[:, None, :] | (rest[:, None, :] & (place < topk))  # [rows, G, nB]
        # (5): one softmax over the visible positions <= t
        seen = jnp.take(visible, keys // B, axis=2) & (keys[None, :] <= t[:, None])[:, None, :]  # [rows, G, T]
        s = jnp.einsum("tgrd,sgd->tgrs", op(qb), op(k)) * scale
        prob = jax.nn.softmax(jnp.where(seen[:, :, None, :], s, -jnp.inf), axis=-1)
        return jnp.einsum("tgrs,sgd->tgrd", op(prob), op(v))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, H * D)
    return jnp.einsum("tk,kd->td", op(att * gate), op(f32(p["w_o"])))


def mlp(p, h, op, rows=ROWS):
    """In blocks of rows: the ``[T, intermediate]`` float32 arrays of a whole
    history would not fit beside two copies of the weights."""
    w_g, w_u, w_d = op(f32(p["w_g"])), op(f32(p["w_u"])), op(f32(p["w_d"]))

    def some_rows(hb):
        mid = jax.nn.silu(jnp.einsum("td,df->tf", op(hb), w_g)) * jnp.einsum("td,df->tf", op(hb), w_u)
        return jnp.einsum("tf,fd->td", op(mid), w_d)

    rows = min(rows, h.shape[0])
    return jax.lax.map(some_rows, h.reshape(-1, rows, h.shape[1])).reshape(h.shape)


class Layers:
    """The model's parts as jitted programs, one layer each."""

    def __init__(self, c, precision):
        op = lambda a: common.operand(a, precision)  # noqa: E731
        eps = c["rms_norm_eps"]
        depth = c.get("published", {}).get("num_hidden_layers", c["num_hidden_layers"])
        step = c["scale_depth"] / math.sqrt(depth)

        def layer(mixer):
            def run(p, x):
                x = x + step * mixer(c, p["mixer"], _norm(x, p["norm_1"], eps), op)
                return x + step * mlp(p["mlp"], _norm(x, p["norm_2"], eps), op)
            return jax.jit(run)

        self.kinds = c["mixer_types"]
        self.layer = {SPARSE: layer(sparse), "lightning-attn": layer(lightning)}
        self.embed = jax.jit(lambda e, t: c["scale_emb"] * f32(e[t]))
        self.head = jax.jit(lambda g, w, x, pos: jnp.einsum(
            "pd,dv->pv", op(_norm(x[pos], g, eps) / (c["hidden_size"] / c["dim_model_base"])), op(f32(w))))

    def logits_at(self, params, tokens, positions):
        """``tokens [T]`` (padded at the end: causality keeps the padding out of
        every earlier position), ``positions [P]`` -> logits ``[P, V]``."""
        x = self.embed(params["tok_emb"], tokens)
        for kind, p in zip(self.kinds, params["layers"]):
            x = self.layer[kind](p, x)
        return self.head(params["norm_f"], params["head"], x, positions)


def run(config, seed, samples, precision="float32", rows=None, params=None):
    """``samples``: a list of ``(history ids [n], positions [p])``. -> a list
    of float32 arrays ``[p, V]`` on the host, and the weights under ``"init"``
    for the exact compare. One history at a time, each padded to the longest
    context the configuration serves (whole blocks of rows): one program a
    part, whatever the lengths."""
    del rows
    longest = int(config["seq_len"])
    whole = ROWS if longest > ROWS else CHUNK  # the tiny preset: short blocks
    T = -(-longest // whole) * whole
    with jax.default_matmul_precision("highest"):
        # one program, as the engine's weights are made: the same fusions, so the same bits
        params = jax.jit(lambda: init(config, seed))() if params is None else params
        layers = Layers(config, precision)
        out = []
        for hist, pos in samples:
            tok = np.zeros((T,), np.int32)
            tok[:len(hist)] = hist
            # positions padded to a power of two: few head programs for every width
            at = np.zeros((1 << (len(pos) - 1).bit_length(),), np.int32)
            at[:len(pos)] = pos
            got = layers.logits_at(params, jnp.asarray(tok), jnp.asarray(at))
            out.append(np.asarray(got)[:len(pos)])
    return {"logits": out, "init": params}
