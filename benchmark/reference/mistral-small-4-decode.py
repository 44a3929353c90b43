"""Plain reference for ``mistral-small-4-decode``: the ``mistral4`` layer
equations as the configuration file states them (ISSUE 33, "The block"), in
``jax.numpy``, float32 at ``highest`` matmul precision, over a request's whole
token history: full causal attention on MATERIALISED per-head K and V (the
expanded form only; nothing is absorbed into the latent space), no cache, no
paging, no kernel, no batching. Imports nothing of the program; ``init`` makes
the same bfloat16 leaves from the seed, and the arithmetic runs on them in
float32.

    x += attn(norm_1(x));  x += moe(norm_2(x));  logits = norm_f(x) head

- ``attn(h)``: ``c_q = norm(h W_dq)``; ``q = c_q W_uq`` (heads of ``nope +
  rope``); ``[c_kv | k_pe] = h W_dkv``; ``c_kv = norm(c_kv)``; ``[k_nope | v] =
  c_kv W_ukv``; ``q_rope`` and ``k_pe`` (one row, shared by the heads) rotated
  by position (yarn frequencies, pairs ``(2j, 2j + 1)``); the query at position
  ``p`` times ``1 + beta ln(1 + floor(p / original context))``; ``softmax(q k^T
  s, causal) v`` with ``s = (nope + rope)^-0.5 m^2``, ``m = 0.1 ln(factor) +
  1``; then ``W_o``.
- ``moe(h)``: the shared SwiGLU expert plus, over the 4 experts a softmax over
  ALL 128 router logits puts first, ``w_j swiglu_j(h)`` with the chosen scores
  divided by their sum: only for the experts held here. What the absent ones
  would add is left out, here as in the program.

It computes LAYER BY LAYER (one jitted program a part), attention in blocks
of query rows, and the routed experts one at a time over the rows that chose
them: the rows of an expert are gathered to a fixed number (the largest count
of any held expert in that layer, read on the host and rounded up to a power
of two, so nothing is cut off and few programs compile) and their results are
added back where they came from. So it fits beside the program's weights and
costs about what the needed arithmetic costs, not 16 experts on every row.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402

ROWS = 256  # query rows a block of attention: [heads, ROWS, T] scores beside two copies of the weights
BF16 = jnp.bfloat16


def init(c, seed):
    """normal(0, init_std) matrices cast to bfloat16, gains ones; keys as the
    configuration's ``notes`` give them."""
    d, H, V = c["hidden_size"], c["num_attention_heads"], c["vocab"]
    G, E, fe, s = c["num_experts_held"], c["n_routed_experts"], c["moe_intermediate_size"], c["init_std"]
    ql, kl, nope, rope, vh = (c["q_lora_rank"], c["kv_lora_rank"], c["qk_nope_head_dim"],
                              c["qk_rope_head_dim"], c["v_head_dim"])

    def w(k, *shape):
        return (s * jax.random.normal(k, shape)).astype(BF16)

    def swiglu(k1, k3, k2, *lead):
        return {"w1": w(k1, *lead, d, fe), "w3": w(k3, *lead, d, fe), "w2": w(k2, *lead, fe, d)}

    ks = jax.random.split(jax.random.PRNGKey(seed), 2 + c["n_layers"])
    params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
              "norm_f": jnp.ones((d,), BF16), "layers": []}
    for kl_ in ks[2:]:
        k = jax.random.split(kl_, 12)
        params["layers"].append({
            "attn": {"w_dq": w(k[0], d, ql), "q_norm": jnp.ones((ql,), BF16),
                     "w_uq": w(k[1], ql, H, nope + rope), "w_dkv": w(k[2], d, kl + rope),
                     "kv_norm": jnp.ones((kl,), BF16), "w_ukv": w(k[3], kl, H, nope + vh),
                     "w_o": w(k[4], H, vh, d)},
            "norm_1": jnp.ones((d,), BF16), "norm_2": jnp.ones((d,), BF16),
            "ffn": {"router": w(k[5], d, E), "shared": swiglu(k[6], k[7], k[8]),
                    "experts": swiglu(k[9], k[10], k[11], G)},
        })
    return params


def f32(a):
    return a.astype(jnp.float32)


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * f32(g)


def yarn_frequencies(c):
    """-> (frequencies ``[rope / 2]``, low, high)."""
    rp, D = c["rope_parameters"], c["qk_rope_head_dim"]
    theta, orig = rp["rope_theta"], rp["original_max_position_embeddings"]

    def dim_of(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), D - 1)
    j = np.arange(D // 2, dtype=np.float64)
    t = theta ** (-2.0 * j / D)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(t * (1 - ramp) + t / rp["factor"] * ramp, jnp.float32), low, high


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(c):
    rp = c["rope_parameters"]
    return (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5 * _mscale(
        rp["factor"], rp["mscale_all_dim"]) ** 2


def rotate(c, x, positions):
    """``[T, (H,) D]`` by ``positions [T]``: the pair ``(2j, 2j + 1)`` turned
    by ``positions * frequency_j``."""
    rp = c["rope_parameters"]
    ang = f32(positions)[:, None] * yarn_frequencies(c)[0][None, :]
    amp = _mscale(rp["factor"], rp["mscale"]) / _mscale(rp["factor"], rp["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def attention(c, p, h, op, rows=ROWS):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T = h.shape[0]
    kl, nope, eps = c["kv_lora_rank"], c["qk_nope_head_dim"], c["rms_norm_eps"]
    rp = c["rope_parameters"]
    pos = jnp.arange(T)
    c_q = _norm(jnp.einsum("td,dr->tr", op(h), op(f32(p["w_dq"]))), p["q_norm"], eps)
    q = jnp.einsum("tr,rhk->thk", op(c_q), op(f32(p["w_uq"])))
    kv = jnp.einsum("td,dr->tr", op(h), op(f32(p["w_dkv"])))
    c_kv = _norm(kv[:, :kl], p["kv_norm"], eps)
    k_pe = rotate(c, kv[:, kl:], pos)
    kv = jnp.einsum("tr,rhk->thk", op(c_kv), op(f32(p["w_ukv"])))
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (T, kv.shape[1], k_pe.shape[-1]))], -1)
    v = kv[..., nope:]
    by_pos = 1.0 + rp["llama_4_scaling_beta"] * jnp.log1p(
        jnp.floor(f32(pos) / rp["original_max_position_embeddings"]))
    q = jnp.concatenate([q[..., :nope], rotate(c, q[..., nope:], pos)], -1) * by_pos[:, None, None]
    rows = min(rows, T)
    if T % rows:
        raise ValueError(f"{T} positions are no whole blocks of {rows} rows")
    scale = softmax_scale(c)

    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        s = jnp.einsum("qhk,thk->hqt", op(qb), op(k)) * scale
        seen = jnp.arange(T)[None, :] <= (start + jnp.arange(rows))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", op(prob), op(v))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, *v.shape[1:])
    return jnp.einsum("thv,hvd->td", op(att), op(f32(p["w_o"])))


def swiglu(w1, w3, w2, h, op):
    mid = jax.nn.silu(jnp.einsum("td,df->tf", op(h), op(f32(w1)))) * jnp.einsum("td,df->tf", op(h), op(f32(w3)))
    return jnp.einsum("tf,fd->td", op(mid), op(f32(w2)))


def route(c, p, h):
    """-> the weight of every expert for every row ``[T, E]`` (nought where
    the row did not choose it); the router computes in fp32 always."""
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    score = jax.nn.softmax(jnp.einsum("td,de->te", h, f32(p["router"])), axis=-1)
    chosen, idx = jax.lax.top_k(score, k)
    weight = chosen / jnp.sum(chosen, -1, keepdims=True) * c["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, E) * weight[..., None], axis=-2)


def routed(c, p, h, per_expert, op, cap, first=None):
    """The held experts' part: expert ``e`` over the (at most ``cap``) rows
    whose weight for it is not nought, added back where they came from."""
    first = c.get("first_expert_held", 0) if first is None else first
    T = h.shape[0]
    ex = p["experts"]

    def one(out, xs):
        e, w1, w3, w2 = xs
        mine = jnp.take(per_expert, first + e, axis=-1)  # [T]
        rows = jnp.nonzero(mine > 0, size=cap, fill_value=T)[0]
        got = swiglu(w1, w3, w2, jnp.take(h, rows, axis=0, mode="fill", fill_value=0), op)
        weight = jnp.take(mine, rows, mode="fill", fill_value=0)
        return out.at[rows].add(got * weight[:, None], mode="drop"), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(ex["w1"].shape[0]), ex["w1"], ex["w3"], ex["w2"]))
    return out


class Layers:
    """The model's parts as jitted programs, one layer (or less) each."""

    def __init__(self, c, precision):
        self.c = c
        op = lambda a: common.operand(a, precision)  # noqa: E731
        eps = c["rms_norm_eps"]
        first = c.get("first_expert_held", 0)
        held = slice(first, first + c["num_experts_held"])

        def attn_part(p, x):
            x = x + attention(c, p["attn"], _norm(x, p["norm_1"], eps), op)
            h = _norm(x, p["norm_2"], eps)
            per_expert = route(c, p["ffn"], h)
            return x, h, per_expert, jnp.max(jnp.sum(per_expert[:, held] > 0, axis=0))

        def ffn_part(p, x, h, per_expert, cap):
            sh = p["ffn"]["shared"]
            return x + swiglu(sh["w1"], sh["w3"], sh["w2"], h, op) + routed(c, p["ffn"], h, per_expert, op, cap)

        self.attn_part = jax.jit(attn_part)
        self.ffn_part = jax.jit(ffn_part, static_argnames="cap")
        self.embed = jax.jit(lambda e, t: f32(e[t]))
        self.head = jax.jit(lambda g, w, x, pos: jnp.einsum(
            "pd,dv->pv", op(_norm(x[pos], g, eps)), op(f32(w))))

    def logits_at(self, params, tokens, positions):
        """``tokens [T]`` (padded at the end: causality keeps the padding out of
        every earlier position), ``positions [P]`` -> logits ``[P, V]``."""
        x = self.embed(params["tok_emb"], tokens)
        for p in params["layers"]:
            x, h, per_expert, most = self.attn_part(p, x)
            cap = max(16, 1 << (int(most) - 1).bit_length())  # a power of two that holds every row
            x = self.ffn_part(p, x, h, per_expert, cap=min(cap, x.shape[0]))
        return self.head(params["norm_f"], params["head"], x, positions)


def run(config, seed, samples, precision="float32", rows=None, params=None):
    """``samples``: a list of ``(history ids [n], positions [p])``. -> a list
    of float32 arrays ``[p, V]`` on the host, and the weights under ``"init"``
    for the exact compare. One history at a time, each padded to the longest
    context the configuration serves (whole attention blocks): one program a
    part, whatever the lengths."""
    del rows
    longest = int(config["seq_len"])
    whole = ROWS if longest > ROWS else 64  # the tiny preset: one short block
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
