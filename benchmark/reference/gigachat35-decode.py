"""Plain reference for ``gigachat35-decode``: the ``gigachat3_5`` layer
equations as the configuration file states them, in ``jax.numpy``, float32 at
``highest`` matmul precision, over a request's whole token history: no cache,
no paging, no kernel, no batching, no chunk. Imports nothing of the program;
``init`` makes the same leaves from the seed, and the arithmetic runs on them
in float32.

    x = emb[token]
    x += norm(mixer(norm(x)));  x += norm(mlp(norm(x)))    norm(x) = x / sqrt(mean(x^2) + eps) * 2 sigma(w)
    logits = norm(x) head

Published layer ``i`` is latent attention where ``i`` is in
``full_attention_layers``, a gated DeltaNet otherwise; its MLP is a dense
SwiGLU where ``i < first_k_dense_replace``, routed experts otherwise.

- gated DeltaNet: ``q, k, v = silu(conv(h [W_q | W_k | W_v]))`` with ``conv``
  the causal depthwise convolution of 4 taps, no bias (noughts before
  position 0); ``q, k`` L2-normed a key head, ``q`` times ``128^-1/2``; value
  head ``h`` reads key head ``h // 2``; ``beta = sigma(h W_b)``, ``g =
  -exp(A_log) softplus(h W_a + dt_bias)``; ONE POSITION AT A TIME
  (``fori_loop``) ``S = e^g S (I - beta k k^T) + beta v k^T``, ``o = S q``,
  with ``S [128 x 128]`` a value head; ``y = rms(o) (1 + w) 2 sigma(h W_z)``;
  ``y W_out``.
- latent attention, EXPANDED: ``c_q = rms(h W_dq)``, ``q = c_q W_uq`` (64
  heads of 128 + 64); ``[c_kv | k_pe] = h W_dkv``, ``c_kv = rms(c_kv)``, ``[k |
  v] = c_kv W_ukv`` (128 + 128 a head); the rotary parts rotated by position
  (yarn, pairs ``(2j, 2j + 1)``); ``softmax(q k^T s, causal) v`` with ``s =
  192^-1/2 m^2``, ``m = 0.1 ln 8 + 1``, in blocks of query rows; times
  ``sigma(h W_g)`` a head; ``W_o``.
- routed MLP: ``sigma(h W_r)`` over all 256 experts, the 8 best by score plus
  the selection bias, the chosen scores over their sum times 2.5; each chosen
  expert HELD HERE ``w_j swiglu_j(h)`` (what the absent ones would add is left
  out, here as in the program), plus the shared expert; ``swiglu(h) =
  (silu(min(h W_1, 10)) clip(h W_3, -10, 10)) W_2``, the dense layer's too.

Departures from the published model: the two multi-token prediction modules
are left out (a server that does not speculate never runs them); the
configuration's ``assumed`` gives every reading of a key the config leaves
open. It computes LAYER BY LAYER (one jitted program a kind of sublayer), the
projections in blocks of output columns and the routed experts one at a time
over the rows that chose them, so that it fits beside the program's weights;
the recurrence runs to the history's real length only; a history that is a
prefix of another is not run again (causality makes its rows the same).
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402

BLOCK = 1 << 24  # elements of a weight's float32 block: 64 MB
QUERY_ROWS = 128  # query rows a block of attention: [64, 128, T] scores
BF16 = jnp.bfloat16


def kinds(c):
    """-> [(full attention?, dense MLP?)] of the layers held, in order."""
    return [(i in c["full_attention_layers"], i < c["first_k_dense_replace"]) for i in c["layer_ids"]]


def init(c, seed):
    """normal(0, init_std) matrices and taps cast to bfloat16, the selection
    bias normal(0, init_std) in float32; zero-centred gains noughts, the
    latent attention's two inner gains ones; ``A_log = log((h + 1) / 4)``,
    ``dt_bias`` ones; keys as the configuration's ``notes`` give them."""
    d, V, s = c["hidden_size"], c["vocab"], c["init_std"]
    Hk, H, D = c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"]
    Ha, nope, rope, vh = c["num_attention_heads"], c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    E, G, fe = c["n_routed_experts"], c["num_experts_held"], c["moe_intermediate_size"]

    def w(k, *shape):
        return (s * jax.random.normal(k, shape)).astype(BF16)

    def swiglu(k1, k3, k2, width, *lead):
        return {"w1": w(k1, *lead, d, width), "w3": w(k3, *lead, d, width), "w2": w(k2, *lead, width, d)}

    ks = jax.random.split(jax.random.PRNGKey(seed), 2 + len(c["layer_ids"]))
    params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V), "norm_f": jnp.zeros((d,), BF16), "layers": []}
    for (full, dense), kl in zip(kinds(c), ks[2:]):
        k = jax.random.split(kl, 16)
        if full:
            mixer = {"w_dq": w(k[0], d, c["q_lora_rank"]), "q_norm": jnp.ones((c["q_lora_rank"],), BF16),
                     "w_uq": w(k[1], c["q_lora_rank"], Ha, nope + rope),
                     "w_dkv": w(k[2], d, c["kv_lora_rank"] + rope), "kv_norm": jnp.ones((c["kv_lora_rank"],), BF16),
                     "w_ukv": w(k[3], c["kv_lora_rank"], Ha, nope + vh), "w_o": w(k[4], Ha, vh, d),
                     "w_g": w(k[5], d, Ha * vh)}
        else:
            mixer = {"w_q": w(k[0], d, Hk * D), "w_k": w(k[1], d, Hk * D), "w_v": w(k[2], d, H * D),
                     "w_z": w(k[3], d, H * D), "w_b": w(k[4], d, H), "w_a": w(k[5], d, H),
                     "conv_w": w(k[6], c["linear_conv_kernel_dim"], (2 * Hk + H) * D),
                     "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32) / 4.0).astype(BF16),
                     "dt_bias": jnp.ones((H,), BF16), "o_norm": jnp.zeros((D,), BF16), "w_o": w(k[7], H * D, d)}
        if dense:
            mlp = swiglu(k[8], k[9], k[10], c["intermediate_size"])
        else:
            mlp = {"router": w(k[8], d, E), "bias": s * jax.random.normal(k[9], (E,), jnp.float32),
                   "shared": swiglu(k[10], k[11], k[12], fe), "experts": swiglu(k[13], k[14], k[15], fe, G)}
        params["layers"].append({"mixer": mixer, "mlp": mlp, **{
            n: jnp.zeros((d,), BF16) for n in ("norm_1", "norm_1p", "norm_2", "norm_2p")}})
    return params


def f32(a):
    return a.astype(jnp.float32)


def _norm(x, w, eps):
    """The zero-centred gain ``2 sigma(w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (2.0 * jax.nn.sigmoid(f32(w)))


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def project(h, w, op):
    """``h [T, n] @ w [n, m]`` in blocks of ``w``'s columns, each made float32
    on its own."""
    n, m = w.shape
    cols = max(c_ for c_ in range(1, min(m, max(1, BLOCK // n)) + 1) if m % c_ == 0)
    blocks = jnp.swapaxes(w.reshape(n, m // cols, cols), 0, 1)
    out = jax.lax.map(lambda wb: jnp.einsum("tn,nm->tm", op(h), op(f32(wb))), blocks)
    return jnp.swapaxes(out, 0, 1).reshape(h.shape[0], m)


def swiglu(p, h, op, limit):
    mid = jax.nn.silu(jnp.minimum(project(h, p["w1"], op), limit)) * jnp.clip(project(h, p["w3"], op), -limit, limit)
    return project(mid, p["w2"], op)


# -- latent attention, expanded ------------------------------------------------
def yarn_frequencies(c):
    rp, D = c["rope_scaling"], c["qk_rope_head_dim"]
    theta, orig = c["rope_theta"], rp["original_max_position_embeddings"]

    def dim_of(rotations):
        return D * math.log(orig / (rotations * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dim_of(rp["beta_fast"])), 0)
    high = min(math.ceil(dim_of(rp["beta_slow"])), D - 1)
    j = np.arange(D // 2, dtype=np.float64)
    t = theta ** (-2.0 * j / D)
    ramp = np.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return jnp.asarray(t * (1 - ramp) + t / rp["factor"] * ramp, jnp.float32)


def _mscale(factor, m):
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(c):
    """``qk_head_dim^-0.5 m^2``, ``m = 0.1 mscale_all_dim ln(factor) + 1``
    (``use_mla_scaling_factor``)."""
    rp = c["rope_scaling"]
    return c["qk_head_dim"] ** -0.5 * _mscale(rp["factor"], rp["mscale_all_dim"]) ** 2


def rotate(c, x, positions):
    """``[T, (H,) D]`` by ``positions [T]``: pair ``(2j, 2j + 1)`` turned by
    ``positions * frequency_j`` (``rope_interleave``)."""
    rp = c["rope_scaling"]
    ang = f32(positions)[:, None] * yarn_frequencies(c)[None, :]
    amp = _mscale(rp["factor"], rp["mscale"]) / _mscale(rp["factor"], rp["mscale_all_dim"])
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if x.ndim == 3:
        cos, sin = cos[:, None, :], sin[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def attention(c, p, h, op):
    """``h [T, d]`` (positions 0 .. T - 1) -> ``[T, d]``."""
    T = h.shape[0]
    H, kl, nope, eps = c["num_attention_heads"], c["kv_lora_rank"], c["qk_nope_head_dim"], c["rms_norm_eps"]
    pos = jnp.arange(T)
    c_q = _rms(project(h, p["w_dq"], op), f32(p["q_norm"]), eps)
    q = project(c_q, p["w_uq"].reshape(c["q_lora_rank"], -1), op).reshape(T, H, -1)
    kv = project(h, p["w_dkv"], op)
    c_kv = _rms(kv[:, :kl], f32(p["kv_norm"]), eps)
    k_pe = rotate(c, kv[:, kl:], pos)
    kv = project(c_kv, p["w_ukv"].reshape(kl, -1), op).reshape(T, H, -1)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe[:, None, :], (T, H, k_pe.shape[-1]))], -1)
    v = kv[..., nope:]
    q = jnp.concatenate([q[..., :nope], rotate(c, q[..., nope:], pos)], -1)
    rows, scale = min(QUERY_ROWS, T), softmax_scale(c)

    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=0)
        s = jnp.einsum("qhk,thk->hqt", op(qb), op(k)) * scale
        seen = jnp.arange(T)[None, :] <= (start + jnp.arange(rows))[:, None]
        prob = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqt,thv->qhv", op(prob), op(v))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows)).reshape(T, H, -1)
    gate = jax.nn.sigmoid(project(h, p["w_g"], op)).reshape(att.shape)
    return project((att * gate).reshape(T, -1), p["w_o"].reshape(-1, h.shape[1]), op)


# -- gated DeltaNet, one position at a time ---------------------------------------
def delta_rule(q, k, v, beta, g, n):
    """``S_t = e^g S (I - beta k k^T) + beta v k^T``, ``o_t = S_t q_t``, for
    positions ``0 .. n - 1`` from ``S = 0``: ``q, k [T, H, D]`` (per value
    head), ``v [T, H, D]``, ``beta, g [T, H]`` -> ``o [T, H, D]`` (noughts past
    ``n``)."""
    H, D = v.shape[1:]

    def one(t, carry):
        S, o = carry
        q_t, k_t, v_t, b_t, g_t = (jax.lax.dynamic_index_in_dim(a, t, keepdims=False) for a in (q, k, v, beta, g))
        S = jnp.exp(g_t)[:, None, None] * S
        S = S - b_t[:, None, None] * jnp.einsum("hvk,hk->hv", S, k_t)[:, :, None] * k_t[:, None, :]
        S = S + b_t[:, None, None] * v_t[:, :, None] * k_t[:, None, :]
        return S, jax.lax.dynamic_update_index_in_dim(o, jnp.einsum("hvk,hk->hv", S, q_t), t, 0)

    _, o = jax.lax.fori_loop(0, n, one, (jnp.zeros((H, D, D), jnp.float32), jnp.zeros(v.shape, jnp.float32)))
    return o


def gated_deltanet(c, p, h, n, op):
    """``h [T, d]`` (positions 0 .. T - 1, ``n`` of them real) -> ``[T, d]``."""
    T = h.shape[0]
    Hk, H, D, K = (c["linear_num_key_heads"], c["linear_num_value_heads"], c["linear_key_head_dim"],
                   c["linear_conv_kernel_dim"])
    pre = jnp.concatenate([project(h, p[n_], op) for n_ in ("w_q", "w_k", "w_v")], -1)
    padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[1]), jnp.float32), pre])
    x = jax.nn.silu(sum(padded[j:j + T] * f32(p["conv_w"][j]) for j in range(K)))

    def l2(a):
        return a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

    q = jnp.repeat(l2(x[:, :Hk * D].reshape(T, Hk, D)) * D ** -0.5, H // Hk, axis=1)
    k = jnp.repeat(l2(x[:, Hk * D:2 * Hk * D].reshape(T, Hk, D)), H // Hk, axis=1)
    v = x[:, 2 * Hk * D:].reshape(T, H, D)
    beta = jax.nn.sigmoid(project(h, p["w_b"], op))
    g = -jnp.exp(f32(p["A_log"])) * jax.nn.softplus(project(h, p["w_a"], op) + f32(p["dt_bias"]))
    o = delta_rule(q, k, v, beta, g, n)
    z = project(h, p["w_z"], op).reshape(T, H, D)
    y = _rms(o, 1.0 + f32(p["o_norm"]), c["linear_attn_o_norm_eps"]) * 2.0 * jax.nn.sigmoid(z)
    return project(y.reshape(T, H * D), p["w_o"], op)


# -- routed experts -----------------------------------------------------------------
def route(c, p, h):
    """-> the weight of every expert for every row ``[T, E]`` (nought where
    the row did not choose it); the router computes in fp32 always."""
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.einsum("td,de->te", h, f32(p["router"])))
    _, idx = jax.lax.top_k(score + p["bias"], k)
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    weight = chosen / jnp.sum(chosen, -1, keepdims=True) * c["routed_scaling_factor"]
    return jnp.sum(jax.nn.one_hot(idx, E) * weight[..., None], axis=-2)


def routed(c, p, h, per_expert, op, cap):
    """The held experts' part: expert ``e`` over the (at most ``cap``) rows
    whose weight for it is not nought, added back where they came from."""
    first, T, ex = c.get("first_expert_held", 0), h.shape[0], p["experts"]
    limit = c["swiglu_limit"]

    def one(out, xs):
        e, w1, w3, w2 = xs
        mine = jnp.take(per_expert, first + e, axis=-1)
        rows = jnp.nonzero(mine > 0, size=cap, fill_value=T)[0]
        got = swiglu({"w1": w1, "w3": w3, "w2": w2}, jnp.take(h, rows, axis=0, mode="fill", fill_value=0), op, limit)
        weight = jnp.take(mine, rows, mode="fill", fill_value=0)
        return out.at[rows].add(got * weight[:, None], mode="drop"), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h), (jnp.arange(ex["w1"].shape[0]), ex["w1"], ex["w3"], ex["w2"]))
    return out


class Layers:
    """The model's parts as jitted programs, one a kind of sublayer."""

    def __init__(self, c, precision):
        self.c = c
        op = lambda a: common.operand(a, precision)  # noqa: E731
        eps, limit = c["rms_norm_eps"], c["swiglu_limit"]
        first, held = c.get("first_expert_held", 0), c["num_experts_held"]

        def mixer(full):
            def run(p, x, n):
                h = _norm(x, p["norm_1"], eps)
                a = attention(c, p["mixer"], h, op) if full else gated_deltanet(c, p["mixer"], h, n, op)
                return x + _norm(a, p["norm_1p"], eps)
            return jax.jit(run)

        def dense(p, x):
            return x + _norm(swiglu(p["mlp"], _norm(x, p["norm_2"], eps), op, limit), p["norm_2p"], eps)

        def routing(p, x):
            h = _norm(x, p["norm_2"], eps)
            per_expert = route(c, p["mlp"], h)
            return h, per_expert, jnp.max(jnp.sum(per_expert[:, first:first + held] > 0, axis=0))

        def experts(p, x, h, per_expert, cap):
            f = swiglu(p["mlp"]["shared"], h, op, limit) + routed(c, p["mlp"], h, per_expert, op, cap)
            return x + _norm(f, p["norm_2p"], eps)

        self.mixer = {True: mixer(True), False: mixer(False)}
        self.dense, self.routing = jax.jit(dense), jax.jit(routing)
        self.experts = jax.jit(experts, static_argnames="cap")
        self.embed = jax.jit(lambda e, t: f32(e[t]))
        self.head = jax.jit(lambda g, w, x, pos: project(_norm(x[pos], g, eps), w, op))

    def logits_at(self, params, tokens, n, positions):
        """``tokens [T]`` (``n`` real, padded at the end: causality keeps the
        padding out of every earlier position), ``positions [P]`` -> logits
        ``[P, V]``."""
        x = self.embed(params["tok_emb"], tokens)
        for (full, dense), p in zip(kinds(self.c), params["layers"]):
            x = self.mixer[full](p, x, n)
            if dense:
                x = self.dense(p, x)
            else:
                h, per_expert, most = self.routing(p, x)
                cap = max(16, 1 << (int(most) - 1).bit_length())  # a power of two that holds every row
                x = self.experts(p, x, h, per_expert, cap=min(cap, x.shape[0]))
        return self.head(params["norm_f"], params["head"], x, positions)


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
    context the configuration serves (whole blocks of query rows): one
    program a kind of sublayer, whatever the lengths."""
    del rows
    T = -(-int(config["seq_len"]) // QUERY_ROWS) * QUERY_ROWS
    hold = _longest_holders(samples)
    with jax.default_matmul_precision("highest"):
        # one program, as the engine's weights are made: the same fusions, so the same bits
        params = jax.jit(lambda: init(config, seed))() if params is None else params
        layers = Layers(config, precision)
        out = [None] * len(samples)
        for root in sorted(set(hold)):
            mine = [i for i, h in enumerate(hold) if h == root]
            wanted = np.concatenate([samples[i][1] for i in mine]).astype(np.int32)
            hist = samples[root][0]
            tok = np.zeros((T,), np.int32)
            tok[:len(hist)] = hist
            # positions padded to a power of two: few head programs for every width
            at = np.zeros((1 << (len(wanted) - 1).bit_length(),), np.int32)
            at[:len(wanted)] = wanted
            got = np.asarray(layers.logits_at(params, jnp.asarray(tok), jnp.int32(len(hist)), jnp.asarray(at)))
            start = 0
            for i in mine:
                n = len(samples[i][1])
                out[i] = got[start:start + n]
                start += n
    return {"logits": out, "init": params}
