"""Plain reference for ``trinity-mini``: the ``afmoe`` layer equations as the
configuration file states them (ISSUE 28, "The layer"), in ``jax.numpy`` and
float32 at ``highest`` precision. No kernel, no sort: attention is a masked
softmax over all keys, computed in blocks of query rows so that the scores
fit; the routed FFN is a loop over the experts held here, each run on every
token and weighed by that token's routing weight for it (nought where the
token did not choose it). Imports nothing of the program; makes its own
weights from the seed; carries the routers' selection bias through its steps.

Sized to fit after the program's state is gone: 2.8 GB of parameters and
twice that of Adam's moments stay on the device; the gradient is made one
layer at a time (:class:`LayerwiseGrad`: each layer recomputed from its kept
input in the backward pass, the same mathematics as the whole model's
``jax.grad``), and each layer's gradient moves its parameters at once, in
place, and is dropped (as one whole program the gradient took 15.9 GB).

What is left out, here as in the program: the experts this chip does not
hold. A token's weights are normalised over all 8 of its choices; the sum
runs over the chosen experts that are held.
"""

import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402


def init(c, seed):
    """normal(0, init_std) matrices, gains ones; keys as the configuration's
    ``notes`` give them."""
    d, H, Hk, hd = c["hidden_size"], c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    V, E, G = c["vocab"], c["num_experts"], c["num_experts_held"]
    f, fe, s = c["intermediate_size"], c["moe_intermediate_size"], c["init_std"]

    def w(k, *shape):
        return s * jax.random.normal(k, shape)

    def swiglu(k1, k3, k2, *lead, width):
        return {"w1": w(k1, *lead, d, width), "w3": w(k3, *lead, d, width),
                "w2": w(k2, *lead, width, d)}

    kinds = c["layers_run"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 2 + len(kinds))
    params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
              "final_norm": jnp.ones((d,)), "layers": []}
    for (_, _, ffn), kl in zip(kinds, ks[2:]):
        k = jax.random.split(kl, 12)
        layer = {
            "attn": {"wq": w(k[0], d, H, hd), "wk": w(k[1], d, Hk, hd),
                     "wv": w(k[2], d, Hk, hd), "wg": w(k[3], d, H, hd),
                     "wo": w(k[4], H, hd, d),
                     "q_norm": jnp.ones((hd,)), "k_norm": jnp.ones((hd,))},
            "norm_in": jnp.ones((d,)), "norm_post_attn": jnp.ones((d,)),
            "norm_pre_mlp": jnp.ones((d,)), "norm_post_mlp": jnp.ones((d,)),
        }
        if ffn == "dense":
            layer["ffn"] = swiglu(k[5], k[6], k[7], width=f)
        else:
            layer["ffn"] = {"router": w(k[5], d, E),
                            "shared": swiglu(k[6], k[7], k[8], width=fe),
                            "experts": swiglu(k[9], k[10], k[11], G, width=fe)}
        params["layers"].append(layer)
    return params


def _norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rotary(x, theta):
    """[B, T, H, D]: element i of a head paired with element i + D/2."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = jnp.arange(T)[:, None] * theta ** (-jnp.arange(half) / half)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(c, p, h, window, positions, op, block):
    """Gated causal attention; query head i reads K/V head i // (H / Hk);
    with a window, query t sees keys s with t - window < s <= t."""
    B, T, _ = h.shape
    Hk, hd, eps = c["num_key_value_heads"], c["head_dim"], c["rms_norm_eps"]
    q = jnp.einsum("btd,dhk->bthk", op(h), op(p["wq"]))
    k = jnp.einsum("btd,dhk->bthk", op(h), op(p["wk"]))
    v = jnp.einsum("btd,dhk->bthk", op(h), op(p["wv"]))
    gate = jnp.einsum("btd,dhk->bthk", op(h), op(p["wg"]))
    q, k = _norm(q, p["q_norm"], eps), _norm(k, p["k_norm"], eps)
    if positions == "rotary":
        q, k = _rotary(q, c["rope_theta"]), _rotary(k, c["rope_theta"])
    q = q.reshape(B, T, Hk, -1, hd)  # [B, T, K/V head, query head of its group, hd]
    rows = min(block, T)
    if T % rows:
        raise ValueError(f"{T} positions are no whole blocks of {rows} rows")

    @jax.checkpoint
    def some_rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, rows, axis=1)
        s = jnp.einsum("bqgrk,btgk->bgrqt", op(qb), op(k)) / math.sqrt(hd)
        t, pos = jnp.arange(T)[None, :], (start + jnp.arange(rows))[:, None]
        seen = t <= pos
        if window is not None:
            seen &= pos - t < window
        prob = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bgrqt,btgk->bqgrk", op(prob), op(v))

    att = jax.lax.map(some_rows, jnp.arange(0, T, rows))  # [blocks, B, rows, Hk, r, hd]
    att = jnp.moveaxis(att, 0, 1).reshape(B, T, -1, hd)
    return jnp.einsum("bthk,hkd->btd", op(att * jax.nn.sigmoid(gate)), op(p["wo"]))


def swiglu(p, h, op):
    mid = jax.nn.silu(jnp.einsum("...d,df->...f", op(h), op(p["w1"]))) \
        * jnp.einsum("...d,df->...f", op(h), op(p["w3"]))
    return jnp.einsum("...f,fd->...d", op(mid), op(p["w2"]))


def routed_ffn(c, p, h, bias, op, first=None, shared=True):
    """-> (FFN output, token counts over ALL experts). ``p["experts"]`` are
    the experts held, ``first`` .. of all ``num_experts`` (the
    configuration's share unless given: the shares-add-up test gives
    others); ``shared=False`` leaves the shared expert out."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    first = c.get("first_expert_held", 0) if first is None else first
    score = jax.nn.sigmoid(jnp.einsum("...d,de->...e", h, p["router"]))  # fp32 always
    _, idx = jax.lax.top_k(score + bias, k)  # the bias selects only
    chosen = jnp.take_along_axis(score, idx, axis=-1)
    weight = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20) * c["route_scale"]
    picked = jax.nn.one_hot(idx, E)  # [..., k, E]
    per_expert = jnp.sum(picked * weight[..., None], axis=-2)  # [..., E]
    ex = p["experts"]
    out = swiglu(p["shared"], h, op) if shared else jnp.zeros_like(h)

    @jax.checkpoint  # the backward pass recomputes an expert: one's activations at a time
    def one(out, xs):
        e, w1, w3, w2 = xs
        mine = jnp.take(per_expert, first + e, axis=-1)[..., None]
        return out + mine * swiglu({"w1": w1, "w3": w3, "w2": w2}, h, op), None

    held = jnp.arange(ex["w1"].shape[0])
    out, _ = jax.lax.scan(one, out, (held, ex["w1"], ex["w3"], ex["w2"]))
    return out, jnp.sum(picked, axis=tuple(range(picked.ndim - 1)))


def bias_step(c, bias, counts):
    """``b += coeff * sign(mean(n) - n)`` with the delta's mean taken out."""
    delta = jnp.sign(jnp.mean(counts, -1, keepdims=True) - counts)
    return bias + c["load_balance_coeff"] * (delta - jnp.mean(delta, -1, keepdims=True))


def layer(c, kind, p, x, b, op, block=256):
    """One layer of ``kind`` (window or None, positions, ffn) -> (x, the
    step's token counts over all experts: zeros for a dense layer)."""
    window, positions, ffn = kind
    eps = c["rms_norm_eps"]
    a = attention(c, p["attn"], _norm(x, p["norm_in"], eps), window, positions, op, block)
    x = x + _norm(a, p["norm_post_attn"], eps)
    h = _norm(x, p["norm_pre_mlp"], eps)
    if ffn == "dense":
        f, n = swiglu(p["ffn"], h, op), jnp.zeros((c["num_experts"],))
    else:
        f, n = routed_ffn(c, p["ffn"], h, b, op)
    return x + _norm(f, p["norm_post_mlp"], eps), n


def embed(c, tok_emb, tokens):
    return tok_emb[tokens] * math.sqrt(c["hidden_size"])


def logits_of(c, final_norm, head, x, op):
    return jnp.einsum("btd,dv->btv", op(_norm(x, final_norm, c["rms_norm_eps"])), op(head))


def nll_sum(logits, tokens):
    """Sum over rows and positions of the next-token NLL (the last position
    has no target)."""
    logits, targets = logits[:, :-1], tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tl)


def biases(c, bias):
    """The bias of each layer: its router's row, or nought for a dense one."""
    out, routed = [], 0
    for _, _, ffn in c["layers_run"]:
        out.append(bias[routed] if ffn == "routed" else jnp.zeros(()))
        routed += ffn == "routed"
    return out


def forward(c, params, tokens, bias, precision="float32", block=256):
    """-> (logits [B, T, V], counts [routed layers, E])."""
    op = lambda a: common.operand(a, precision)  # noqa: E731
    x = embed(c, params["tok_emb"], tokens)
    counts = []
    for kind, p, b in zip(c["layers_run"], params["layers"], biases(c, bias)):
        x, n = layer(c, tuple(kind), p, x, b, op, block)
        if kind[2] == "routed":
            counts.append(n)
    logits = logits_of(c, params["final_norm"], params["head"], x, op)
    return logits, jnp.stack(counts) if counts else jnp.zeros((0, c["num_experts"]))


def loss_sum(c, params, tokens, bias, precision="float32", block=256):
    """-> (summed next-token NLL, the step's token counts): the whole
    model as one function, for ``jax.grad`` at sizes that fit."""
    logits, counts = forward(c, params, tokens, bias, precision, block)
    return nll_sum(logits, tokens), counts


def n_routed(c):
    return sum(ffn == "routed" for _, _, ffn in c["layers_run"])


class LayerwiseGrad:
    """The gradient of the mean loss one layer at a time, so that the
    reference fits beside its own optimizer state: the forward keeps each
    layer's input, the backward recomputes the layer from it (the same
    mathematics as ``jax.grad(loss_sum)``, which the CPU tests hold it to)
    and hands each layer's gradient to ``sink`` before the next is made."""

    def __init__(self, c, precision, block):
        self.c, self.block = c, block
        self.op = op = lambda a: common.operand(a, precision)  # noqa: E731
        self._fwd, self._bwd = {}, {}

        def head(final_norm, w, x, tokens):
            denom = tokens.shape[0] * (tokens.shape[1] - 1)
            return nll_sum(logits_of(c, final_norm, w, x, op), tokens) / denom

        self.head = jax.jit(jax.value_and_grad(head, argnums=(0, 1, 2)))
        self.embed = jax.jit(lambda e, t: embed(c, e, t))
        # d loss / d embedding: the rows' cotangents added where they were read
        self.embed_bwd = jax.jit(lambda e, t, dx: jnp.zeros_like(e).at[t].add(
            dx * math.sqrt(c["hidden_size"])))

    def _fns(self, kind):
        if kind not in self._fwd:
            def run(p, x, b):
                return layer(self.c, kind, p, x, b, self.op, self.block)

            def back(p, x, b, dx):
                _, pull = jax.vjp(lambda p, x: run(p, x, b)[0], p, x)
                return pull(dx)

            self._fwd[kind], self._bwd[kind] = jax.jit(run), jax.jit(back)
        return self._fwd[kind], self._bwd[kind]

    def __call__(self, params, tokens, bias, sink):
        """-> (mean loss, counts); ``sink(path, gradient)`` gets each part's
        gradient under its path in ``params``: the final norm and the head,
        then ``("layers", i)`` from the last layer to the first, then the
        embedding."""
        c = self.c
        kinds = [tuple(k) for k in c["layers_run"]]
        bs = biases(c, bias)
        xs, counts = [self.embed(params["tok_emb"], tokens)], []
        for kind, p, b in zip(kinds, params["layers"], bs):
            x, n = self._fns(kind)[0](p, xs[-1], b)
            xs.append(x)
            if kind[2] == "routed":
                counts.append(n)
        loss, (d_norm, d_head, dx) = self.head(params["final_norm"], params["head"], xs.pop(), tokens)
        sink(("final_norm",), d_norm)
        sink(("head",), d_head)
        del d_norm, d_head
        for i in reversed(range(len(kinds))):
            dp, dx = self._fns(kinds[i])[1](params["layers"][i], xs.pop(), bs[i], dx)
            sink(("layers", i), dp)
            del dp
        sink(("tok_emb",), self.embed_bwd(params["tok_emb"], tokens, dx))
        return loss, jnp.stack(counts) if counts else jnp.zeros((0, c["num_experts"]))


def follow(c, seed, batches, precision="float32", rows=None, block=256):
    """Follow ``len(batches)`` steps from the seed's weights. -> dict with
    the final ``params`` and ``bias`` besides what :func:`run` hands on."""
    opt = c["optimizer"]
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]

    def adam(p, g, m, v, t):
        """Adam on one subtree, in place (the old buffers are donated)."""
        tm = jax.tree_util.tree_map
        m = tm(lambda m, g: b1 * m + (1 - b1) * g, m, g)
        v = tm(lambda v, g: b2 * v + (1 - b2) * g * g, v, g)
        scale = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        return tm(lambda p, m, v: p - scale * m / (jnp.sqrt(v) + eps), p, m, v), m, v

    adam = jax.jit(adam, donate_argnums=(0, 2, 3))
    norms = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), t))

    with jax.default_matmul_precision("highest"):
        params = init(c, seed)
        p0 = jax.device_get(params)  # on the host: the device holds one copy
        m = jax.tree_util.tree_map(jnp.zeros_like, params)
        v = jax.tree_util.tree_map(jnp.zeros_like, params)
        bias = jnp.zeros((n_routed(c), c["num_experts"]), jnp.float32)
        grad = LayerwiseGrad(c, precision, block)
        losses, g1 = [], None
        for step, (x, _) in enumerate(batches, 1):
            tok = jnp.asarray(np.asarray(x)[rows] if rows is not None else x, jnp.int32)
            if not tok.shape[0]:
                raise ValueError("no rows left of the batch")
            first = {} if g1 is None else None
            t = jnp.float32(step)

            def sink(path, g):
                """A part's gradient moves that part at once and is dropped."""
                *parents, last = path
                p_at, m_at, v_at = params, m, v
                for key in parents:
                    p_at, m_at, v_at = p_at[key], m_at[key], v_at[key]
                if first is not None:
                    first[path] = norms(g)
                p_at[last], m_at[last], v_at[last] = adam(p_at[last], g, m_at[last], v_at[last], t)

            loss, counts = grad(params, tok, bias, sink)
            if first is not None:
                g1 = common._named(p0, jax.tree_util.tree_leaves({
                    **{k: first[(k,)] for k in ("final_norm", "head", "tok_emb")},
                    "layers": [first[("layers", i)] for i in range(len(params["layers"]))]}))
            if counts.shape[0]:
                bias = bias_step(c, bias, counts)
            losses.append(float(loss))
        del m, v
        return {"losses": losses, "grad_norms": g1, "init": p0,
                "change_norms": common.diff_norms(params, p0), "params": params, "bias": bias}


def run(config, seed, batches, precision="float32", rows=None, block=256):
    """-> losses, first gradient's leaf norms, change's leaf norms, the
    initial weights (on the host). ``rows`` (a slice of the batch's rows)
    plants the half-batch fault, which a batch of one row cannot have."""
    out = follow(config, seed, batches, precision, rows, block)
    return {k: out[k] for k in ("losses", "grad_norms", "change_norms", "init")}
