"""Plain reference for ``lm136m``: a decoder-only transformer at GPT-2-small
widths as the configuration file states it, in ``jax.numpy`` and float32 at
``highest`` precision, full causal softmax attention (no kernel), Adam.
Imports nothing of the program; makes its own weights from the seed.

Departures from GPT-2 (the repo's block, listed under ``assumed`` in the
configuration): RMS pre-norm with a gain and no bias, tanh GELU, no final
norm, learned positions, untied head.
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402


def init(config, seed):
    d, h, V, T = config["d_model"], config["d_ff"], config["vocab"], config["seq_len"]
    nh, hd, L, s = config["n_heads"], config["head_dim"], config["n_layers"], config["init_std"]
    ks = jax.random.split(jax.random.PRNGKey(seed), 3 + 4 * L)
    params = {
        "tok_emb": s * jax.random.normal(ks[0], (V, d)),
        "pos_emb": s * jax.random.normal(ks[1], (T, d)),
        "head": s * jax.random.normal(ks[2], (d, V)),
        "blocks": [],
    }
    for i in range(L):
        k0, k1, k2, k3 = ks[3 + 4 * i: 7 + 4 * i]
        params["blocks"].append({
            "qkv": s * jax.random.normal(k0, (d, 3, nh, hd)),
            "proj": s * jax.random.normal(k1, (nh, hd, d)),
            "mlp_in": s * jax.random.normal(k2, (d, h)),
            "mlp_out": s * jax.random.normal(k3, (h, d)),
            "ln1": jnp.ones((d,)),
            "ln2": jnp.ones((d,)),
        })
    return params


def _rms(x, g):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * g


def row_loss_sum(params, tokens, precision="float32"):
    """Sum over rows and positions of the next-token NLL (last position has
    no target)."""
    op = lambda a: common.operand(a, precision)  # noqa: E731
    B, T = tokens.shape
    x = params["tok_emb"][tokens] + params["pos_emb"][jnp.arange(T)][None]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for blk in params["blocks"]:
        hin = _rms(x, blk["ln1"])
        qkv = jnp.einsum("btd,dchk->btchk", op(hin), op(blk["qkv"]))
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhk,bthk->bhqt", op(q), op(k)) / np.sqrt(q.shape[-1])
        p = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), axis=-1)
        att = jnp.einsum("bhqt,bthk->bqhk", op(p), op(v))
        x = x + jnp.einsum("bthk,hkd->btd", op(att), op(blk["proj"]))
        hin = _rms(x, blk["ln2"])
        mid = jax.nn.gelu(jnp.einsum("btd,df->btf", op(hin), op(blk["mlp_in"])), approximate=True)
        x = x + jnp.einsum("btf,fd->btd", op(mid), op(blk["mlp_out"]))
    logits = jnp.einsum("btd,dv->btv", op(x), op(params["head"]))[:, :-1]
    targets = tokens[:, 1:]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tl = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tl)


def run(config, seed, batches, precision="float32", rows=None, block=2):
    """Follow ``len(batches)`` steps from the seed's weights. ``rows`` (a
    slice) plants the half-batch fault: the rest left out, the mean taken
    over those rows. -> losses, first gradient's leaf norms, change's leaf
    norms, the initial weights."""
    opt = config["optimizer"]
    with jax.default_matmul_precision("highest"):
        params = p0 = init(config, seed)
        state = common.adam_init(params)
        opt_step = common.make_adam_step(opt)
        losses, g1 = [], None
        fn = jax.jit(jax.value_and_grad(lambda p, t: row_loss_sum(p, t, precision)))
        for x, _ in batches:
            tok = jnp.asarray(np.asarray(x)[rows] if rows is not None else x, jnp.int32)
            n, T = tok.shape
            loss, grads = common.blocked_loss_and_grads(
                fn, params, (tok,), n, block, denom=n * (T - 1))
            if g1 is None:
                g1 = common.grad_norms(grads, params, opt["weight_decay"])
            params, state = opt_step(params, grads, state)
            losses.append(float(loss))
        return {"losses": losses, "grad_norms": g1,
                "change_norms": common.diff_norms(params, p0), "init": p0}
