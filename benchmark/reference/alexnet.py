"""Plain reference for ``alexnet``: Krizhevsky et al. 2012 in the one-tower
grouped form the configuration file states, in float32 at ``highest``
precision with ``jax.numpy``/``lax`` primitives (no banded-matmul LRN, no
kernel), classical momentum with L2 decay. Imports nothing of the program;
makes its own weights and dropout masks from the seed. Where the cell's
batches come out of the program's loader, the cell's data kind rebuilds them
apart from it (``data/<kind>.py reference_batches``).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import common  # noqa: E402


def layers(config):
    """The layer sequence, one entry per position of the program's list (the
    position numbers the parameter names and splits the keys)."""
    seq = []
    for c in config["conv"]:
        seq.append(("conv", c))
        seq.append(("relu", None))
        if c["lrn"]:
            seq.append(("lrn", None))
        if c["pool"]:
            seq.append(("pool", None))
    seq.append(("flatten", None))
    for f in config["fc"]:
        seq.append(("fc", f))
        if f["dropout"]:
            seq.append(("relu", None))
            seq.append(("dropout", f["dropout"]))
    return seq


def init(config, seed):
    seq = layers(config)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(seq))
    h, w, cin = config["input_shape"]
    params = {}
    for i, (kind, c) in enumerate(seq):
        if kind == "conv":
            wkey, _ = jax.random.split(keys[i])
            k = c["kernel"]
            params[f"{i:02d}_{c['name']}"] = {
                "w": 0.0 + c["std"] * jax.random.normal(wkey, (k, k, cin // c["groups"], c["out"]), jnp.float32),
                "b": jnp.full((c["out"],), c["bias"], jnp.float32),
            }
            h = (h + 2 * c["pad"] - k) // c["stride"] + 1
            w = (w + 2 * c["pad"] - k) // c["stride"] + 1
            cin = c["out"]
        elif kind == "pool":
            h = (h - config["pool"]["window"]) // config["pool"]["stride"] + 1
            w = (w - config["pool"]["window"]) // config["pool"]["stride"] + 1
        elif kind == "flatten":
            cin = h * w * cin
        elif kind == "fc":
            wkey, _ = jax.random.split(keys[i])
            params[f"{i:02d}_{c['name']}"] = {
                "w": 0.0 + c["std"] * jax.random.normal(wkey, (cin, c["out"]), jnp.float32),
                "b": jnp.full((c["out"],), c["bias"], jnp.float32),
            }
            cin = c["out"]
    return params


def _lrn(x, n, alpha, beta, k):
    half = n // 2
    sq = jnp.pad(x * x, [(0, 0)] * 3 + [(half, half)])
    c = x.shape[-1]
    win = sum(sq[..., j:j + c] for j in range(n))
    return x / jnp.power(k + (alpha / n) * win, beta)


def row_loss_sum(params, images, labels, masks, config, precision="float32"):
    """Sum of the rows' cross-entropy. ``precision`` other than float32 holds
    every operand and every activation the configuration computes in bf16 in
    that precision instead (the control; the bf16 witness)."""
    op = lambda a: common.operand(a, precision)  # noqa: E731
    x = (images.astype(jnp.float32) - config["input_mean"]) * config["input_scale"]
    pw, ps = config["pool"]["window"], config["pool"]["stride"]
    masks = list(masks)
    for i, (kind, c) in enumerate(layers(config)):
        if kind == "conv":
            p = params[f"{i:02d}_{c['name']}"]
            x = lax.conv_general_dilated(
                op(x), op(p["w"]), (c["stride"],) * 2, [(c["pad"],) * 2] * 2,
                feature_group_count=c["groups"],
                dimension_numbers=("NHWC", "HWIO", "NHWC")) + p["b"]
        elif kind == "relu":
            x = jnp.maximum(x, 0.0)
        elif kind == "lrn":
            x = op(_lrn(x, **config["lrn"]))
        elif kind == "pool":
            x = lax.reduce_window(x, -jnp.inf, lax.max, (1, pw, pw, 1), (1, ps, ps, 1), "VALID")
        elif kind == "flatten":
            x = x.reshape(x.shape[0], -1)
        elif kind == "fc":
            p = params[f"{i:02d}_{c['name']}"]
            x = op(x) @ op(p["w"]) + p["b"]
        elif kind == "dropout":
            x = op(jnp.where(masks.pop(0), x / (1.0 - c), 0.0))
    logp = jax.nn.log_softmax(x)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=-1))


def dropout_masks(config, step_key, n_rows):
    """The step's masks as the program draws them: the one-device BSP step
    folds 0 into the step's key, the layer list splits that over its
    positions, and each dropout draws bernoulli(keep) for the whole batch."""
    seq = layers(config)
    keys = jax.random.split(jax.random.fold_in(step_key, 0), len(seq))
    width, out = None, []
    for i, (kind, c) in enumerate(seq):
        if kind == "fc":
            width = c["out"]
        elif kind == "dropout":
            out.append(jax.random.bernoulli(keys[i], 1.0 - c, (n_rows, width)))
    return out


def run(config, seed, batches, precision="float32", rows=None, block=128):
    """Follow ``len(batches)`` steps from the seed's weights; see the LM's
    ``run`` for what comes back. ``seed`` is the program's seed: it makes the
    weights and the dropout keys."""
    opt = config["optimizer"]
    with jax.default_matmul_precision("highest"):
        params = p0 = init(config, seed)
        state = common.momentum_init(params)
        opt_step = common.make_momentum_step(opt)
        key = jax.random.PRNGKey(seed)
        losses, g1 = [], None
        fn = jax.jit(jax.value_and_grad(
            lambda p, x, y, *m: row_loss_sum(p, x, y, m, config, precision)))
        for x, y in batches:
            key, sub = jax.random.split(key)
            masks = dropout_masks(config, sub, len(x))
            if rows is not None:
                x, y, masks = x[rows], y[rows], [m[rows] for m in masks]
            n = len(x)
            loss, grads = common.blocked_loss_and_grads(
                fn, params, (jnp.asarray(x), jnp.asarray(y), *masks), n, block, denom=n)
            if g1 is None:
                g1 = common.grad_norms(grads, params, opt["weight_decay"])
            params, state = opt_step(params, grads, state)
            losses.append(float(loss))
        return {"losses": losses, "grad_norms": g1,
                "change_norms": common.diff_norms(params, p0), "init": p0}
