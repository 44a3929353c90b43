"""Shared by the plain references: float32 at ``highest`` matmul precision,
gradients in blocks of rows so that the reference fits beside nothing else on
the chip, the two optimizers as their papers state them, and the int8
fake-quantiser of the lower-precision control. Imports nothing of the program.
"""

import jax
import jax.numpy as jnp


# -- the control: int8 or fp8, the nearest precisions below the configurations' bf16 --
def _q8(x):
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.round(x / scale) * scale


def _qf8(x):
    """fp8 e4m3 by arithmetic (no fp8 dtype needed of the backend): one scale
    per tensor puts the largest magnitude at 448, four significant bits are
    kept down to 2**-6, and below that the spacing is the subnormals' 2**-9."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    y = x / scale
    m, e = jnp.frexp(y)
    normal = jnp.ldexp(jnp.round(m * 16.0) / 16.0, e)
    subnormal = jnp.round(y * 512.0) / 512.0
    return jnp.where(jnp.abs(y) >= 2.0 ** -6, normal, subnormal) * scale


def _both_ways(q):
    """``q`` applied to the tensor going forward and to its cotangent coming
    back, as training in that precision would."""
    @jax.custom_vjp
    def f(x):
        return q(x)

    f.defvjp(lambda x: (q(x), None), lambda _, g: (q(g),))
    return f


_q8_both = _both_ways(_q8)
_qf8_both = _both_ways(_qf8)


def operand(x, precision):
    """A tensor in the named precision. ``float32`` is the reference; ``int8``
    and ``fp8`` (e4m3) round the tensor going forward and its cotangent coming
    back with one scale per tensor, as training in 8 bits would; ``bfloat16`` (what the
    configurations state) is the witness that tells rounding from a fault."""
    if precision == "float32":
        return x
    if precision == "int8":
        return _q8_both(x)
    if precision == "fp8":
        return _qf8_both(x)
    if precision == "bfloat16":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    raise ValueError(f"unknown precision {precision!r}")


# -- gradients in blocks of rows ---------------------------------------------
def blocked_loss_and_grads(fn, params, batch, n_rows, block, denom):
    """``fn(params, *rows) -> (sum of the rows' losses, its gradient)``, jitted
    by the caller once. Returns (mean loss, grads of the mean loss),
    accumulated over blocks of ``block`` rows in float32. ``batch`` is a tuple
    of arrays whose leading axis is the row; ``denom`` is what the summed loss
    is divided by."""
    total, grads = 0.0, None
    for s in range(0, n_rows, block):
        part = tuple(a[s:s + block] for a in batch)
        l, g = fn(params, *part)
        total = total + l
        grads = g if grads is None else jax.tree_util.tree_map(jnp.add, grads, g)
    return total / denom, jax.tree_util.tree_map(lambda g: g / denom, grads)


# -- optimizers (each step one jitted program) --------------------------------
def adam_init(params):
    z = jax.tree_util.tree_map(jnp.zeros_like, params)
    return {"m": z, "v": z, "t": jnp.zeros((), jnp.float32)}


def make_adam_step(opt):
    b1, b2, eps, lr = opt["b1"], opt["b2"], opt["eps"], opt["lr"]

    @jax.jit
    def step(params, grads, state):
        t = state["t"] + 1.0
        m = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, state["m"], grads)
        v = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, state["v"], grads)
        scale = lr * jnp.sqrt(1 - b2 ** t) / (1 - b1 ** t)
        new = jax.tree_util.tree_map(
            lambda p, m, v: p - scale * m / (jnp.sqrt(v) + eps), params, m, v)
        return new, {"m": m, "v": v, "t": t}

    return step


def momentum_init(params):
    return {"vel": jax.tree_util.tree_map(jnp.zeros_like, params)}


def make_momentum_step(opt):
    """Classical momentum with L2 decay folded into the gradient."""
    mu, wd, lr = opt["momentum"], opt["weight_decay"], opt["lr"]

    @jax.jit
    def step(params, grads, state):
        vel = jax.tree_util.tree_map(
            lambda v, g, p: mu * v - lr * (g + wd * p), state["vel"], grads, params)
        return jax.tree_util.tree_map(jnp.add, params, vel), {"vel": vel}

    return step


# -- norms by leaf, reduced on the device --------------------------------------
@jax.jit
def _norms(tree, decay_of, wd):
    """Norm of every leaf of ``tree + wd * decay_of`` (the gradient as the
    optimizer gets it, decay folded in)."""
    return [jnp.sqrt(jnp.sum(jnp.square(a + wd * p)))
            for a, p in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(decay_of))]


@jax.jit
def _diff_norms(a, b):
    return [jnp.sqrt(jnp.sum(jnp.square(x - y)))
            for x, y in zip(jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b))]


def _named(tree, values):
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {jax.tree_util.keystr(p): float(v) for (p, _), v in zip(flat, values)}


def grad_norms(grads, params, wd):
    """{path: norm} of the first gradient as the optimizer gets it."""
    return _named(grads, _norms(grads, params, jnp.float32(wd)))


def diff_norms(a, b):
    """{path: norm of the leaf's change}."""
    return _named(a, _diff_norms(a, b))
