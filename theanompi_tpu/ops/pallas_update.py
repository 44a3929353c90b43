"""Fused optimizer-update Pallas kernel: weight decay + global-norm
grad clip + momentum/Nesterov + param write in ONE pass over HBM.

The classic path (ops/optimizers.py) walks every parameter leaf ~4
times per step — ``_decayed`` (read g, read p, write g'), the velocity
tree_map (read v, write v'), and ``apply_updates`` (read p, read u,
write p') — each a full HBM round-trip XLA does not reliably fuse
across the tree_map boundaries. At AlexNet scale that is ~1 GB of
avoidable HBM traffic per step, a first-order term in the 0.38-MFU
plateau (ROADMAP item 2a; see ``tmpi profile``'s residual fraction).
This module fuses the whole epilogue into one Pallas kernel per leaf:

    g_eff = clip_coef * g + wd * p          (decay + clip folded)
    v'    = mu * v - lr * g_eff
    p'    = p + v'                          (classical)
    p'    = p + mu * v' - lr * g_eff        (Nesterov)

reading each of (p, v, g) once and writing (p', v') once, with
``input_output_aliases`` donating the param/velocity buffers so the
update happens in place. The global-norm clip coefficient is ONE scalar
reduction over the grads computed before the kernel launch (clipping is
inherently global; ``clip_norm=None`` skips it and the coefficient is
the constant 1). Arithmetic runs in fp32 regardless of the param dtype
(bf16 params keep fp32 velocity, exactly like the tree_map rules) and
the fused ``p + step`` rounds ONCE to the param dtype — one ulp-level
difference from ``apply_updates``'s round-then-add on bf16 params,
bit-identical on fp32 (tests/test_pallas_update.py).

Exposed as a drop-in :class:`~theanompi_tpu.ops.optimizers.Optimizer`
whose ``apply`` field carries the fused form — ``train.make_train_step``
(and the ZeRO-1 / ND steps) prefer ``apply`` when present, so every
engine opts in through one ``--fused-update`` knob. ``update`` remains
the reference tree_map math (the oracle the parity tests diff against).

Layout: leaves are flattened and zero-padded to (rows, 128) lanes (the
repo's Pallas idiom — ops/pallas_quant.py) and the kernel runs on a
row-block grid so arbitrarily large leaves stream through VMEM.
``TMPI_PALLAS=0`` routes to the pure-jnp fallback (same math); off-TPU
the kernel runs through the Pallas interpreter — identical numerics
everywhere.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.optimizers import Optimizer, _acc_like
from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret
from theanompi_tpu.ops.pallas_util import use_pallas as _use_pallas

_LANES = 128
# rows per grid step: 5 buffers x 512 rows x 128 lanes x 4 B ~= 1.3 MB
# of VMEM per iteration — comfortably under the ~16 MB budget while
# large enough that the grid overhead is noise
_BLOCK_ROWS = 512


def _block_rows(rows: int) -> int:
    """Grid block size: VMEM-bounded row blocks on real TPU; ONE block
    in interpreter mode (no VMEM to respect, and the interpreter pays
    per grid step — a 37M-element AlexNet fc leaf would otherwise trace
    ~1000 interpreted iterations)."""
    if _interpret():
        return rows
    return min(_BLOCK_ROWS, rows)


# --------------------------------------------------------------------------
# kernels (momentum variant carries velocity; plain SGD is stateless)
# --------------------------------------------------------------------------


MOMENTUM_NAME = "update_momentum"  # the kernel's name in a device trace


def _momentum_kernel(p_ref, v_ref, g_ref, sc_ref, p_out, v_out, *,
                     momentum, weight_decay, nesterov):
    lr = sc_ref[0, 0]
    coef = sc_ref[0, 1]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * coef + weight_decay * p
    v = momentum * v_ref[:] - lr * g
    v_out[:] = v
    step = momentum * v - lr * g if nesterov else v
    p_out[:] = (p + step).astype(p_out.dtype)


SGD_NAME = "update_sgd"  # the kernel's name in a device trace


def _sgd_kernel(p_ref, g_ref, sc_ref, p_out, *, weight_decay):
    lr = sc_ref[0, 0]
    coef = sc_ref[0, 1]
    p = p_ref[:].astype(jnp.float32)
    g = g_ref[:].astype(jnp.float32) * coef + weight_decay * p
    p_out[:] = (p - lr * g).astype(p_out.dtype)


def _to_rows(flat: jax.Array, block_rows: int):
    """Zero-pad a flat vector to a (rows, 128) layout whose row count
    divides the grid's block size; returns (2-D view, rows)."""
    L = flat.shape[0]
    rows = -(-L // _LANES)
    rows = -(-rows // block_rows) * block_rows
    pad = rows * _LANES - L
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES), rows


def _scalars(lr, clip_coef) -> jax.Array:
    return jnp.stack([jnp.asarray(lr, jnp.float32),
                      jnp.asarray(clip_coef, jnp.float32)]).reshape(1, 2)


def fused_update_leaf(p, v, g, lr, clip_coef, *, momentum: float,
                      weight_decay: float, nesterov: bool):
    """One leaf through the fused momentum kernel -> ``(p', v')``.
    ``v`` is the fp32 velocity (same shape as ``p``); ``clip_coef`` is
    the precomputed global-norm clip scale (1.0 = no clip)."""
    if not _use_pallas():
        pf = p.astype(jnp.float32)
        gf = g.astype(jnp.float32) * clip_coef + weight_decay * pf
        v2 = momentum * v - lr * gf
        step = momentum * v2 - lr * gf if nesterov else v2
        return (pf + step).astype(p.dtype), v2
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = p.shape
    flat_p = p.reshape(-1)
    block = _block_rows(-(-flat_p.shape[0] // _LANES))
    p2, rows = _to_rows(flat_p, block)
    v2, _ = _to_rows(v.astype(jnp.float32).reshape(-1), block)
    g2, _ = _to_rows(g.astype(jnp.float32).reshape(-1), block)
    grid = (rows // block,)
    vspec = pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM)
    new_p, new_v = pl.pallas_call(
        partial(_momentum_kernel, momentum=momentum,
                weight_decay=weight_decay, nesterov=nesterov),
        out_shape=(
            jax.ShapeDtypeStruct(p2.shape, p2.dtype),
            jax.ShapeDtypeStruct(v2.shape, jnp.float32),
        ),
        grid=grid,
        in_specs=[vspec, vspec, vspec, sspec],
        out_specs=(vspec, vspec),
        # in-place: the param and velocity buffers are rewritten, not
        # copied — the donation that makes this ONE HBM round-trip
        input_output_aliases={0: 0, 1: 1},
        name=MOMENTUM_NAME,
        interpret=_interpret(),
    )(p2, v2, g2, _scalars(lr, clip_coef))
    L = math.prod(shape) if shape else 1
    return (new_p.reshape(-1)[:L].reshape(shape),
            new_v.reshape(-1)[:L].reshape(shape))


def fused_sgd_leaf(p, g, lr, clip_coef, *, weight_decay: float):
    """Stateless fused SGD leaf -> ``p'`` (no velocity buffer)."""
    if not _use_pallas():
        pf = p.astype(jnp.float32)
        gf = g.astype(jnp.float32) * clip_coef + weight_decay * pf
        return (pf - lr * gf).astype(p.dtype)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    shape = p.shape
    flat_p = p.reshape(-1)
    block = _block_rows(-(-flat_p.shape[0] // _LANES))
    p2, rows = _to_rows(flat_p, block)
    g2, _ = _to_rows(g.astype(jnp.float32).reshape(-1), block)
    vspec = pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM)
    sspec = pl.BlockSpec((1, 2), lambda i: (0, 0),
                         memory_space=pltpu.SMEM)
    new_p = pl.pallas_call(
        partial(_sgd_kernel, weight_decay=weight_decay),
        out_shape=jax.ShapeDtypeStruct(p2.shape, p2.dtype),
        grid=(rows // block,),
        in_specs=[vspec, vspec, sspec],
        out_specs=vspec,
        input_output_aliases={0: 0},
        name=SGD_NAME,
        interpret=_interpret(),
    )(p2, g2, _scalars(lr, clip_coef))
    L = math.prod(shape) if shape else 1
    return new_p.reshape(-1)[:L].reshape(shape)


# --------------------------------------------------------------------------
# clip coefficient: ONE global scalar over the raw grads
# --------------------------------------------------------------------------


def clip_coefficient(grads, clip_norm: Optional[float]):
    """Global-norm clip scale ``min(1, clip_norm / ||g||)`` over ALL
    leaves' raw gradients (fp32). Safe at both edges: a zero-norm grad
    tree yields coefficient 1 (no 0/0 NaN), a norm beyond ``clip_norm``
    scales every leaf by the same factor. ``None`` -> the constant 1."""
    if clip_norm is None:
        return jnp.float32(1.0)
    gsq = sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in jax.tree_util.tree_leaves(grads)
    )
    norm = jnp.sqrt(gsq)
    return jnp.minimum(jnp.float32(1.0),
                       jnp.float32(clip_norm) / jnp.maximum(norm, 1e-16))


# --------------------------------------------------------------------------
# drop-in Optimizer builders (``apply`` = fused; ``update`` = the
# reference tree_map math, kept as the parity oracle)
# --------------------------------------------------------------------------


def _ref_decayed_clipped(grads, params, weight_decay, coef):
    return jax.tree_util.tree_map(
        lambda g, p: g.astype(jnp.float32) * coef
        + weight_decay * p.astype(jnp.float32),
        grads, params,
    )


def fused_momentum_sgd(momentum: float = 0.9, weight_decay: float = 0.0,
                       clip_norm: Optional[float] = None,
                       nesterov: bool = False) -> Optimizer:
    """Fused classical/Nesterov momentum SGD. State layout is IDENTICAL
    to ``momentum_sgd``/``nesterov_sgd`` (``{"vel": fp32}``), so
    checkpoints resume across the fused/unfused boundary."""
    mu, wd = float(momentum), float(weight_decay)

    def init(params):
        return {"vel": _acc_like(params)}

    def apply(grads, state, params, lr):
        coef = clip_coefficient(grads, clip_norm)
        leaves_p, treedef = jax.tree_util.tree_flatten(params)
        leaves_v = jax.tree_util.tree_leaves(state["vel"])
        leaves_g = jax.tree_util.tree_leaves(grads)
        out_p, out_v = [], []
        for p, v, g in zip(leaves_p, leaves_v, leaves_g):
            np_, nv = fused_update_leaf(
                p, v, g, lr, coef, momentum=mu, weight_decay=wd,
                nesterov=nesterov,
            )
            out_p.append(np_)
            out_v.append(nv)
        return (
            jax.tree_util.tree_unflatten(treedef, out_p),
            {"vel": jax.tree_util.tree_unflatten(treedef, out_v)},
        )

    def update(grads, state, params, lr):
        coef = clip_coefficient(grads, clip_norm)
        g = _ref_decayed_clipped(grads, params, wd, coef)
        vel = jax.tree_util.tree_map(
            lambda v, gi: mu * v - lr * gi, state["vel"], g
        )
        if nesterov:
            updates = jax.tree_util.tree_map(
                lambda v, gi: mu * v - lr * gi, vel, g
            )
        else:
            updates = vel
        return updates, {"vel": vel}

    name = ("nesterov" if nesterov else "momentum") + "_fused"
    return Optimizer(name, init, update, apply)


def fused_nesterov_sgd(momentum: float = 0.9, weight_decay: float = 0.0,
                       clip_norm: Optional[float] = None) -> Optimizer:
    return fused_momentum_sgd(momentum, weight_decay, clip_norm,
                              nesterov=True)


def fused_sgd(weight_decay: float = 0.0,
              clip_norm: Optional[float] = None) -> Optimizer:
    """Fused vanilla SGD (stateless, like ``sgd``)."""
    wd = float(weight_decay)

    def init(params):
        return ()

    def apply(grads, state, params, lr):
        coef = clip_coefficient(grads, clip_norm)
        new_p = jax.tree_util.tree_map(
            lambda p, g: fused_sgd_leaf(p, g, lr, coef, weight_decay=wd),
            params, grads,
        )
        return new_p, state

    def update(grads, state, params, lr):
        coef = clip_coefficient(grads, clip_norm)
        g = _ref_decayed_clipped(grads, params, wd, coef)
        return jax.tree_util.tree_map(lambda gi: -lr * gi, g), state

    return Optimizer("sgd_fused", init, update, apply)


_FUSED_BUILDERS = {
    "sgd": fused_sgd,
    "momentum": fused_momentum_sgd,
    "nesterov": fused_nesterov_sgd,
}


def fuse_optimizer(name: str, **kwargs) -> Optimizer:
    """The ``--fused-update`` entry point: the fused equivalent of a
    registry optimizer name (recipes name their rule as a string). Only
    the AlexNet-era SGD family has a fused kernel; anything else is
    refused loudly rather than silently falling back to the slow path.
    ``clip_norm`` is accepted here but is a DIRECT-API feature of the
    fused builders: a recipe cannot carry it in ``opt_kwargs``, because
    state init walks the classic registry, which refuses the fused-only
    knob (and ZeRO-1/ND refuse it regardless — their steps see local
    shards, so the fused global norm would be per-rank partial)."""
    try:
        builder = _FUSED_BUILDERS[name]
    except KeyError:
        raise ValueError(
            f"--fused-update has no fused kernel for optimizer {name!r}; "
            f"fused rules: {sorted(_FUSED_BUILDERS)} "
            "(ops/pallas_update.py — drop the flag for other rules)"
        ) from None
    return builder(**kwargs)
