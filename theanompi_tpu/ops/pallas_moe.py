"""Pallas grouped matrix products for routed experts — rows sorted by
expert against one weight matrix per expert, with the custom VJP.

BEYOND-PARITY EXTENSION (the reference has no experts; SURVEY.md §2.3).
The routed layer (:func:`theanompi_tpu.ops.moe.routed_experts`) sorts its
token-expert pairs by expert and lays each expert's rows out from a row
tile's boundary, so a tile of ``tm`` rows belongs to ONE expert. Then

- ``moe_gmm``: ``y[tile] = x[tile] @ w[expert of tile]`` — grid (column
  tiles, row tiles), the row tiles innermost so an expert's weight block
  is fetched once per column tile; the tile-to-expert map and the number
  of live tiles are scalar-prefetched, and the block index maps read
  them. Run again on the same weights, their last dimension contracted,
  it gives ``dX = dY @ w[e]^T``.
- ``moe_tgmm``: ``dW[e] = X_e^T dY_e`` — the row tiles innermost again,
  an expert's output block accumulating in VMEM over its tiles.

The row buffer is sized for the worst case (every pair may land here),
so most tiles are DEAD: past ``n_live`` the index maps repeat the last
live block (no copy) and the body does nothing. What dead tiles hold in
the output is never written: the caller reads live rows only. A group of
no rows has no tile; its ``dW`` is zeroed after the kernel.

Numerics: operands in the input dtype on the MXU, fp32 accumulation.
Off-TPU the kernels run through the Pallas interpreter; the tests' oracle
is ``lax.ragged_dot``.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

GMM_NAME = "moe_gmm"  # the kernels' names in a device trace
TGMM_NAME = "moe_tgmm"
_TN = 512  # columns of an output tile (a whole narrower output is one tile)
_TK = 1024  # moe_tgmm: rows of a dW block


class _Cfg(NamedTuple):
    """Static kernel config (hashable: custom_vjp nondiff argument)."""

    tm: int  # rows of a tile: every group starts at a multiple of it
    transpose_rhs: bool  # dX: w stays [G, k, n] and its last dim is contracted
    interpret: bool


def padded_rows(pairs: int, groups: int, tm: int) -> int:
    """Rows of the sorted buffer that can never overflow: every one of
    ``pairs`` rows real, and each group padded up to its next tile."""
    return -(-pairs // tm) * tm + groups * tm


def group_tiles(group_sizes: jax.Array, tm: int) -> jax.Array:
    """Tiles each group takes: its rows rounded up; none for no rows."""
    return (group_sizes + tm - 1) // tm


def tile_map(group_sizes: jax.Array, tm: int, n_tiles: int):
    """-> (expert of each of ``n_tiles`` tiles, [1] number of live
    tiles). Dead tiles carry the last expert: never read past the clamp."""
    ends = jnp.cumsum(group_tiles(group_sizes, tm))
    te = jnp.searchsorted(ends, jnp.arange(n_tiles, dtype=ends.dtype), side="right")
    te = jnp.minimum(te, group_sizes.shape[0] - 1)
    return te.astype(jnp.int32), ends[-1:].astype(jnp.int32)


def _live(i, nl_ref):
    """Tile ``i`` clamped into the live ones (tile 0 where none is)."""
    return jnp.minimum(i, jnp.maximum(nl_ref[0] - 1, 0))


def _gmm_kernel(cfg: _Cfg, te_ref, nl_ref, x_ref, w_ref, o_ref):
    del te_ref  # read by the index maps

    @pl.when(pl.program_id(1) < nl_ref[0])
    def _tile():
        rhs = (1,) if cfg.transpose_rhs else (0,)
        o_ref[...] = lax.dot_general(
            x_ref[...], w_ref[0], (((1,), rhs), ((), ())),
            preferred_element_type=jnp.float32,
        ).astype(o_ref.dtype)


def _gmm_call(cfg: _Cfg, x, w, te, nl):
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = w.shape[1] if cfg.transpose_rhs else w.shape[2]
    tn = min(_TN, N)
    w_block = (1, tn, K) if cfg.transpose_rhs else (1, K, tn)
    w_at = ((lambda j, i, te, nl: (te[_live(i, nl)], j, 0)) if cfg.transpose_rhs
            else (lambda j, i, te, nl: (te[_live(i, nl)], 0, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(N // tn, M // cfg.tm),
            in_specs=[
                pl.BlockSpec((cfg.tm, K), lambda j, i, te, nl: (_live(i, nl), 0)),
                pl.BlockSpec(w_block, w_at),
            ],
            out_specs=pl.BlockSpec((cfg.tm, tn),
                                   lambda j, i, te, nl: (_live(i, nl), j)),
        ),
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        name=GMM_NAME,
        interpret=cfg.interpret,
    )(te, nl, x, w)


def _tgmm_kernel(cfg: _Cfg, te_ref, nl_ref, x_ref, dy_ref, o_ref):
    i = pl.program_id(2)
    live = i < nl_ref[0]
    first = (i == 0) | (te_ref[jnp.maximum(i - 1, 0)] != te_ref[i])

    @pl.when(live & first)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(live)
    def _acc():
        o_ref[0] += lax.dot_general(
            x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )


def _tgmm_call(cfg: _Cfg, x, dy, te, nl, groups: int):
    """``[G, K, N]`` fp32: ``x_e^T dy_e`` of every group that has a tile
    (the blocks of the others are never written)."""
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    N = dy.shape[1]
    tk, tn = min(_TK, K), min(_TN, N)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(K // tk, N // tn, M // cfg.tm),
            in_specs=[
                pl.BlockSpec((cfg.tm, tk), lambda a, j, i, te, nl: (_live(i, nl), a)),
                pl.BlockSpec((cfg.tm, tn), lambda a, j, i, te, nl: (_live(i, nl), j)),
            ],
            out_specs=pl.BlockSpec(
                (1, tk, tn), lambda a, j, i, te, nl: (te[_live(i, nl)], a, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((groups, K, N), jnp.float32),
        name=TGMM_NAME,
        interpret=cfg.interpret,
    )(te, nl, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _gmm(cfg: _Cfg, x, w, group_sizes):
    te, nl = tile_map(group_sizes, cfg.tm, x.shape[0] // cfg.tm)
    return _gmm_call(cfg, x, w, te, nl)


def _gmm_vjp_fwd(cfg, x, w, group_sizes):
    return _gmm(cfg, x, w, group_sizes), (x, w, group_sizes)


def _gmm_vjp_bwd(cfg, res, dy):
    x, w, group_sizes = res
    te, nl = tile_map(group_sizes, cfg.tm, x.shape[0] // cfg.tm)
    dx = _gmm_call(cfg._replace(transpose_rhs=True), dy, w, te, nl)
    dw = _tgmm_call(cfg, x, dy, te, nl, w.shape[0])  # dW[e] = x_e^T dy_e
    dw = jnp.where((group_sizes > 0)[:, None, None], dw, 0.0).astype(w.dtype)
    return dx, dw, None


_gmm.defvjp(_gmm_vjp_fwd, _gmm_vjp_bwd)


def gmm(
    x: jax.Array,  # [M, k] rows sorted by group, each group from a tile's boundary
    w: jax.Array,  # [G, k, n]
    group_sizes: jax.Array,  # [G] int32 real rows of each group
    *,
    tm: int = 256,
) -> jax.Array:
    """Grouped matrix product, differentiable in ``x`` and ``w``: row
    ``r`` of group ``g`` times ``w[g]``. Group ``g`` starts at row ``tm *
    (tiles of the groups before it)`` (:func:`group_tiles`); rows between
    a group's end and its last tile's end are computed with it (the caller
    keeps them finite and weighs them nought); rows of tiles past the last
    group's are NOT written, in ``y`` and in ``dx`` alike."""
    M, K = x.shape
    N = w.shape[2]
    if M % tm or N % min(_TN, N) or K % min(_TN, K) or K % min(_TK, K):
        raise ValueError(
            f"gmm: rows {M} must be whole tiles of {tm}, columns {N} and the "
            f"contracted {K} whole tiles of {_TN} (and {K} of {_TK}) where wider")
    return _gmm(_Cfg(tm, False, _interpret()), x, w, group_sizes.astype(jnp.int32))
