"""Pallas max-pool (3x3, stride 1, SAME) with a fused eq-mask backward
— not the default path; opt-in via ``TMPI_PALLAS_POOL=1``.

Why this kernel was built: GoogLeNet's nine inception pool branches are
3x3/stride-1 max pools, and XLA lowers the AD of ``reduce_window`` max
to ``select-and-scatter``, the pool BACKWARD's whole cost. The classic
eq-mask backward
(``dx[p] = sum_over_window_offsets g[q] * [x[p] == y[q]]``) is
bandwidth-optimal on paper; the pure-jnp formulation loses because XLA
won't fuse the 9-way shifted accumulation, so this Pallas version keeps
the whole spatial map in one VMEM block (inception maps are <= 28x28)
and runs the accumulation register-resident.

**Routed in, it lost end to end on an earlier backend; on the current
chip it is not measured** (no benchmark cell runs it; ROADMAP S5/D6: a
parent-against-flipped pair decides, and the loser's path and switch
go). Two reasons it lost, recorded for the next person who tries:

1. In NHWC the +-1 spatial shifts fall on W — the SUBLANE dim of the
   (8, 128) vector tile — so every shifted read is a misaligned
   sublane shuffle, not an addressed VMEM row. Cheap shifts need H/W
   ABOVE the tile, i.e. an HWNC layout, and the NHWC<->HWNC transposes
   around the kernel cost ~as much as select-and-scatter itself.
2. The custom call is a fusion barrier: the reduce_window forward
   otherwise fuses into its neighbors (the ``broadcast_maximum_fusion``
   ops in the profile), and the custom VJP's saved ``y`` residual adds
   a full activation copy of HBM traffic.

So select-and-scatter is close to the practical optimum for NHWC max
pool on this target, and the kernel stays opt-in only.

Tie semantics when enabled: the gradient goes to EVERY position equal
to the window max (a valid subgradient). This matches the reference
stack — Theano's ``DownsampleFactorMaxGrad`` computed exactly this
eq-mask — while XLA's select-and-scatter picks the first maximum.
Tests pin tie-free equivalence with select-and-scatter and the
all-maxima tie behavior; off-TPU the kernels run in the Pallas
interpreter, and ``TMPI_PALLAS=0`` selects a jnp fallback with the
same semantics.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret
from theanompi_tpu.ops.pallas_util import use_pallas as _use_pallas

_LANES = 128
# VMEM budget per buffer copy (bytes) when picking the batch tile.
# Mosaic materializes each of the 9 shifted slices on the kernel's VMEM
# stack (~12 block-sized temporaries total incl. the framed buffers and
# the f32 accumulator), so the per-buffer budget must leave the 16 MB
# scoped-vmem limit room for all of them: 2 MB blocks OOM'd at
# 18.5 MB stack; 512 KB keeps the stack ~5 MB.
_BLOCK_BYTES = 512 * 1024
# whole-spatial blocking only: cap on H*W (inception maps are <= 28x28;
# a 64x64 map would force batch-tile 1 and ~4 buffers x 2MB, still fine,
# but beyond that halo tiling would be needed — route to XLA instead)
_MAX_HW = 64 * 64


def _ninf(dtype):
    return jnp.array(-jnp.finfo(dtype).max, dtype)


def _frame(x, fill):
    """Pad spatial axes (1, 2) of a 4-D block by 1 with ``fill``, via
    concatenate — Mosaic TPU has no dynamic_update_slice/pad lowering."""
    B, H, W, C = x.shape
    row = jnp.full((B, 1, W, C), fill, x.dtype)
    xp = jnp.concatenate([row, x, row], axis=1)
    col = jnp.full((B, H + 2, 1, C), fill, x.dtype)
    return jnp.concatenate([col, xp, col], axis=2)


def _shift_max(xp, H, W):
    """Max over the 9 shifted (H, W) views of the padded (H+2, W+2)
    spatial dims (axes 1, 2 of a 4-D block)."""
    y = None
    for di in range(3):
        for dj in range(3):
            s = lax.slice_in_dim(
                lax.slice_in_dim(xp, di, di + H, axis=1), dj, dj + W, axis=2
            )
            y = s if y is None else jnp.maximum(y, s)
    return y


FWD_NAME = "pool3x3_fwd"  # the kernel's name in a device trace


def _fwd_kernel(x_ref, y_ref, *, H, W):
    x = x_ref[:]
    xp = _frame(x, _ninf(x.dtype))
    y_ref[:] = _shift_max(xp, H, W)


BWD_NAME = "pool3x3_bwd"  # the kernel's name in a device trace


def _bwd_kernel(x_ref, y_ref, g_ref, dx_ref, *, H, W):
    # compare in f32: Mosaic's vector cmpf has no bf16 form on this
    # target, and bf16 embeds exactly in f32 so equality is unchanged
    x = x_ref[:].astype(jnp.float32)
    yp = _frame(y_ref[:].astype(jnp.float32), _ninf(jnp.float32))
    gp = _frame(g_ref[:].astype(jnp.float32), jnp.array(0.0, jnp.float32))
    dx = jnp.zeros(x.shape, jnp.float32)
    for di in range(3):
        for dj in range(3):
            ys = lax.slice_in_dim(
                lax.slice_in_dim(yp, di, di + H, axis=1), dj, dj + W, axis=2
            )
            gs = lax.slice_in_dim(
                lax.slice_in_dim(gp, di, di + H, axis=1), dj, dj + W, axis=2
            )
            dx = dx + jnp.where(x == ys, gs, 0.0)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _tiles(N, H, W, C, itemsize):
    """(batch_tile, channel_tile): whole spatial map per block, channel
    tile one lane group, batch tile sized to the VMEM budget."""
    bc = min(C, _LANES)
    per_row = (H + 2) * (W + 2) * bc * itemsize
    bb = max(1, min(N, _BLOCK_BYTES // per_row))
    return bb, bc


def _pallas_fwd(x):
    from jax.experimental import pallas as pl

    N, H, W, C = x.shape
    bb, bc = _tiles(N, H, W, C, x.dtype.itemsize)
    spec = pl.BlockSpec((bb, H, W, bc), lambda i, j: (i, 0, 0, j))
    return pl.pallas_call(
        functools.partial(_fwd_kernel, H=H, W=W),
        grid=(pl.cdiv(N, bb), pl.cdiv(C, bc)),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=FWD_NAME,
        interpret=_interpret(),
    )(x)


def _pallas_bwd(x, y, g):
    from jax.experimental import pallas as pl

    N, H, W, C = x.shape
    bb, bc = _tiles(N, H, W, C, x.dtype.itemsize)
    spec = pl.BlockSpec((bb, H, W, bc), lambda i, j: (i, 0, 0, j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, H=H, W=W),
        grid=(pl.cdiv(N, bb), pl.cdiv(C, bc)),
        in_specs=[spec, spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        name=BWD_NAME,
        interpret=_interpret(),
    )(x, y, g)


def _jnp_fwd(x):
    return lax.reduce_window(
        x, _ninf(x.dtype), lax.max, (1, 3, 3, 1), (1, 1, 1, 1), "SAME"
    )


def _jnp_bwd(x, y, g):
    pad = ((0, 0), (1, 1), (1, 1), (0, 0))
    yp = jnp.pad(y, pad, constant_values=_ninf(y.dtype))
    gp = jnp.pad(g.astype(jnp.float32), pad)
    H, W = x.shape[1], x.shape[2]
    dx = jnp.zeros(x.shape, jnp.float32)
    for di in range(3):
        for dj in range(3):
            ys = lax.slice_in_dim(
                lax.slice_in_dim(yp, di, di + H, axis=1), dj, dj + W, axis=2
            )
            gs = lax.slice_in_dim(
                lax.slice_in_dim(gp, di, di + H, axis=1), dj, dj + W, axis=2
            )
            dx = dx + jnp.where(x == ys, gs, 0.0)
    return dx.astype(x.dtype)


@jax.custom_vjp
def maxpool3x3_s1(x):
    """NHWC 3x3/stride-1/SAME max pool; backward is the fused eq-mask
    kernel (all-maxima subgradient — Theano semantics, see module
    docstring)."""
    return _pallas_fwd(x) if _use_pallas() else _jnp_fwd(x)


def _vjp_fwd(x):
    y = maxpool3x3_s1(x)
    return y, (x, y)


def _vjp_bwd(res, g):
    x, y = res
    dx = _pallas_bwd(x, y, g) if _use_pallas() else _jnp_bwd(x, y, g)
    return (dx,)


maxpool3x3_s1.defvjp(_vjp_fwd, _vjp_bwd)


def routable(window, stride, padding, x) -> bool:
    """Can ``nn.Pool`` route this max pool here? OPT-IN only
    (``TMPI_PALLAS_POOL=1`` — see module docstring for the measured
    rejection), 3x3/stride-1 with SAME-equivalent padding, 4-D input,
    spatial map small enough for whole-map VMEM blocks."""
    import os

    if os.environ.get("TMPI_PALLAS_POOL", "0") != "1":
        return False
    if window != (3, 3) or stride != (1, 1) or x.ndim != 4:
        return False
    if isinstance(padding, str):
        if padding != "SAME":
            return False
    else:
        p = (padding, padding) if isinstance(padding, int) else tuple(padding)
        if p != (1, 1):
            return False
    return x.shape[1] * x.shape[2] <= _MAX_HW
