"""Pallas int8 quantize/dequantize kernels — the compressed-wire
building block for gradient exchange.

The reference's ``Exch_asa16`` cast ring segments to fp16 on the wire
(reference: ``lib/exchanger_strategy.py``; SURVEY.md §2.3 "fp16-
compressed comm"); the TPU-native escalation is int8 with a per-block
scale (EQuARX-style, PAPERS.md): ~4x wire compression vs fp32 with the
accumulation still fp32. The quantize/dequantize hot loops are Pallas
TPU kernels (VPU elementwise over VMEM tiles); off-TPU (CPU test
meshes) the same kernels run through the Pallas interpreter, so the
numerics are identical everywhere.

Two scale granularities:

- **per-buffer** (``quantize_int8``): one absmax scale for the whole
  chunk — the original ring-segment scheme;
- **per-block** (``quantize_int8_block``): one absmax scale per
  (1, 128) lane row — the block-scaled recipe the codec layer
  (``parallel/codec.py``) uses per leaf, so one huge outlier only
  costs its own 128-element block the dynamic range.

Layout: kernels take the flat buffer reshaped to (rows, 128) lanes —
the natural VPU shape. ``wire_encode``/``wire_decode`` accept ANY
length (internal zero-pad to a 128 multiple; 1-element leaves work)
and pack values + block scales into ONE int8 message.

``TMPI_PALLAS=0`` switches to the pure-jnp fallback (same math).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret
from theanompi_tpu.ops.pallas_util import use_pallas as _use_pallas

_LANES = 128
# f32 scale bytes per value row packed into the wire tail (one f32 per
# 128-lane block -> 32 block scales per 128-byte scale row)
_SCALES_PER_ROW = _LANES // 4


QUANT_NAME = "quant_int8"  # the kernel's name in a device trace


def _quant_kernel(x_ref, vals_ref, scale_ref):
    amax = jnp.max(jnp.abs(x_ref[:]))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    scale_ref[0, 0] = scale
    scaled = x_ref[:] / scale
    # round-to-nearest-even, clamp to int8 range
    vals_ref[:] = jnp.clip(jnp.round(scaled), -127, 127).astype(jnp.int8)


DEQUANT_NAME = "dequant_int8"  # the kernel's name in a device trace


def _dequant_kernel(vals_ref, scale_ref, out_ref):
    out_ref[:] = vals_ref[:].astype(jnp.float32) * scale_ref[0, 0]


def _quantize_jnp(x2d):
    amax = jnp.max(jnp.abs(x2d))
    scale = jnp.maximum(amax, 1e-30) / 127.0
    vals = jnp.clip(jnp.round(x2d / scale), -127, 127).astype(jnp.int8)
    return vals, jnp.reshape(scale, (1, 1))


def quantize_int8(x2d: jax.Array):
    """``(rows, 128) f32 -> ((rows, 128) int8, (1, 1) f32 scale)`` with a
    single per-buffer absmax scale."""
    if not _use_pallas():
        return _quantize_jnp(x2d)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _quant_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=(
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ),
        name=QUANT_NAME,
        interpret=_interpret(),
    )(x2d)


def dequantize_int8(vals: jax.Array, scale: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_int8`."""
    if not _use_pallas():
        return vals.astype(jnp.float32) * scale[0, 0]
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    return pl.pallas_call(
        _dequant_kernel,
        out_shape=jax.ShapeDtypeStruct(vals.shape, jnp.float32),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
        name=DEQUANT_NAME,
        interpret=_interpret(),
    )(vals, scale)


# --------------------------------------------------------------------------
# block-scaled variants: one absmax scale per (1, 128) lane row — the
# per-leaf block quantizer the codec layer builds on
# --------------------------------------------------------------------------


QUANT_BLOCK_NAME = "quant_int8_block"  # the kernel's name in a device trace


def _quant_block_kernel(x_ref, vals_ref, scale_ref):
    # per-row reduction stays in VMEM (vector data, not a scalar):
    # keepdims shapes line up with the (rows, 1) scale output
    amax = jnp.max(jnp.abs(x_ref[:]), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    scale_ref[:] = scale
    vals_ref[:] = jnp.clip(jnp.round(x_ref[:] / scale), -127, 127).astype(
        jnp.int8
    )


DEQUANT_BLOCK_NAME = "dequant_int8_block"  # the kernel's name in a device trace


def _dequant_block_kernel(vals_ref, scale_ref, out_ref):
    out_ref[:] = vals_ref[:].astype(jnp.float32) * scale_ref[:]


def _quantize_block_jnp(x2d):
    amax = jnp.max(jnp.abs(x2d), axis=1, keepdims=True)
    scale = jnp.maximum(amax, 1e-30) / 127.0
    vals = jnp.clip(jnp.round(x2d / scale), -127, 127).astype(jnp.int8)
    return vals, scale


# rows per grid step of the block kernels: 1024 x 128 lanes of f32 in,
# int8 + one lane-padded scale column out ~= 1.2 MB of VMEM — a whole
# leaf in one block is refused by Mosaic from 32 MB up (16 MB scoped
# VMEM: an AlexNet fc leaf is 151 MB). Rows are independent (one scale
# each), so a ragged last block is harmless: what it reads past the end
# only reaches rows whose writes are dropped.
_BLOCK_ROWS = 1024


def _row_blocks(rows: int):
    """``(block_rows, lanes_spec, scale_column_spec)`` of the row grid.
    One block in interpreter mode (it pays per grid step, and has no
    VMEM to respect) — the same policy as ops/pallas_update.py."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    block = rows if _interpret() else min(_BLOCK_ROWS, rows)
    return (
        block,
        pl.BlockSpec((block, _LANES), lambda i: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block, 1), lambda i: (i, 0), memory_space=pltpu.VMEM),
    )


def quantize_int8_block(x2d: jax.Array):
    """``(rows, 128) f32 -> ((rows, 128) int8, (rows, 1) f32 scales)``
    with one absmax scale PER ROW (128-element block)."""
    if not _use_pallas():
        return _quantize_block_jnp(x2d)
    from jax.experimental import pallas as pl

    rows = x2d.shape[0]
    block, lanes, column = _row_blocks(rows)
    return pl.pallas_call(
        _quant_block_kernel,
        out_shape=(
            jax.ShapeDtypeStruct(x2d.shape, jnp.int8),
            jax.ShapeDtypeStruct((rows, 1), jnp.float32),
        ),
        grid=(pl.cdiv(rows, block),),
        in_specs=[lanes],
        out_specs=(lanes, column),
        name=QUANT_BLOCK_NAME,
        interpret=_interpret(),
    )(x2d)


def dequantize_int8_block(vals: jax.Array, scales: jax.Array) -> jax.Array:
    """Inverse of :func:`quantize_int8_block`."""
    if not _use_pallas():
        return vals.astype(jnp.float32) * scales
    from jax.experimental import pallas as pl

    rows = vals.shape[0]
    block, lanes, column = _row_blocks(rows)
    return pl.pallas_call(
        _dequant_block_kernel,
        out_shape=jax.ShapeDtypeStruct(vals.shape, jnp.float32),
        grid=(pl.cdiv(rows, block),),
        in_specs=[lanes, column],
        out_specs=lanes,
        name=DEQUANT_BLOCK_NAME,
        interpret=_interpret(),
    )(vals, scales)


# --------------------------------------------------------------------------
# packed wire format: values + block scales in ONE int8 message
# --------------------------------------------------------------------------


def _pad_rows(flat: jax.Array) -> jax.Array:
    """Zero-pad a flat f32 vector to a (rows, 128) lane layout."""
    L = flat.shape[0]
    rows = -(-L // _LANES)
    pad = rows * _LANES - L
    if pad:
        flat = jnp.pad(flat, (0, pad))
    return flat.reshape(rows, _LANES)


def wire_rows(length: int) -> tuple:
    """``(value_rows, scale_rows)`` of the packed message for a flat
    buffer of ``length`` elements — the static wire-geometry helper the
    traffic accounting shares with the encoder."""
    if length < 1:
        raise ValueError(f"cannot wire-encode a length-{length} buffer")
    rows = -(-length // _LANES)
    srows = -(-rows // _SCALES_PER_ROW)
    return rows, srows


def _rows_from_packed(n_rows: int) -> int:
    """Invert ``rows + ceil(rows/32) == n_rows`` (strictly increasing in
    ``rows``, so the solution is unique); static shapes only."""
    for rows in range(1, n_rows):
        if rows + -(-rows // _SCALES_PER_ROW) == n_rows:
            return rows
    raise ValueError(f"not a packed wire message: {n_rows} rows")


def wire_encode(chunk: jax.Array) -> jax.Array:
    """Flat f32 chunk of ANY length >= 1 -> ONE packed int8 message
    ``(rows + ceil(rows/32), 128)``: block-quantized lanes plus tail
    rows carrying the per-block f32 scales' bytes — a single ppermute
    per ring hop instead of a values+scales pair (the hops are
    latency-bound, especially over DCN). Non-128-multiple lengths are
    zero-padded internally (decode with ``length=`` to strip); a
    zero-filled buffer encodes to zeros and decodes to exact zeros (the
    scale floor keeps it finite — no NaN/Inf on decode)."""
    rows, srows = wire_rows(chunk.shape[0])
    vals, scales = quantize_int8_block(_pad_rows(chunk))
    scale_bytes = jax.lax.bitcast_convert_type(
        scales.reshape(rows), jnp.int8
    ).reshape(-1)
    tail = (
        jnp.zeros((srows * _LANES,), jnp.int8)
        .at[: rows * 4]
        .set(scale_bytes)
        .reshape(srows, _LANES)
    )
    return jnp.concatenate([vals, tail], axis=0)


def wire_decode(packed: jax.Array, length: Optional[int] = None) -> jax.Array:
    """Inverse of :func:`wire_encode` -> flat f32 of the padded length
    ``rows * 128`` (callers that encoded a non-128-multiple buffer pass
    their static ``length`` to strip the zero pad)."""
    if length is not None:
        rows, srows = wire_rows(length)
        if rows + srows != packed.shape[0]:
            raise ValueError(
                f"packed message has {packed.shape[0]} rows but length="
                f"{length} implies {rows + srows}"
            )
    else:
        rows = _rows_from_packed(packed.shape[0])
    vals = packed[:rows]
    scales = jax.lax.bitcast_convert_type(
        packed[rows:].reshape(-1)[: rows * 4].reshape(rows, 1, 4),
        jnp.float32,
    ).reshape(rows, 1)
    flat = dequantize_int8_block(vals, scales).reshape(-1)
    if length is not None:
        flat = flat[:length]
    return flat
