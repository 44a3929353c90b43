"""Mamba-1's selective state space: the prompt's scan as one Pallas kernel a
layer, and the one-position step of a decode iteration over per-slot state.

BEYOND-PARITY EXTENSION (the reference has no recurrent layer; SURVEY.md
§5.7). A layer of ``E`` channels keeps ``N`` states a channel in fp32,
nought before position 0:

    h_t = exp(dt_t * A) * h_(t-1) + (dt_t * x_t) B_t        [N, E]
    y_t = (C_t . h_t + D * x_t) * silu(z_t)                  [E]

``dt [T, E]`` (after the softplus), ``x`` (after the convolution), ``z`` (the
gate), ``B, C [T, N]`` and ``A [N, E]`` (``-exp(A_log)``, transposed), ``D
[E]``. The decay depends on the position, the channel and the state, so the
recurrence does not factor into chunk products (as ``pallas_lightning``'s
does): it is elementwise work, ``N E`` exponentials and a few multiply-adds a
position. ``dt``, ``exp(dt A)``, the state and ``y`` are fp32; ``x`` and ``z``
come in the compute dtype (they are the matmuls' operands too).

- :func:`mamba_scan`: one kernel a layer over a prompt. The grid is (channel
  blocks, time chunks), the chunks in order; a block's state ``[N, Cb]`` fp32
  (the ``N`` states on sublanes, the channels on lanes: every operation is
  whole vregs) is the call's output block, held in VMEM across the chunks and
  written to HBM once. Inside a chunk ``_GROUP`` rows at a time: their ``dt``,
  ``x`` and ``z`` as one ``[_GROUP, Cb]`` load, ``B`` and ``C`` as one
  ``[N, _GROUP]`` load (``B`` comes as ``[T / _GROUP, N, _GROUP]``, a group's
  columns), the state carried in registers from row to row, each row's ``C .
  h`` put in a scratch row, and ``y``, gated, stored once a group. Rows at or
  past ``n_real`` (a prefetched scalar) take ``dt = 0``: they neither decay
  nor add, and the state handed back is the one after row ``n_real - 1``. A
  chunk wholly past ``n_real`` writes zeros and reads nothing new (its
  blocks' index stays at the last real chunk).
- :func:`mamba_scan_reference`: its ``jnp`` twin, the same recurrence as an
  associative scan over ``(decay, input)`` pairs (it holds ``[T, N, E]`` fp32:
  small sizes only).
- :func:`mamba_step`: one position for every slot, ``jnp``: the state ``[S,
  N, E]`` read once and written once, from nought where ``fresh``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

SCAN_NAME = "mamba_scan"  # the kernel's name in a device trace
_GROUP = 16  # rows a group: one packed [16, 128] tile of the bfloat16 inputs


def _silu(z):
    return z * jax.nn.sigmoid(z)


def _scan_kernel(tc, n_ref, x_ref, dt_ref, b_ref, c_ref, z_ref, a_ref, d_ref, y_ref, h_ref, row_ref):
    n = pl.program_id(1)
    start, n_real = n * tc, n_ref[0]
    f32 = jnp.float32

    @pl.when(n == 0)
    def _():
        h_ref[...] = jnp.zeros_like(h_ref)

    @pl.when(start >= n_real)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(start < n_real)
    def _():
        A, D = a_ref[...], d_ref[...]  # [N, Cb], [1, Cb]

        def group(g, h):
            rows = pl.ds(pl.multiple_of(g * _GROUP, _GROUP), _GROUP)
            real = start + g * _GROUP + lax.broadcasted_iota(jnp.int32, (_GROUP, 1), 0) < n_real
            dt = jnp.where(real, dt_ref[rows, :], 0.0)  # [G, Cb]
            x = x_ref[rows, :].astype(f32)
            dtx = dt * x
            b, c = b_ref[g], c_ref[g]  # [N, G]: row i's B and C as column i
            for i in range(_GROUP):
                h = jnp.exp(dt[i:i + 1, :] * A) * h + dtx[i:i + 1, :] * b[:, i:i + 1]
                row_ref[i:i + 1, :] = jnp.sum(h * c[:, i:i + 1], axis=0, keepdims=True)
            z = z_ref[rows, :].astype(f32)
            y_ref[rows, :] = ((row_ref[...] + D * x) * _silu(z)).astype(y_ref.dtype)
            return h

        h_ref[...] = lax.fori_loop(0, tc // _GROUP, group, h_ref[...])


def mamba_scan(x, dt, B, C, z, A, D, n_real, *, chunk: int, block: int):
    """``x, z [T, E]`` (compute dtype), ``dt [T, E]`` fp32, ``B, C [T, N]``,
    ``A [N, E]``, ``D [E]`` (``T`` whole chunks, ``E`` whole blocks),
    ``n_real`` the prompt's real length (a traced scalar). -> (``y [T, E]`` in
    ``z``'s dtype, the state ``[N, E]`` fp32 after row ``n_real - 1``)."""
    T, E = x.shape
    N = A.shape[0]
    tc, cb = min(int(chunk), T), min(int(block), E)
    if T % tc or tc % _GROUP or E % cb:
        raise ValueError(f"{T} rows are no whole chunks of {tc} (whole groups of {_GROUP}), "
                         f"or {E} channels no whole blocks of {cb}")
    f32 = jnp.float32

    def last_real(n, n_real):  # a chunk past the prompt re-reads the last real one's blocks: no copy
        return jnp.minimum(n, jnp.maximum(n_real[0] - 1, 0) // tc)

    rows = pl.BlockSpec((tc, cb), lambda c, n, n_real: (last_real(n, n_real), c))
    cols = pl.BlockSpec((tc // _GROUP, N, _GROUP), lambda c, n, n_real: (last_real(n, n_real), 0, 0))
    by_group = lambda a: jnp.swapaxes(a.astype(f32).reshape(T // _GROUP, _GROUP, N), 1, 2)  # noqa: E731
    y, state = pl.pallas_call(
        functools.partial(_scan_kernel, tc),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(E // cb, T // tc),
            in_specs=[rows, rows, cols, cols, rows,
                      pl.BlockSpec((N, cb), lambda c, n, n_real: (0, c)),
                      pl.BlockSpec((1, cb), lambda c, n, n_real: (0, c))],
            out_specs=(pl.BlockSpec((tc, cb), lambda c, n, n_real: (n, c)),
                       pl.BlockSpec((N, cb), lambda c, n, n_real: (0, c))),
            scratch_shapes=[pltpu.VMEM((_GROUP, cb), f32)],
        ),
        out_shape=(jax.ShapeDtypeStruct((T, E), z.dtype), jax.ShapeDtypeStruct((N, E), f32)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name=SCAN_NAME,
        interpret=_interpret(),
    )(jnp.reshape(n_real, (1,)).astype(jnp.int32), x, dt.astype(f32), by_group(B), by_group(C), z,
      A.astype(f32), D.astype(f32).reshape(1, E))
    return y, state


def mamba_scan_reference(x, dt, B, C, z, A, D, n_real, *, chunk: int = 0, block: int = 0):
    """The scan kernel's ``jnp`` twin: ``h_t = a_t h_(t-1) + b_t`` as an
    associative scan over the pairs ``(a_t, b_t)``, fp32."""
    del chunk, block
    T = x.shape[0]
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    dt = jnp.where((jnp.arange(T) < n_real)[:, None], dt.astype(f32), 0.0)
    x = x.astype(f32)
    decay = jnp.exp(dt[:, None, :] * A.astype(f32)[None])  # [T, N, E]
    push = (dt * x)[:, None, :] * B.astype(f32)[:, :, None]

    def combine(early, late):
        return late[0] * early[0], late[0] * early[1] + late[1]

    _, h = lax.associative_scan(combine, (decay, push))
    y = jnp.einsum("tne,tn->te", h, C.astype(f32), precision=hi) + D.astype(f32) * x
    return (y * _silu(z.astype(f32))).astype(z.dtype), h[-1]  # padded rows leave the state as it was


def mamba_step(x, dt, B, C, z, A, D, h, fresh):
    """One position for ``S`` slots: ``x, dt, z [S, E]``, ``B, C [S, N]``,
    the state ``h [S, N, E]`` fp32, ``fresh [S]`` bool (a slot at position 0
    starts from nought). -> (``y [S, E]`` fp32, gated; the new state)."""
    f32 = jnp.float32
    x, dt = x.astype(f32), dt.astype(f32)
    h = jnp.where(fresh[:, None, None], 0.0, h)
    h = jnp.exp(dt[:, None, :] * A.astype(f32)[None]) * h + (dt * x)[:, None, :] * B.astype(f32)[:, :, None]
    y = jnp.sum(h * C.astype(f32)[:, :, None], axis=1) + D.astype(f32) * x
    return y * _silu(z.astype(f32)), h
