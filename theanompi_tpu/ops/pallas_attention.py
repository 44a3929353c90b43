"""Pallas fused flash attention — forward + custom-VJP backward TPU kernels.

BEYOND-PARITY EXTENSION. The 2016 reference has no attention op anywhere
(SURVEY.md §5.7); this module is the TPU-native fused kernel behind the
framework's long-context demonstrators. XLA's default lowering of
softmax attention materializes the [B, H, T, T] score matrix in HBM
twice (forward + transposed backward); the flash formulation (online
softmax over K/V blocks, Dao et al.) keeps scores in VMEM tiles and
streams K/V through them, making attention HBM-traffic-bound in O(T·D)
instead of O(T^2). Both passes are Pallas TPU kernels:

- forward: one kernel, grid over (batch·heads, query blocks); K/V loops
  run as ``fori_loop`` over VMEM slices; per-row logsumexp is saved as
  the softmax residual.
- backward: ONE kernel (``flash_bwd``), gridded over (batch·heads, key
  blocks), that recomputes each live probability tile from (q, k, lse)
  once and takes all three gradients from it (:func:`_tile_grads`, five
  products a tile): dk and dv leave by key block, dq is an fp32 output
  that stays whole in VMEM while the grid revisits it over the key
  blocks. The O(T^2) matrix never exists in either pass.

Numerics: EVERY product, forward and backward, runs in the INPUT dtype
on the MXU with fp32 accumulation (``preferred_element_type``); softmax
statistics, probability tiles until their one cast, and all gradient
accumulators are fp32 (tests/test_flash_backward.py walks the kernels'
jaxprs for it). For fp32 inputs the result matches the unfused reference
to float tolerance (tests/test_pallas_attention.py).

Layout contract matches :func:`theanompi_tpu.ops.ring_attention.
full_attention_reference`: ``[B, T, H, D] -> [B, Tq, H, D]``, optional
causal masking in GLOBAL position order (query i attends keys <= i),
optionally within a sliding ``window`` (keys > i - window; single shard
only), K/V allowed fewer heads than the queries (a divisor; repeated
outside the kernels).
Off-TPU the kernels run through the Pallas interpreter — identical
numerics on the CPU test meshes. ``TMPI_PALLAS=0`` falls back to the
unfused reference implementation.

K/V (and in backward Q) blocks for one batch·head row must fit VMEM:
fine through T ~ 8-16k at D <= 128; beyond that use
:func:`~theanompi_tpu.ops.ring_attention.ring_attention`, whose local
block this kernel exactly is (each device's ring hop folds one K/V
shard — the same online-softmax recurrence, distributed).

The ``block_q=block_k=512`` defaults come from a block sweep on an
earlier development backend (wider blocks amortize the accumulator
rescale; the causal block skip, :func:`_k_blocks_for`, drops the
all-masked half of the blocks, and under a window
:func:`_k_block_start` drops those older than the window). Measured on
the v5e (PERF.md section 5, PR 29), a live 512 x 512 tile inside the
training step: forward 1.70 us at the 136M shape (B=8, T=1024, 12 heads
of 64) and 1.33-1.46 us at T=8192 with heads of 128; backward 2.26 us
and 2.34-2.53 us, against 0.34 us of MXU time a product. The forward writes ``lse`` as
[BH, T, 1], which the chip pads to 128 lanes, and the backward wants it
as rows: the relayout between them takes 0.11 ms a layer at the 136M
shape. No speedup over the unfused lowering has been measured.
Checked without a chip: forward and backward compile for a v5e at both
shapes (tests/test_tpu_compile.py).

Long-context operation: ``flash_bwd`` keeps the FULL query side (q, dO,
lse, dsum) VMEM-resident per grid step. At T >= ``_BWD_2D_MIN_T`` the
backward is the same tile algorithm on a 3-D grid (``flash_bwd_2d``:
batch·heads, key blocks, query steps) that streams BOTH sides in blocks
and accumulates dk and dv across the query steps; only dq stays whole
(fp32: 4 MB a buffer at T=8192, D=128, so the call states its VMEM
limit, :func:`_bwd_2d_vmem_bytes`). The query steps of a key block count
from the first block that sees it (the offsets reach the index maps as
prefetched scalars, so this holds under the ring too) and are clamped to
the last block: a causally dead step is skipped in the kernel and copies
nothing (136 of 256 steps are live at T=8192 under full causal
attention). Under a WINDOW the grid is only as long as the window in
blocks (:func:`_win_q_steps`: 5 of 16 steps at T=8192, window 2048,
512-wide blocks), so blocks outside it are neither visited nor copied.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret
from theanompi_tpu.ops.pallas_util import use_pallas as _use_pallas

_NEG = -1e30  # masked-logit sentinel (finite: keeps exp/max NaN-free)


class _Cfg(NamedTuple):
    """Static kernel config (hashable: custom_vjp nondiff argument)."""

    causal: bool
    scale: float
    Tq: int  # real (unpadded) query length
    Tk: int  # real (unpadded) key length
    BQ: int
    BK: int
    interpret: bool
    # sliding window (causal only): query t sees keys s with
    # t - window < s <= t; None = every earlier key, as before
    window: Optional[int] = None


def _mask(cfg: _Cfg, i, j, q_off, k_off, key_major: bool = False):
    """[BQ, BK] validity of (query block i, key block j) ([BK, BQ] where
    ``key_major``): key PADDING is masked in local coordinates (padding
    is per-shard); the causal triangle compares GLOBAL positions ``q_off
    + local`` vs ``k_off + local`` — offsets are zero for single-shard
    use and ``rank * T`` under the ring."""
    shape = (cfg.BK, cfg.BQ) if key_major else (cfg.BQ, cfg.BK)
    lrow = i * cfg.BQ + lax.broadcasted_iota(jnp.int32, shape, int(key_major))
    lcol = j * cfg.BK + lax.broadcasted_iota(jnp.int32, shape, int(not key_major))
    valid = lcol < cfg.Tk
    if cfg.causal:
        valid = valid & ((q_off + lrow) >= (k_off + lcol))
    if cfg.window is not None:
        valid = valid & ((q_off + lrow) - (k_off + lcol) < cfg.window)
    return valid


def _k_blocks_for(cfg: _Cfg, i, nk, q_off, k_off):
    """Last k-block index (exclusive) query block ``i`` touches: under
    causal masking blocks strictly above the (global) diagonal are
    all-masked and skipped entirely — ~2x less work at large T, and
    whole fully-future K/V shards cost ~nothing under the ring."""
    if not cfg.causal:
        return nk
    jmax = (q_off - k_off + i * cfg.BQ + cfg.BQ - 1) // cfg.BK + 1
    return jnp.clip(jmax, 0, nk)


def _k_block_start(cfg: _Cfg, i, q_off, k_off):
    """First k-block index query block ``i`` touches: under a sliding
    window the blocks wholly older than the window of the block's FIRST
    row are all-masked and skipped, as the future ones are."""
    if cfg.window is None:
        return 0
    return jnp.maximum(0, q_off + i * cfg.BQ - (cfg.window - 1) - k_off) // cfg.BK


def _q_block_start(cfg: _Cfg, j, q_off, k_off):
    """First q-block index whose rows can (causally) see key block
    ``j`` — the dkv-kernel mirror of :func:`_k_blocks_for`."""
    if not cfg.causal:
        return 0
    return jnp.maximum(0, (k_off + j * cfg.BK - q_off) // cfg.BQ)


def _q_block_end(cfg: _Cfg, j, nq, q_off, k_off):
    """Last q-block index (exclusive) whose rows still hold key block
    ``j`` in their window — the mirror of :func:`_k_block_start`."""
    if cfg.window is None:
        return nq
    last = k_off + j * cfg.BK + cfg.BK - 1 + cfg.window - 1 - q_off  # last row that sees the block
    return jnp.clip(last // cfg.BQ + 1, 0, nq)


def _win_q_steps(cfg: _Cfg, nq: int, nk: int) -> int:
    """Single shard (offsets zero): static inner extent of the WINDOWED
    2-D grid, the most query blocks that hold one key block in theirs."""
    return max(min((j * cfg.BK + cfg.BK - 1 + cfg.window - 1) // cfg.BQ, nq - 1)
               - (j * cfg.BK) // cfg.BQ + 1 for j in range(nk))


FWD_NAME = "flash_fwd"  # the kernel's name in a device trace


def _fwd_kernel(cfg: _Cfg, qo_ref, ko_ref, q_ref, k_ref, v_ref, o_ref, lse_ref):
    i = pl.program_id(1)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    q = q_ref[0]  # [BQ, D], input dtype
    D = q.shape[-1]
    nk = k_ref.shape[1] // cfg.BK

    acc0 = jnp.zeros((cfg.BQ, D), jnp.float32)
    m0 = jnp.full((cfg.BQ, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((cfg.BQ, 1), jnp.float32)

    def body(j, carry):
        acc, m, l = carry
        k = k_ref[0, pl.ds(j * cfg.BK, cfg.BK), :]
        v = v_ref[0, pl.ds(j * cfg.BK, cfg.BK), :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        valid = _mask(cfg, i, j, q_off, k_off)
        s = jnp.where(valid, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        return acc, m_new, l

    acc, m, l = lax.fori_loop(
        _k_block_start(cfg, i, q_off, k_off),
        _k_blocks_for(cfg, i, nk, q_off, k_off), body, (acc0, m0, l0)
    )
    # l == 0 only for rows with no visible key at all — impossible
    # single-shard (causal: the diagonal key is local), but routine for
    # a ring hop whose whole K/V shard is in the causal future; the safe
    # divisor yields o = 0 and an effectively -inf lse, which the ring
    # merge weights to zero
    l_safe = jnp.maximum(l, 1e-37)
    o_ref[0] = (acc / l_safe).astype(o_ref.dtype)
    lse_ref[0] = m + jnp.log(l_safe)  # [BQ, 1]


BWD_NAME = "flash_bwd"  # the kernel's name in a device trace


def _tile_grads(cfg: _Cfg, i, j, q_off, k_off, q, do, lse, dsum, k, v):
    """The backward of ONE live (query block ``i``, key block ``j``) tile:
    ``s``, ``p``, ``dp`` and ``ds`` are made once, in fp32, and the tile's
    three partials ``(dq_i, dk_j, dv_j)`` are taken from them: five
    products on operands of the input dtype with fp32 accumulation. The
    tile is KEY-major (``s^T = k q^T``, [BK, BQ]; ``lse``, ``dsum`` are
    rows [1, BQ]), which gives ``p^T`` and ``ds^T`` as ``dv`` and ``dk``
    want them and leaves one transposed contraction, ``dq``'s."""
    st = lax.dot_general(
        k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * cfg.scale
    pt = jnp.where(_mask(cfg, i, j, q_off, k_off, True), jnp.exp(st - lse), 0.0)
    dv = lax.dot_general(
        pt.astype(do.dtype), do, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dpt = lax.dot_general(
        v, do.astype(v.dtype), (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    dst = (pt * (dpt - dsum) * cfg.scale).astype(q.dtype)
    dk = lax.dot_general(
        dst, q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    dq = lax.dot_general(
        dst.astype(k.dtype), k, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return dq, dk, dv


def _bwd_kernel(cfg: _Cfg, qo_ref, ko_ref, q_ref, do_ref, lse_ref, dsum_ref,
                k_ref, v_ref, dq_ref, dk_ref, dv_ref):
    """Grid (BH, key blocks): the queries' side whole in VMEM, ``dk`` and
    ``dv`` by key block, and ``dq`` whole and resident, revisited over
    the key blocks: zeroed at the first, each live tile adds its part."""
    j = pl.program_id(1)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    k = k_ref[0]
    v = v_ref[0]
    nq = q_ref.shape[1] // cfg.BQ

    @pl.when(j == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    def body(i, carry):
        dk, dv = carry
        rows = pl.ds(i * cfg.BQ, cfg.BQ)
        dq_i, dk_i, dv_i = _tile_grads(
            cfg, i, j, q_off, k_off, q_ref[0, rows, :], do_ref[0, rows, :],
            lse_ref[0, :, rows], dsum_ref[0, :, rows], k, v)
        dq_ref[0, rows, :] += dq_i
        return dk + dk_i, dv + dv_i

    # causal: query blocks strictly below this key block's diagonal see
    # none of it, and under a window those past it: neither is visited
    dk, dv = lax.fori_loop(
        _q_block_start(cfg, j, q_off, k_off),
        _q_block_end(cfg, j, nq, q_off, k_off), body,
        (jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32)),
    )
    dk_ref[0] = dk  # f32: ring hops accumulate partials losslessly
    dv_ref[0] = dv


# Threshold (local sequence length) at which the backward streams BOTH
# sides in blocks (``_bwd_kernel_2d``) where ``_bwd_kernel`` keeps the
# whole query side VMEM-resident per grid step: the same tiles, another
# residency. Kept at 8192 (not lower): below it the resident form fits
# (it compiles for a v5e through T=8064 at D=128) and its in-kernel
# fori_loop visits live tiles only, with no grid step, no block copy and
# no dk/dv read-modify-write a tile. Tests monkeypatch this to exercise
# the 2-D form at small T.
_BWD_2D_MIN_T = 8192


BWD_2D_NAME = "flash_bwd_2d"  # the kernel's name in a device trace


def _bwd_kernel_2d(cfg: _Cfg, qo_ref, ko_ref, q_ref, do_ref, lse_ref, dsum_ref,
                   k_ref, v_ref, dq_ref, dk_ref, dv_ref):
    """:func:`_bwd_kernel` with BOTH sides blocked: grid (BH, key blocks,
    query steps), the query steps innermost and counted from the first
    block that sees the key block, so ``dk`` and ``dv`` accumulate in
    VMEM over them; ``dq`` is whole and resident over a batch-head."""
    j = pl.program_id(1)
    ii = pl.program_id(2)
    q_off, k_off = qo_ref[0], ko_ref[0]
    nq = dq_ref.shape[1] // cfg.BQ
    i = _q_block_start(cfg, j, q_off, k_off) + ii

    @pl.when((j == 0) & (ii == 0))
    def _init_dq():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    @pl.when(ii == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    @pl.when(i < _q_block_end(cfg, j, nq, q_off, k_off))
    def _acc():
        dq_i, dk_i, dv_i = _tile_grads(
            cfg, i, j, q_off, k_off, q_ref[0], do_ref[0], lse_ref[0],
            dsum_ref[0], k_ref[0], v_ref[0])
        dq_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :] += dq_i
        dk_ref[0] += dk_i
        dv_ref[0] += dv_i


def _zero_offs():
    z = jnp.zeros((1, 1), jnp.int32)
    return z, z


def _as_off(x) -> jax.Array:
    return jnp.reshape(jnp.asarray(x, jnp.int32), (1, 1))


def _smem_spec():
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((1, 1), lambda b, i: (0, 0), memory_space=pltpu.SMEM)


def _q_major(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(shape, lambda b, i: (b, i) + (0,) * (len(shape) - 2),
                        memory_space=pltpu.VMEM)


def _full(shape):
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec(shape, lambda b, i: (b,) + (0,) * (len(shape) - 1),
                        memory_space=pltpu.VMEM)


def _fwd(cfg: _Cfg, q3, k3, v3, q_off, k_off):
    """Padded [BH, T_pad, D] flash forward -> (o, lse[BH, T_pad, 1])."""
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, cfg),
        grid=(BH, Tqp // cfg.BQ),
        in_specs=[
            _smem_spec(),                     # q_off
            _smem_spec(),                     # k_off
            _q_major((1, cfg.BQ, D)),         # q
            _full((1, Tkp, D)),               # k
            _full((1, Tkp, D)),               # v
        ],
        out_specs=(
            _q_major((1, cfg.BQ, D)),
            # [BH, Tqp, 1]: a trailing singleton lane keeps the block's
            # last-two dims Mosaic-legal ((BQ, 1) == (div 8, full dim))
            _q_major((1, cfg.BQ, 1)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tqp, D), q3.dtype),
            jax.ShapeDtypeStruct((BH, Tqp, 1), jnp.float32),
        ),
        name=FWD_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, k3, v3)
    return o, lse


def _bwd_call(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    """(dq, dk, dv) partials (f32) of these queries against one K/V
    shard, given the GLOBAL lse/dsum as rows [BH, 1, Tqp], in one kernel."""
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    return pl.pallas_call(
        functools.partial(_bwd_kernel, cfg),
        grid=(BH, Tkp // cfg.BK),
        in_specs=[
            _smem_spec(), _smem_spec(),
            _full((1, Tqp, D)),               # q
            _full((1, Tqp, D)),               # dO
            _full((1, 1, Tqp)),               # lse, as a row
            _full((1, 1, Tqp)),               # dsum
            _q_major((1, cfg.BK, D)),         # k block
            _q_major((1, cfg.BK, D)),         # v block
        ],
        out_specs=(
            _full((1, Tqp, D)),               # dq: revisited over the key blocks
            _q_major((1, cfg.BK, D)),
            _q_major((1, cfg.BK, D)),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tqp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
        ),
        name=BWD_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, g, lse, dsum, k3, v3)


def _dsum_of(g, o):
    """Per-row sum(dO * O) — the softmax-gradient correction term
    (padded rows of g are zero, so their dsum is zero); [BH, Tqp, 1]."""
    return jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )


def _bwd_call_2d(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    """:func:`_bwd_call` with both sides streamed in blocks."""
    from jax.experimental.pallas import tpu as pltpu

    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    nq, nk = Tqp // cfg.BQ, Tkp // cfg.BK

    def q_side(shape):
        # the step's query block, clamped into the blocks there are: a
        # step past the last live block is dead, the kernel skips it and
        # its block is not copied again
        def pick(b, x, y, qo, ko):
            i = jnp.minimum(_q_block_start(cfg, x, qo[0], ko[0]) + y, nq - 1)
            return (b, 0, i) if shape[1] == 1 else (b, i, 0)  # a row, or rows

        return pl.BlockSpec(shape, pick, memory_space=pltpu.VMEM)

    def k_side(shape):
        return pl.BlockSpec(shape, lambda b, x, y, qo, ko: (b, x, 0),
                            memory_space=pltpu.VMEM)

    return pl.pallas_call(
        functools.partial(_bwd_kernel_2d, cfg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,            # q_off, k_off: the index maps read them
            grid=(BH, nk, nq if cfg.window is None else _win_q_steps(cfg, nq, nk)),
            in_specs=[
                q_side((1, cfg.BQ, D)),       # q
                q_side((1, cfg.BQ, D)),       # dO
                q_side((1, 1, cfg.BQ)),       # lse, as a row
                q_side((1, 1, cfg.BQ)),       # dsum
                k_side((1, cfg.BK, D)),       # k block
                k_side((1, cfg.BK, D)),       # v block
            ],
            out_specs=(
                pl.BlockSpec((1, Tqp, D), lambda b, x, y, qo, ko: (b, 0, 0),
                             memory_space=pltpu.VMEM),
                k_side((1, cfg.BK, D)),
                k_side((1, cfg.BK, D)),
            ),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tqp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_bwd_2d_vmem_bytes(Tqp, D)),
        name=BWD_2D_NAME,
        interpret=cfg.interpret,
    )(q_off.reshape(1), k_off.reshape(1), q3, g, lse, dsum, k3, v3)


def _bwd_2d_vmem_bytes(Tqp: int, D: int) -> int:
    """What :func:`_bwd_kernel_2d` may take of VMEM: the resident ``dq``
    (fp32, lanes padded to 128, two buffers), and 24 MiB for the blocks
    of the two sides and the tile's fp32 intermediates."""
    return 2 * Tqp * _ceil_to(D, 128) * 4 + (24 << 20)


def _bwd_dispatch(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    """(dq, dk, dv) partials (f32) via ``flash_bwd``, or the block-streamed
    ``flash_bwd_2d`` when either side's LOCAL length reaches
    _BWD_2D_MIN_T — the one dispatch shared by the local backward and
    every ring hop (a ring shard of 8k+ would otherwise rebuild the
    full-residency kernel the threshold exists to avoid)."""
    call = _bwd_call_2d if max(q3.shape[1], k3.shape[1]) >= _BWD_2D_MIN_T else _bwd_call
    # the tiles are key-major: the per-row statistics [BH, Tqp, 1] go in as rows
    lse, dsum = (x.reshape(x.shape[0], 1, x.shape[1]) for x in (lse, dsum))
    return call(cfg, q3, k3, v3, g, lse, dsum, q_off, k_off)


def _bwd(cfg: _Cfg, q3, k3, v3, o, lse, g):
    q_off, k_off = _zero_offs()
    dsum = _dsum_of(g, o)
    dq, dk, dv = _bwd_dispatch(cfg, q3, k3, v3, g, lse, dsum, q_off, k_off)
    return dq.astype(q3.dtype), dk.astype(k3.dtype), dv.astype(v3.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _flash(cfg: _Cfg, q3, k3, v3):
    o, _ = _fwd(cfg, q3, k3, v3, *_zero_offs())
    return o


def _flash_vjp_fwd(cfg, q3, k3, v3):
    o, lse = _fwd(cfg, q3, k3, v3, *_zero_offs())
    return o, (q3, k3, v3, o, lse)


def _flash_vjp_bwd(cfg, res, g):
    return _bwd(cfg, *res, g)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def _to_heads_major(x, B, T, H, D):
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(B * H, T, D)


def _prepare(q, k, v, causal, scale, precision, block_q, block_k, window=None):
    """Shared prologue of the public entry points: precision upcast,
    block sizing, heads-major reshape, padding. Returns
    ``(cfg, q3, k3, v3, shape_meta)`` where shape_meta =
    ``(B, Tq, H, D, out_dtype)`` for :func:`_finish`."""
    out_dtype = q.dtype
    if precision in (lax.Precision.HIGHEST, "highest", "float32"):
        q, k, v = (t.astype(jnp.float32) for t in (q, k, v))

    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    BQ, BK = min(block_q, _ceil_to(Tq, 8)), min(block_k, _ceil_to(Tk, 8))
    Tqp, Tkp = _ceil_to(Tq, BQ), _ceil_to(Tk, BK)
    cfg = _Cfg(bool(causal), float(sc), Tq, Tk, BQ, BK, _interpret(),
               None if window is None else int(window))

    q3 = _to_heads_major(q, B, Tq, H, D)
    k3 = _to_heads_major(k, B, Tk, H, D)
    v3 = _to_heads_major(v, B, Tk, H, D)
    if Tqp != Tq:
        q3 = jnp.pad(q3, ((0, 0), (0, Tqp - Tq), (0, 0)))
    if Tkp != Tk:
        k3 = jnp.pad(k3, ((0, 0), (0, Tkp - Tk), (0, 0)))
        v3 = jnp.pad(v3, ((0, 0), (0, Tkp - Tk), (0, 0)))
    return cfg, q3, k3, v3, (B, Tq, H, D, out_dtype)


def _finish(o_padded, shape_meta):
    """Shared epilogue: unpad, restore [B, Tq, H, D], original dtype."""
    B, Tq, H, D, out_dtype = shape_meta
    o = o_padded[:, :Tq]
    return jnp.transpose(o.reshape(B, H, Tq, D), (0, 2, 1, 3)).astype(out_dtype)


def flash_attention(
    q: jax.Array,  # [B, Tq, H, D]
    k: jax.Array,  # [B, Tk, H, D]
    v: jax.Array,  # [B, Tk, H, D]
    causal: bool = False,
    scale: Optional[float] = None,
    precision=None,
    *,
    block_q: int = 512,
    block_k: int = 512,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused blockwise attention, differentiable: drop-in for
    :func:`~theanompi_tpu.ops.ring_attention.full_attention_reference`.

    ``window`` (causal only): query ``t`` sees keys ``s`` with ``t -
    window < s <= t``; blocks wholly outside the window are skipped in
    the forward and in both forms of the backward. ``k``/``v`` may carry fewer
    heads than ``q`` (a divisor): query head ``i`` reads K/V head ``i //
    (H / H_kv)``; they are repeated to the query heads outside the kernel
    (the backward of the repeat sums each group).

    Sequence lengths are padded up to the block sizes internally
    (padded keys masked, padded query rows discarded); head dim is used
    as-is (Mosaic pads lanes — D a multiple of 128 is fastest).

    ``precision``: matmuls run in the INPUT dtype with fp32 accumulation
    (softmax statistics are always fp32); ``Precision.HIGHEST`` upcasts
    the q/k/v tiles to fp32 — same numerics knob as the unfused
    reference, at ~2x matmul cost for bf16 inputs.
    """
    if window is not None and not causal:
        raise ValueError("flash_attention: a sliding window needs causal=True")
    if k.shape[2] != q.shape[2]:
        if q.shape[2] % k.shape[2]:
            raise ValueError(
                f"flash_attention: {q.shape[2]} query heads over {k.shape[2]} K/V heads")
        k, v = (jnp.repeat(t, q.shape[2] // t.shape[2], axis=2) for t in (k, v))
    if not _use_pallas():
        from theanompi_tpu.ops.ring_attention import full_attention_reference

        return full_attention_reference(
            q, k, v, causal=causal, scale=scale, precision=precision, window=window
        )

    cfg, q3, k3, v3, meta = _prepare(
        q, k, v, causal, scale, precision, block_q, block_k, window
    )
    return _finish(_flash(cfg, q3, k3, v3), meta)


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


# -- ring + flash: sequence-parallel attention with fused local folds -------


class _RingCfg(NamedTuple):
    cfg: _Cfg
    axis: str


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def _ring_fwd_parts(rcfg: _RingCfg, q3, k3, v3):
    """Distributed flash forward: each hop folds one K/V shard with the
    fused kernel, producing a per-hop (o_j, lse_j); hops merge by the
    logsumexp-rescale law. Exact (not approximate) global softmax."""
    cfg, ax = rcfg.cfg, rcfg.axis
    n = lax.psum(1, ax)
    rank = lax.axis_index(ax)
    BH, Tqp, D = q3.shape
    q_off = _as_off(rank * cfg.Tq)

    acc0 = jnp.zeros((BH, Tqp, D), jnp.float32)
    m0 = jnp.full((BH, Tqp, 1), _NEG, jnp.float32)
    l0 = jnp.zeros((BH, Tqp, 1), jnp.float32)
    kv0 = jnp.stack([k3, v3])
    perm = _ring_perm(n)

    def hop(carry, t):
        acc, m, l, kv = carry
        src = jnp.mod(rank - t, n)
        o_j, lse_j = _fwd(cfg, q3, kv[0], kv[1], q_off, _as_off(src * cfg.Tk))
        # merge block j into the running (acc, m, l): a fully-masked hop
        # has lse_j ~ -1e30 and o_j = 0, weighting to zero
        m_new = jnp.maximum(m, lse_j)
        w_old = jnp.exp(m - m_new)
        w_new = jnp.exp(lse_j - m_new)
        acc = acc * w_old + o_j.astype(jnp.float32) * w_new
        l = l * w_old + w_new
        kv = lax.ppermute(kv, ax, perm)
        return (acc, m_new, l, kv), None

    (acc, m, l, _), _ = lax.scan(hop, (acc0, m0, l0, kv0), jnp.arange(n))
    l_safe = jnp.maximum(l, 1e-37)
    o = (acc / l_safe).astype(q3.dtype)
    lse = m + jnp.log(l_safe)
    return o, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _ring_flash(rcfg: _RingCfg, q3, k3, v3):
    return _ring_fwd_parts(rcfg, q3, k3, v3)[0]


def _ring_flash_vjp_fwd(rcfg, q3, k3, v3):
    o, lse = _ring_fwd_parts(rcfg, q3, k3, v3)
    return o, (q3, k3, v3, o, lse)


def _ring_flash_vjp_bwd(rcfg, res, g):
    """Ring backward (Liu et al. blockwise formulation): dq accumulates
    locally across hops; (dk, dv) partials travel WITH their K/V shard
    (one extra ppermute pair per hop) and are home after the n-th
    rotation. The per-hop kernels take the GLOBAL lse/dsum, so each
    partial is exact — fp32 accumulation end to end."""
    cfg, ax = rcfg.cfg, rcfg.axis
    q3, k3, v3, o, lse = res
    n = lax.psum(1, ax)
    rank = lax.axis_index(ax)
    dsum = _dsum_of(g, o)
    q_off = _as_off(rank * cfg.Tq)
    perm = _ring_perm(n)

    dq0 = jnp.zeros(q3.shape, jnp.float32)
    kv0 = jnp.stack([k3, v3])
    dkv0 = jnp.zeros(kv0.shape, jnp.float32)

    def hop(carry, t):
        dq, kv, dkv = carry
        src = jnp.mod(rank - t, n)
        k_off = _as_off(src * cfg.Tk)
        dq_j, dk_j, dv_j = _bwd_dispatch(
            cfg, q3, kv[0], kv[1], g, lse, dsum, q_off, k_off
        )
        dq = dq + dq_j
        dkv = dkv + jnp.stack([dk_j, dv_j])
        kv = lax.ppermute(kv, ax, perm)
        dkv = lax.ppermute(dkv, ax, perm)
        return (dq, kv, dkv), None

    (dq, _, dkv), _ = lax.scan(hop, (dq0, kv0, dkv0), jnp.arange(n))
    return dq.astype(q3.dtype), dkv[0].astype(k3.dtype), dkv[1].astype(v3.dtype)


_ring_flash.defvjp(_ring_flash_vjp_fwd, _ring_flash_vjp_bwd)


def ring_flash_attention(
    q: jax.Array,  # [B, T_local, H, D] — this shard's queries
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    precision=None,
    *,
    block_q: int = 512,
    block_k: int = 512,
) -> jax.Array:
    """Sequence-parallel ring attention whose per-hop fold IS the fused
    flash kernel — the composition of
    :func:`~theanompi_tpu.ops.ring_attention.ring_attention` (K/V
    rotation over ``axis_name``, one ppermute per hop, O(T/n) memory)
    with this module's Pallas kernels (no [T_local, T_local] score
    materialization per hop either). Must run inside ``shard_map`` with
    the sequence dim sharded over ``axis_name``; causal masking is in
    GLOBAL position order via the kernels' offset scalars, and the
    causal block skip makes fully-future K/V shards cost ~nothing.
    Differentiable via a whole-ring custom VJP (backward rings the K/V
    shards again, dk/dv partials traveling with them).

    ``precision=HIGHEST`` upcasts tiles to fp32 as in
    :func:`flash_attention`. ``TMPI_PALLAS=0`` falls back to the
    unfused :func:`~theanompi_tpu.ops.ring_attention.ring_attention`.
    """
    if not _use_pallas():
        from theanompi_tpu.ops.ring_attention import ring_attention

        return ring_attention(
            q, k, v, axis_name, causal=causal, scale=scale, precision=precision
        )

    cfg, q3, k3, v3, meta = _prepare(
        q, k, v, causal, scale, precision, block_q, block_k
    )
    return _finish(_ring_flash(_RingCfg(cfg, axis_name), q3, k3, v3), meta)
