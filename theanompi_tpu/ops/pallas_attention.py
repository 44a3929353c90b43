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
- backward: the classic two-kernel split — a dq kernel gridded over
  query blocks and a dk/dv kernel gridded over key blocks — each
  recomputing the probability tiles from (q, k, lse) so the O(T^2)
  matrix never exists in either pass.

Numerics: the q·k^T and p·v matmuls run in the INPUT dtype on the MXU
with fp32 accumulation (``preferred_element_type``); softmax statistics,
probability tiles, and all gradient accumulators are fp32. For fp32
inputs the result matches the unfused reference to float tolerance
(tests/test_pallas_attention.py).

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
the v5e (PERF.md): 11.92 % of the bf16 peak over forward and backward
at the 136M shape (B=8, T=1024, 12 heads of 64; PR 26), and what the
windowed layers at T=8192 and head size 128 reach is in PERF.md section
5 (PR 28). No speedup over the unfused lowering has been measured.
Checked without a chip: forward and backward compile for a v5e at both
shapes (tests/test_tpu_compile.py).

Long-context operation: the classic backward kernels keep the FULL
opposite sequence VMEM-resident per grid step, which overflows the
16 MB scoped VMEM stack at T >= 8192 (a compile failure). The fix is
structural: at T >= ``_BWD_2D_MIN_T`` the backward dispatches to
2-D-grid kernels (``_dq_kernel_2d``/``_dkv_kernel_2d``) that stream
BOTH sides in blocks and accumulate outputs across sequential grid
revisits — residency is O(block x D) regardless of T, no compiler
flags, and 512-wide blocks stay usable. The 1-D kernels keep the
short-T regime (their in-register fori_loop skips causal-dead blocks
entirely; the 2-D grid only masks the causally dead ones: it still
steps through them and copies their blocks). Under a WINDOW the 2-D
grids are only as long as the window in blocks (:func:`_win_steps`: 5
of 16 steps at T=8192, window 2048, 512-wide blocks), their index maps
counting from the window's first block, so blocks outside it are
skipped, not masked.
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


def _mask(cfg: _Cfg, i, j, q_off, k_off):
    """[BQ, BK] validity of (query block i, key block j): key PADDING is
    masked in local coordinates (padding is per-shard); the causal
    triangle compares GLOBAL positions ``q_off + local`` vs ``k_off +
    local`` — offsets are zero for single-shard use and ``rank * T``
    under the ring."""
    lrow = i * cfg.BQ + lax.broadcasted_iota(jnp.int32, (cfg.BQ, cfg.BK), 0)
    lcol = j * cfg.BK + lax.broadcasted_iota(jnp.int32, (cfg.BQ, cfg.BK), 1)
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


def _n_blocks(T: int, B: int) -> int:
    return -(-T // B)


def _win_k_first(cfg: _Cfg, i):
    """Single shard (offsets zero): first key block in the window of
    query block ``i`` — what the windowed 2-D grid counts its k steps from."""
    return _k_block_start(cfg, i, 0, 0)


def _win_q_first(cfg: _Cfg, j):
    return _q_block_start(cfg, j, 0, 0)


def _win_steps(cfg: _Cfg) -> tuple[int, int]:
    """Static inner extents of the windowed 2-D grids: the most key
    blocks any query block's window touches, and the most query blocks
    that hold one key block in theirs."""
    nq, nk = _n_blocks(cfg.Tq, cfg.BQ), _n_blocks(cfg.Tk, cfg.BK)
    w = cfg.window - 1
    nj = max(min((i * cfg.BQ + cfg.BQ - 1) // cfg.BK, nk - 1)
             - max(i * cfg.BQ - w, 0) // cfg.BK + 1 for i in range(nq))
    ni = max(min((j * cfg.BK + cfg.BK - 1 + w) // cfg.BQ, nq - 1)
             - (j * cfg.BK) // cfg.BQ + 1 for j in range(nk))
    return nj, ni


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


DQ_NAME = "flash_bwd_dq"  # the kernel's name in a device trace


def _dq_kernel(cfg: _Cfg, qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
               lse_ref, dsum_ref, dq_ref):
    i = pl.program_id(1)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    q = q_ref[0]
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0]  # [BQ, 1]
    dsum = dsum_ref[0]
    nk = k_ref.shape[1] // cfg.BK

    def body(j, dq):
        k = k_ref[0, pl.ds(j * cfg.BK, cfg.BK), :]
        v = v_ref[0, pl.ds(j * cfg.BK, cfg.BK), :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        p = jnp.where(_mask(cfg, i, j, q_off, k_off), jnp.exp(s - lse), 0.0)
        dp = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - dsum) * cfg.scale).astype(k.dtype)
        return dq + lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )

    dq = lax.fori_loop(
        _k_block_start(cfg, i, q_off, k_off),
        _k_blocks_for(cfg, i, nk, q_off, k_off), body,
        jnp.zeros(q.shape, jnp.float32),
    )
    dq_ref[0] = dq  # f32: ring hops accumulate partials losslessly


DKV_NAME = "flash_bwd_dkv"  # the kernel's name in a device trace


def _dkv_kernel(cfg: _Cfg, qo_ref, ko_ref, q_ref, do_ref, lse_ref, dsum_ref,
                k_ref, v_ref, dk_ref, dv_ref):
    j = pl.program_id(1)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    k = k_ref[0]
    v = v_ref[0]
    nq = q_ref.shape[1] // cfg.BQ

    def body(i, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :]
        do = do_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :]   # [BQ, 1]
        dsum = dsum_ref[0, pl.ds(i * cfg.BQ, cfg.BQ), :]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        p = jnp.where(
            _mask(cfg, i, j, q_off, k_off), jnp.exp(s - lse), 0.0
        )  # [BQ, BK]
        dv = dv + lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - dsum) * cfg.scale).astype(q.dtype)
        dk = dk + lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        return dk, dv

    dk0 = jnp.zeros(k.shape, jnp.float32)
    dv0 = jnp.zeros(v.shape, jnp.float32)
    # causal: query blocks strictly below this key block's diagonal see
    # none of it — start at the first overlapping block
    dk, dv = lax.fori_loop(
        _q_block_start(cfg, j, q_off, k_off),
        _q_block_end(cfg, j, nq, q_off, k_off), body, (dk0, dv0)
    )
    dk_ref[0] = dk  # f32: ring hops accumulate partials losslessly
    dv_ref[0] = dv


# Threshold (local sequence length) above which the backward runs on the
# 2-D-grid kernels below: the classic 1-D kernels keep the FULL opposite
# sequence VMEM-resident per grid step, which overflows the scoped VMEM
# stack at long T (module docstring); the 2-D variants stream both sides
# in blocks, so residency is O(BQ x D + BK x D) regardless of T. Kept at
# 8192 (not lower) because the 1-D kernels' in-register fori_loop avoids
# the 2-D grid's per-(i, j) output read-modify-write and its masked
# causal-skip steps in the short-T regime where they already fit.
# Tests monkeypatch this to exercise the 2-D path at small T.
_BWD_2D_MIN_T = 8192


DQ_2D_NAME = "flash_bwd_dq_2d"  # the kernel's name in a device trace


def _dq_kernel_2d(cfg: _Cfg, qo_ref, ko_ref, q_ref, k_ref, v_ref, do_ref,
                  lse_ref, dsum_ref, dq_ref):
    """dq with BOTH sides blocked: grid (BH, q blocks, k blocks), the
    k dim innermost so ``dq_ref``'s block is revisited sequentially and
    accumulates in VMEM (written back when the q index advances)."""
    i = pl.program_id(1)
    jj = pl.program_id(2)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    if cfg.window is None:
        j, nk = jj, pl.num_programs(2)
    else:
        # the grid's k dim spans the window's blocks only (_dq_call_2d)
        j, nk = _win_k_first(cfg, i) + jj, _n_blocks(cfg.Tk, cfg.BK)

    @pl.when(jj == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    jmax = _k_blocks_for(cfg, i, nk, q_off, k_off)

    @pl.when(j < jmax)
    def _acc():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dsum = dsum_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        p = jnp.where(_mask(cfg, i, j, q_off, k_off), jnp.exp(s - lse), 0.0)
        dp = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - dsum) * cfg.scale).astype(k.dtype)
        dq_ref[0] += lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )


DKV_2D_NAME = "flash_bwd_dkv_2d"  # the kernel's name in a device trace


def _dkv_kernel_2d(cfg: _Cfg, qo_ref, ko_ref, q_ref, do_ref, lse_ref,
                   dsum_ref, k_ref, v_ref, dk_ref, dv_ref):
    """(dk, dv) with both sides blocked: grid (BH, k blocks, q blocks),
    the q dim innermost so the per-key-block outputs accumulate in VMEM
    across the q sweep."""
    j = pl.program_id(1)
    ii = pl.program_id(2)
    q_off, k_off = qo_ref[0, 0], ko_ref[0, 0]
    # windowed: the grid's q dim spans the blocks that see key block j
    i = ii if cfg.window is None else _win_q_first(cfg, j) + ii

    @pl.when(ii == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    istart = _q_block_start(cfg, j, q_off, k_off)
    if cfg.window is None:
        live = i >= istart
    else:
        live = (i >= istart) & (
            i < _q_block_end(cfg, j, _n_blocks(cfg.Tq, cfg.BQ), q_off, k_off))

    @pl.when(live)
    def _acc():
        k = k_ref[0]
        v = v_ref[0]
        q = q_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]
        dsum = dsum_ref[0]
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * cfg.scale
        p = jnp.where(_mask(cfg, i, j, q_off, k_off), jnp.exp(s - lse), 0.0)
        dv_ref[0] += lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = lax.dot_general(
            do.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = (p * (dp - dsum) * cfg.scale).astype(q.dtype)
        dk_ref[0] += lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )


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


# NOTE: _smem_spec3/_by mirror _smem_spec/_q_major/_full for the 3-dim
# (b, x, y) grids of the 2-D backward kernels — the index-map arity is
# part of pallas_call's contract, so the families cannot share a lambda;
# keep the two groups in sync when changing memory spaces or layouts.
def _smem_spec3():
    from jax.experimental.pallas import tpu as pltpu

    return pl.BlockSpec((1, 1), lambda b, x, y: (0, 0),
                        memory_space=pltpu.SMEM)


def _by(which: str, shape):
    """3-index-grid block spec selecting the grid dim that indexes this
    operand's second axis: 'x' = grid dim 1, 'y' = grid dim 2."""
    from jax.experimental.pallas import tpu as pltpu

    pick = (lambda b, x, y: (b, x) + (0,) * (len(shape) - 2)) if which == "x" \
        else (lambda b, x, y: (b, y) + (0,) * (len(shape) - 2))
    return pl.BlockSpec(shape, pick, memory_space=pltpu.VMEM)


def _by_window(shape, first, n):
    """The 'y' operand of a WINDOWED 2-D grid: grid dim 2 counts from
    ``first(x)``, the first block in the window of grid dim 1's block,
    clamped into the ``n`` blocks there are (a clamped step is dead: the
    kernel skips it and the block is not copied again)."""
    from jax.experimental.pallas import tpu as pltpu

    def pick(b, x, y):
        return (b, jnp.minimum(first(x) + y, n - 1)) + (0,) * (len(shape) - 2)

    return pl.BlockSpec(shape, pick, memory_space=pltpu.VMEM)


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


def _dq_call(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    """dq partial (f32) for one K/V shard, given the GLOBAL lse/dsum."""
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    return pl.pallas_call(
        functools.partial(_dq_kernel, cfg),
        grid=(BH, Tqp // cfg.BQ),
        in_specs=[
            _smem_spec(), _smem_spec(),
            _q_major((1, cfg.BQ, D)),         # q
            _full((1, Tkp, D)),               # k
            _full((1, Tkp, D)),               # v
            _q_major((1, cfg.BQ, D)),         # dO
            _q_major((1, cfg.BQ, 1)),         # lse
            _q_major((1, cfg.BQ, 1)),         # dsum
        ],
        out_specs=_q_major((1, cfg.BQ, D)),
        out_shape=jax.ShapeDtypeStruct((BH, Tqp, D), jnp.float32),
        name=DQ_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, k3, v3, g, lse, dsum)


def _dkv_call(cfg: _Cfg, q3, g, lse, dsum, k3, v3, q_off, k_off):
    """(dk, dv) partials (f32) for one K/V shard vs these queries."""
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    return pl.pallas_call(
        functools.partial(_dkv_kernel, cfg),
        grid=(BH, Tkp // cfg.BK),
        in_specs=[
            _smem_spec(), _smem_spec(),
            _full((1, Tqp, D)),               # q
            _full((1, Tqp, D)),               # dO
            _full((1, Tqp, 1)),               # lse
            _full((1, Tqp, 1)),               # dsum
            _q_major((1, cfg.BK, D)),         # k block
            _q_major((1, cfg.BK, D)),         # v block
        ],
        out_specs=(_q_major((1, cfg.BK, D)), _q_major((1, cfg.BK, D))),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
        ),
        name=DKV_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, g, lse, dsum, k3, v3)


def _dsum_of(g, o):
    """Per-row sum(dO * O) — the softmax-gradient correction term
    (padded rows of g are zero, so their dsum is zero); [BH, Tqp, 1]."""
    return jnp.sum(
        g.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True
    )


def _dq_call_2d(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    nk = Tkp // cfg.BK
    if cfg.window is None:
        kv = _by("y", (1, cfg.BK, D))
    else:
        # skip, not mask, the blocks outside the window: the k dim of
        # the grid is as long as the widest window in blocks
        nk = _win_steps(cfg)[0]
        kv = _by_window((1, cfg.BK, D), functools.partial(_win_k_first, cfg),
                        Tkp // cfg.BK)
    return pl.pallas_call(
        functools.partial(_dq_kernel_2d, cfg),
        grid=(BH, Tqp // cfg.BQ, nk),
        in_specs=[
            _smem_spec3(), _smem_spec3(),
            _by("x", (1, cfg.BQ, D)),         # q
            kv,                               # k
            kv,                               # v
            _by("x", (1, cfg.BQ, D)),         # dO
            _by("x", (1, cfg.BQ, 1)),         # lse
            _by("x", (1, cfg.BQ, 1)),         # dsum
        ],
        out_specs=_by("x", (1, cfg.BQ, D)),   # revisited over the k dim
        out_shape=jax.ShapeDtypeStruct((BH, Tqp, D), jnp.float32),
        name=DQ_2D_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, k3, v3, g, lse, dsum)


def _dkv_call_2d(cfg: _Cfg, q3, g, lse, dsum, k3, v3, q_off, k_off):
    BH, Tqp, D = q3.shape
    Tkp = k3.shape[1]
    nq = Tqp // cfg.BQ
    if cfg.window is None:
        qside = functools.partial(_by, "y")
    else:
        nq = _win_steps(cfg)[1]
        qside = functools.partial(
            _by_window, first=functools.partial(_win_q_first, cfg), n=Tqp // cfg.BQ)
    return pl.pallas_call(
        functools.partial(_dkv_kernel_2d, cfg),
        grid=(BH, Tkp // cfg.BK, nq),
        in_specs=[
            _smem_spec3(), _smem_spec3(),
            qside((1, cfg.BQ, D)),            # q
            qside((1, cfg.BQ, D)),            # dO
            qside((1, cfg.BQ, 1)),            # lse
            qside((1, cfg.BQ, 1)),            # dsum
            _by("x", (1, cfg.BK, D)),         # k block
            _by("x", (1, cfg.BK, D)),         # v block
        ],
        out_specs=(_by("x", (1, cfg.BK, D)), _by("x", (1, cfg.BK, D))),
        out_shape=(
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
            jax.ShapeDtypeStruct((BH, Tkp, D), jnp.float32),
        ),
        name=DKV_2D_NAME,
        interpret=cfg.interpret,
    )(q_off, k_off, q3, g, lse, dsum, k3, v3)


def _bwd_dispatch(cfg: _Cfg, q3, k3, v3, g, lse, dsum, q_off, k_off):
    """(dq, dk, dv) partials via the 1-D kernels, or the block-streamed
    2-D kernels when either side's LOCAL length reaches _BWD_2D_MIN_T —
    the one dispatch shared by the local backward and every ring hop
    (a ring shard of 8k+ would otherwise rebuild the full-residency
    kernels the threshold exists to avoid)."""
    if max(q3.shape[1], k3.shape[1]) >= _BWD_2D_MIN_T:
        dq = _dq_call_2d(cfg, q3, k3, v3, g, lse, dsum, q_off, k_off)
        dk, dv = _dkv_call_2d(cfg, q3, g, lse, dsum, k3, v3, q_off, k_off)
    else:
        dq = _dq_call(cfg, q3, k3, v3, g, lse, dsum, q_off, k_off)
        dk, dv = _dkv_call(cfg, q3, g, lse, dsum, k3, v3, q_off, k_off)
    return dq, dk, dv


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
    the forward and in both backward forms. ``k``/``v`` may carry fewer
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
