"""Sequence/context-parallel attention over a named mesh axis: ring
attention (K/V rotation) and Ulysses (head<->sequence all-to-all).

BEYOND-PARITY EXTENSION. The reference is a 2016 CNN framework with no
attention anywhere (SURVEY.md §5.7: "absent — definitively; do not build
SP/CP for parity"), but the same section's design note requires the mesh
layer to admit a ``seq`` axis additively — this module is that promise
kept, and the long-context capability the TPU rebuild is expected to
carry (ring attention per Liu et al. 2023, blockwise parallel
transformers; PAPERS.md).

Design: the sequence is sharded over a mesh axis. Each device keeps its
local Q block and streams the K/V blocks around the ring with ONE
``lax.ppermute`` per step (n-1 hops total), accumulating attention with
the online-softmax (flash) recurrence — peak memory is O(T/n) per
device, compute overlaps the neighbor exchange, and the collective
rides ICI. Works on any axis of any mesh built by
:mod:`theanompi_tpu.parallel.mesh` (including a future ('data', 'seq')
2-D layout) and on the virtual CPU mesh for tests.

Numerically exact (not approximate) attention: matches the full
single-device softmax to float tolerance (tests/test_ring_attention.py).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

_NEG = -1e30  # masked-logit sentinel (finite: keeps the recurrence NaN-free)


def ring_attention(
    q: jax.Array,  # [B, Tq_local, H, D] — this shard's queries
    k: jax.Array,  # [B, Tk_local, H, D] — this shard's keys
    v: jax.Array,  # [B, Tk_local, H, D] — this shard's values
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    precision=None,
) -> jax.Array:
    """Exact blockwise attention with K/V rotating around ``axis_name``.

    Must run inside ``shard_map`` with the sequence dim sharded over
    ``axis_name``; global positions are derived from the axis index, so
    ``causal=True`` masks against the GLOBAL sequence order. Returns the
    local output block ``[B, Tq_local, H, D]``.

    ``precision``: forwarded to the two einsums — TPU's default bf16
    matmul passes give ~5e-3 absolute error vs fp32 (measured);
    ``jax.lax.Precision.HIGHEST`` restores fp32 exactness at ~2x matmul
    cost.
    """
    n = lax.psum(1, axis_name)
    rank = lax.axis_index(axis_name)
    B, Tq, H, D = q.shape
    Tk = k.shape[1]
    sc = scale if scale is not None else 1.0 / math.sqrt(D)

    q_pos = rank * Tq + jnp.arange(Tq)  # global query positions

    # online-softmax accumulators, [B, H, Tq(, D)]
    o0 = jnp.zeros((B, H, Tq, D), jnp.float32)
    m0 = jnp.full((B, H, Tq), _NEG, jnp.float32)
    l0 = jnp.zeros((B, H, Tq), jnp.float32)

    perm = [(i, (i + 1) % n) for i in range(n)]

    def attend(o, m, l, kt, vt, src):
        """Fold one K/V block (originating on rank ``src``) into the
        online-softmax accumulators."""
        s = jnp.einsum(
            "bqhd,bkhd->bhqk", q.astype(jnp.float32), kt.astype(jnp.float32),
            precision=precision,
        ) * sc
        if causal:
            k_pos = src * Tk + jnp.arange(Tk)
            mask = q_pos[:, None] >= k_pos[None, :]  # [Tq, Tk]
            s = jnp.where(mask[None, None], s, _NEG)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        if causal:
            # exp(_NEG - m_new) underflows to 0 whenever any real logit
            # exists; when ALL logits in the block are masked m_new==_NEG
            # and p would be exp(0)=1 — zero those explicitly
            p = jnp.where(mask[None, None], p, 0.0)
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        o = o * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, vt.astype(jnp.float32), precision=precision
        )
        return o, m_new, l

    # local block first (no rotation), then exactly n-1 hops; K and V
    # travel as ONE stacked ppermute per hop
    o, m, l = attend(o0, m0, l0, k, v, rank)
    kv = jnp.stack([k, v])

    def body(carry, t):
        o, m, l, kv = carry
        kv = lax.ppermute(kv, axis_name, perm)
        src = jnp.mod(rank - t, n)
        o, m, l = attend(o, m, l, kv[0], kv[1], src)
        return (o, m, l, kv), None

    (o, m, l, _), _ = lax.scan(body, (o, m, l, kv), jnp.arange(1, n))
    # causal guarantees >= 1 valid key per query (its own position), so l > 0
    out = o / l[..., None]
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)  # [B, Tq, H, D]


def ulysses_attention(
    q: jax.Array,  # [B, T_local, H, D] — this shard's queries
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    causal: bool = False,
    scale: Optional[float] = None,
    precision=None,
    local_fn=None,
) -> jax.Array:
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style; Jacobs
    et al. 2023, PAPERS.md) — the other canonical SP scheme next to
    :func:`ring_attention`, trading the ring's n-1 K/V hops for two
    ``lax.all_to_all`` head<->sequence transposes.

    Inside ``shard_map`` with the sequence sharded over ``axis_name``:
    the first all-to-all scatters heads and gathers sequence, so each
    device holds ``H/n`` full-sequence heads; attention is then plain
    local softmax attention (no cross-device mask bookkeeping); the
    second all-to-all restores the ``[B, T_local, H, D]`` layout.
    Requires ``H % n == 0``. Peak memory is O(T_global^2) scores for the
    local heads — choose ring attention when T^2 dominates, Ulysses when
    head count is plentiful and ICI all-to-all is cheap (both are exact).

    ``local_fn`` overrides the local per-head attention step — pass
    :func:`theanompi_tpu.ops.pallas_attention.flash_attention` to run
    the gathered-sequence step as the fused Pallas kernel (drops the
    O(T^2) score materialization, keeping only the all-to-alls as the
    SP cost).
    """
    n = lax.psum(1, axis_name)
    # scatter heads (axis 2) across the mesh, gather sequence (axis 1):
    # [B, T/n, H, D] -> [B, T, H/n, D], blocks concatenated in rank order
    qg = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kg = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vg = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    if local_fn is not None:
        out = local_fn(qg, kg, vg, causal=causal, scale=scale,
                       precision=precision)
    else:
        out = full_attention_reference(
            qg, kg, vg, causal=causal, scale=scale, precision=precision
        )
    # gather heads back, re-scatter the sequence
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)


def full_attention_reference(q, k, v, causal=False, scale=None, precision=None,
                             window=None):
    """Plain full-softmax attention — the single-device oracle for tests
    and the local per-head kernel inside :func:`ulysses_attention`.
    ``window`` (causal only): query ``t`` sees keys ``t - window < s <= t``."""
    if window is not None and not causal:
        raise ValueError("full_attention_reference: a sliding window needs causal=True")
    B, T, H, D = q.shape
    Tk = k.shape[1]
    sc = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32),
        precision=precision,
    ) * sc
    if causal:
        # position-aligned-at-start convention, valid for Tq != Tk too
        # (matches pallas_attention's global row >= col mask)
        mask = jnp.arange(T)[:, None] >= jnp.arange(Tk)[None, :]
        if window is not None:
            mask &= jnp.arange(T)[:, None] - jnp.arange(Tk)[None, :] < window
        s = jnp.where(mask[None, None], s, _NEG)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bhqd", p, v.astype(jnp.float32), precision=precision
    )
    return jnp.transpose(out, (0, 2, 1, 3)).astype(q.dtype)
