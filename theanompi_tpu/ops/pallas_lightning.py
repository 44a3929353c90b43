"""Linear attention with a per-head decay (Lightning Attention): the
one-position step of a decode iteration over per-slot recurrent state, and the
chunked form of the same recurrence over a prompt.

BEYOND-PARITY EXTENSION (the reference has no attention; SURVEY.md §5.7).
A head keeps a state ``S [D, D]`` in fp32, nought before position 0:

    S_t = l S_(t-1) + k_t^T v_t          o_t = q_t S_t

with one decay ``l = exp(-rate)`` a head (the caller scales ``o``).

- :func:`lightning_step`: one Pallas kernel a layer over

      state [L, n_slots, H, D, D] fp32

  reads each slot's state ONCE, starts from nought where ``lens[s] == 0`` (a
  slot taken anew), adds the step's outer product, writes the state back and
  gives ``q S``: the state is the call's aliased output, so a donated state
  is updated in place and only ``layer``'s part of it moves. ``q`` and ``k``
  come TRANSPOSED by head group (``[S, H / hb, D, hb]``: ``D`` along the
  sublanes), so that a head's key is a column to spread along the lanes
  beside its value row: the outer product and ``q S`` are then plain
  elementwise work and one sublane reduction, exact in fp32.
- :func:`lightning_chunked` (plain ``jnp``, under the caller's scope): a
  prompt in chunks of ``C``. Inside a chunk ``o_i = sum_(j<=i) l^(i-j)
  (q_i . k_j) v_j + l^(i+1) q_i S_prev``; across chunks ``S_next = l^r
  S_prev + sum_(j<r) l^(r-1-j) k_j^T v_j`` where ``r`` is the number of REAL
  rows of the chunk (``C``, less in the chunk the prompt ends in, 0 past
  it): padded rows neither add to the state nor decay it, and the state
  handed back is the one at the real length. ``l^(i-j)`` is built as a
  masked matrix of ``exp(-rate (i - j))`` in fp32 (``l`` is 0.43 for the
  fastest head: a split into ``l^i / l^j`` overflows). The fp32 operands'
  products run at ``HIGHEST``.

:func:`lightning_reference` is the recurrence itself, position by position:
the oracle of both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

STEP_NAME = "lightning_step"  # the kernel's name in a device trace
_HEADS_A_STEP = 8


def _step_kernel(hb, lens_ref, qt_ref, kt_ref, v_ref, dec_ref, s_ref, o_ref, so_ref):
    fresh = lens_ref[pl.program_id(0)] == 0
    qt, kt = qt_ref[...], kt_ref[...]  # [D, hb]
    v = v_ref[...]  # [hb, D]
    for i in range(hb):
        s = jnp.where(fresh, 0.0, s_ref[i])  # [D, D]
        s = s * dec_ref[i] + kt[:, i:i + 1] * v[i:i + 1, :]
        so_ref[i] = s
        o_ref[i:i + 1, :] = jnp.sum(qt[:, i:i + 1] * s, axis=0, keepdims=True)


def lightning_step(
    q: jax.Array,  # [S, H, D] the step's queries (rotated, normed)
    k: jax.Array,  # [S, H, D]
    v: jax.Array,  # [S, H, D]
    rates: jax.Array,  # [H] fp32: the state decays by exp(-rate) a position
    state: jax.Array,  # [L, S, H, D, D] fp32
    lens: jax.Array,  # [S] int32: a slot at position 0 starts from nought
    *,
    layer: int,
):
    """-> (``o [S, H, D]`` fp32 = ``q S_new``, the state with ``layer``'s part
    stepped; the state is aliased to the output)."""
    from jax.experimental.pallas import tpu as pltpu

    S, H, D = q.shape
    hb = min(_HEADS_A_STEP, H)
    if H % hb:
        raise ValueError(f"{H} heads are no whole groups of {hb}")
    f32 = jnp.float32
    layer = int(layer)
    by_group = lambda a: jnp.swapaxes(a.astype(f32).reshape(S, H // hb, hb, D), 2, 3)  # noqa: E731
    decay = jnp.broadcast_to(jnp.exp(-rates.astype(f32))[:, None, None], (H, 1, D))
    col = pl.BlockSpec((None, None, D, hb), lambda s, j, lens: (s, j, 0, 0))
    a_state = pl.BlockSpec((None, None, hb, D, D), lambda s, j, lens: (layer, s, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, H // hb),
            in_specs=[col, col,
                      pl.BlockSpec((None, hb, D), lambda s, j, lens: (s, j, 0)),
                      pl.BlockSpec((hb, 1, D), lambda s, j, lens: (j, 0, 0)),
                      a_state],
            out_specs=(pl.BlockSpec((None, hb, D), lambda s, j, lens: (s, j, 0)), a_state),
        ),
        out_shape=(jax.ShapeDtypeStruct((S, H, D), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={5: 1},
        name=STEP_NAME,
        interpret=_interpret(),
    )(lens.astype(jnp.int32), by_group(q), by_group(k), v.astype(f32), decay, state)
    return o, state


def lightning_step_reference(q, k, v, rates, state, lens, *, layer: int):
    """The step kernel's ``jnp`` twin."""
    f32 = jnp.float32
    s = jnp.where((lens == 0)[:, None, None, None], 0.0, state[layer])
    s = (s * jnp.exp(-rates.astype(f32))[None, :, None, None]
         + k.astype(f32)[..., :, None] * v.astype(f32)[..., None, :])
    o = jnp.einsum("shd,shde->she", q.astype(f32), s, precision=lax.Precision.HIGHEST)
    return o, state.at[layer].set(s)


def lightning_chunked(q, k, v, rates, n_real, chunk: int):
    """``q, k, v [T, H, D]`` (``T`` whole chunks), ``rates [H]``, ``n_real``
    the prompt's real length (a traced scalar). -> (``o [T, H, D]`` fp32,
    unscaled; the state ``[H, D, D]`` fp32 after position ``n_real - 1``)."""
    T, H, D = q.shape
    C = int(chunk)
    if T % C:
        raise ValueError(f"{T} positions are no whole chunks of {C}")
    N, f32, hi = T // C, jnp.float32, lax.Precision.HIGHEST
    rates = rates.astype(f32)
    qc, kc, vc = (a.reshape(N, C, H, D) for a in (q, k, v))
    i = jnp.arange(C)
    # inside a chunk: scores in the input dtype (exact products, fp32 sums), the decay as a masked matrix
    s = jnp.einsum("nihd,njhd->nhij", qc, kc, preferred_element_type=f32)
    apart = (i[:, None] - i[None, :]).astype(f32)
    decay = jnp.where(apart >= 0, jnp.exp(-rates[:, None, None] * jnp.maximum(apart, 0.0)), 0.0)  # [H, C, C]
    o = jnp.einsum("nhij,njhd->nihd", s * decay, vc.astype(f32), precision=hi)
    # a chunk's own contribution to the state, over its r real rows
    r = jnp.clip(n_real - jnp.arange(N) * C, 0, C)  # [N]
    left = (r[:, None] - 1 - i[None, :]).astype(f32)  # [N, C]: positions from row j to the chunk's last real row
    w = jnp.where(left[:, None, :] >= 0, jnp.exp(-rates[None, :, None] * jnp.maximum(left, 0.0)[:, None, :]), 0.0)  # [N, H, C]
    kv = jnp.einsum("njhd,njhe->nhde", kc.astype(f32) * jnp.swapaxes(w, 1, 2)[..., None], vc.astype(f32),
                    precision=hi)
    through = jnp.exp(-rates[None, :] * r[:, None].astype(f32))  # [N, H]: l^r

    def carry(S, x):
        kv_n, d_n = x
        return d_n[:, None, None] * S + kv_n, S

    last, before = lax.scan(carry, jnp.zeros((H, D, D), f32), (kv, through))
    reach = jnp.exp(-rates[None, :] * (i[:, None] + 1).astype(f32))  # [C, H]: l^(i+1)
    o = o + jnp.einsum("nihd,nhde->nihe", qc.astype(f32) * reach[None, :, :, None], before, precision=hi)
    return o.reshape(T, H, D), last


def lightning_reference(q, k, v, rates, n_real=None):
    """The recurrence, one position at a time. -> (``o [T, H, D]``, the state
    after position ``n_real - 1``)."""
    T, H, D = q.shape
    f32 = jnp.float32
    n_real = T if n_real is None else n_real
    l = jnp.exp(-rates.astype(f32))[:, None, None]

    def one(S, x):
        t, q_t, k_t, v_t = x
        S_new = l * S + k_t[:, :, None] * v_t[:, None, :]
        S_new = jnp.where(t < n_real, S_new, S)
        return S_new, jnp.einsum("hd,hde->he", q_t, S_new, precision=lax.Precision.HIGHEST)

    last, o = lax.scan(one, jnp.zeros((H, D, D), f32),
                       (jnp.arange(T), q.astype(f32), k.astype(f32), v.astype(f32)))
    return o, last
