"""The gated delta rule (Gated DeltaNet): the prompt in chunks, and the
one-position step of a decode iteration over per-slot recurrent state.

BEYOND-PARITY EXTENSION (the reference has no recurrent layer; SURVEY.md
§5.7; Yang, Kautz, Hatamizadeh, arXiv:2412.06464). A value head keeps a state
``M [Dk, Dv]`` in fp32 (keys on the first axis), nought before position 0:

    M_t = e^(g_t) M_(t-1) + k_t u_t^T,   u_t = beta_t (v_t - e^(g_t) M_(t-1)^T k_t)
    o_t = M_t^T q_t

which is ``S_t = e^(g_t) S_(t-1) (I - beta_t k_t k_t^T) + beta_t v_t k_t^T``,
``o_t = S_t q_t`` with ``S = M^T``. ``q`` and ``k`` come L2-normed (``q``
scaled) per KEY head; value head ``h`` reads key head ``h // (H / Hk)``.
``beta`` (``sigma(b)``) and ``g`` (the log decay, ``<= 0``) are one a value
head a position, fp32.

- :func:`gdn_chunk`: one Pallas kernel a layer over a prompt in chunks of
  ``C`` (the WY/UT form). Inside a chunk, with ``G_i`` the decay summed over
  rows ``0 .. i`` and ``Gamma_ij = e^(G_i - G_j)`` (``j <= i``):

      A = strict_lower(beta K K^T * Gamma)        T = (I + A)^-1
      W = T (beta e^G K)     U = T (beta V) - W M
      O = (e^G Q) M + lower(Q K^T * Gamma) U
      M <- e^(G_last) M + (K e^(G_last - G))^T U

  ``T`` is the inverse of a unit lower triangular matrix by blocks: from
  the identity (blocks of one row), each level doubles the block, ``T <- T -
  T (A masked to the lower-left quarter of each new block) T``: two
  products a level, six at ``C = 64``, no power of ``A`` (whose entries
  grow where keys are alike) is ever formed. Every product runs on the MXU;
  those with an fp32 operand at ``HIGHEST``, ``K K^T`` and ``Q K^T`` on the
  compute dtype (exact products, fp32 sums). The grid is (value-head groups,
  chunks), the chunks in order; a group's states ``[hb, Dk, Dv]`` fp32 are
  the call's output block, held in VMEM across the chunks and written to HBM
  once. Rows at or past ``n_real`` take ``beta = 0`` and ``g = 0``: they
  neither add to the state nor decay it, and the state handed back is the
  one after row ``n_real - 1``; a chunk wholly past ``n_real`` writes zeros
  and computes nothing.
- :func:`gdn_step`: one Pallas kernel a layer over ALL slots, one position
  each, over ``state [L, n_slots, H, Dk, Dv]`` fp32: each (slot, head group)
  block read once, from nought where ``lens[s] == 0`` (a slot taken anew),
  stepped, written back; the state is the call's aliased output, so a
  donated state is updated in place and only ``layer``'s part of it moves.
  ``q`` and ``k`` come TRANSPOSED by head group (``[S, H / hb, Dk, hb]``: a
  head's key is a column to spread along the lanes), so ``M^T k``, the
  rank-1 update and ``M^T q`` are elementwise work and sublane sums, exact
  in fp32.

:func:`gdn_reference` is the recurrence itself, position by position: the
oracle of both kernels. :func:`gdn_chunk_reference` is the chunk kernel's
``jnp`` twin (the same sums over whole arrays, ``T`` by a triangular solve),
:func:`gdn_step_reference` the step kernel's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

CHUNK_NAME = "gdn_chunk"  # the kernels' names in a device trace
STEP_NAME = "gdn_step"
_HEADS_A_STEP = 8


def _heads_a_step(H: int, ratio: int) -> int:
    hb = min(_HEADS_A_STEP, H)
    if H % hb or hb % ratio:
        raise ValueError(f"{H} value heads are no whole groups of {hb} over key heads of {ratio} value heads")
    return hb


def _quarter_masks(C: int) -> np.ndarray:
    """``[log2 C, C, C]`` fp32: level ``l``'s lower-left quarter of every
    block of ``2^(l+1)`` rows (rows in its lower half, columns in its upper)."""
    if C & (C - 1):
        raise ValueError(f"a chunk of {C} rows is no power of two")
    i = np.arange(C)
    levels = []
    s = 1
    while s < C:
        same = (i[:, None] // (2 * s)) == (i[None, :] // (2 * s))
        levels.append(same & ((i[:, None] // s) % 2 == 1) & ((i[None, :] // s) % 2 == 0))
        s *= 2
    return np.stack(levels).astype(np.float32)


def _inverse(a, quarters, hi):
    """``(I + a)^-1`` for a strictly lower ``a [C, C]`` fp32, by blocks."""
    C = a.shape[0]
    t = (lax.broadcasted_iota(jnp.int32, (C, C), 0) == lax.broadcasted_iota(jnp.int32, (C, C), 1)).astype(a.dtype)
    for lvl in range(quarters.shape[0]):
        inner = jnp.dot(a * quarters[lvl], t, precision=hi, preferred_element_type=jnp.float32)
        t = t - jnp.dot(t, inner, precision=hi, preferred_element_type=jnp.float32)
    return t


def _chunk_kernel(hb, ratio, C, Dk, Dv, n_ref, q_ref, k_ref, v_ref, b_ref, gc_ref, gr_ref, m_ref, o_ref, s_ref):
    n = pl.program_id(1)
    f32, hi = jnp.float32, lax.Precision.HIGHEST

    @pl.when(n == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(n * C >= n_ref[0])
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(n * C < n_ref[0])
    def _():
        row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
        col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
        quarters = m_ref[...]
        for i in range(hb):
            kc, vc = slice((i // ratio) * Dk, (i // ratio + 1) * Dk), slice(i * Dv, (i + 1) * Dv)
            q, k, v = q_ref[:, kc], k_ref[:, kc], v_ref[:, vc].astype(f32)
            beta, G = b_ref[i], gc_ref[i]  # [C, 1]
            apart = G - gr_ref[0, i:i + 1, :]  # [C, C]: G_i - G_j
            decay = jnp.where(col <= row, jnp.exp(jnp.minimum(apart, 0.0)), 0.0)
            kk = lax.dot_general(k, k, (((1,), (1,)), ((), ())), preferred_element_type=f32)
            qk = lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=f32)
            t = _inverse(jnp.where(col < row, beta * kk * decay, 0.0), quarters, hi)
            kf, gamma = k.astype(f32), jnp.exp(G)
            S = s_ref[i]
            w = jnp.dot(t, beta * gamma * kf, precision=hi, preferred_element_type=f32)
            u = (jnp.dot(t, beta * v, precision=hi, preferred_element_type=f32)
                 - jnp.dot(w, S, precision=hi, preferred_element_type=f32))
            o_ref[:, vc] = (jnp.dot(q.astype(f32) * gamma, S, precision=hi, preferred_element_type=f32)
                            + jnp.dot(qk * decay, u, precision=hi, preferred_element_type=f32))
            last = jnp.min(G, axis=0, keepdims=True)  # [1, 1]: G falls row by row, and padded rows add nothing
            s_ref[i] = jnp.exp(last) * S + lax.dot_general(
                kf * jnp.exp(last - G), u, (((0,), (0,)), ((), ())), precision=hi, preferred_element_type=f32)


def _prepared(beta, g, n_real, C):
    """Padded rows masked, ``G`` summed within each chunk: -> (``beta``,
    ``G``) ``[T, H]`` fp32."""
    T, H = g.shape
    real = (jnp.arange(T) < n_real)[:, None]
    beta = jnp.where(real, beta.astype(jnp.float32), 0.0)
    g = jnp.where(real, g.astype(jnp.float32), 0.0)
    return beta, jnp.cumsum(g.reshape(T // C, C, H), axis=1).reshape(T, H)


def gdn_chunk(q, k, v, beta, g, n_real, *, chunk: int = 64):
    """``q, k [T, Hk, Dk]`` (L2-normed per head, ``q`` scaled) and ``v [T,
    H, Dv]`` in the compute dtype (``T`` whole chunks), ``beta, g [T, H]``
    fp32, ``n_real`` the prompt's real length (a traced scalar). -> (``o [T,
    H, Dv]`` fp32; the state ``[H, Dk, Dv]`` fp32 after position ``n_real -
    1``)."""
    T, Hk, Dk = q.shape
    H, Dv = v.shape[1:]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"{T} positions are no whole chunks of {C}")
    ratio = H // Hk
    hb = _heads_a_step(H, ratio)
    beta, G = _prepared(beta, g, n_real, C)
    column = lambda a: a.T[:, :, None]  # noqa: E731 - [H, T, 1]: a head's rows of a chunk as a column
    quarters = jnp.asarray(_quarter_masks(C))
    o, state = pl.pallas_call(
        functools.partial(_chunk_kernel, hb, ratio, C, Dk, Dv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(H // hb, T // C),
            in_specs=[pl.BlockSpec((C, hb // ratio * Dk), lambda h, n, n_real: (n, h)),
                      pl.BlockSpec((C, hb // ratio * Dk), lambda h, n, n_real: (n, h)),
                      pl.BlockSpec((C, hb * Dv), lambda h, n, n_real: (n, h)),
                      pl.BlockSpec((hb, C, 1), lambda h, n, n_real: (h, n, 0)),
                      pl.BlockSpec((hb, C, 1), lambda h, n, n_real: (h, n, 0)),
                      pl.BlockSpec((1, hb, C), lambda h, n, n_real: (n, h, 0)),
                      pl.BlockSpec(quarters.shape, lambda h, n, n_real: (0, 0, 0))],
            out_specs=(pl.BlockSpec((C, hb * Dv), lambda h, n, n_real: (n, h)),
                       pl.BlockSpec((hb, Dk, Dv), lambda h, n, n_real: (h, 0, 0))),
        ),
        out_shape=(jax.ShapeDtypeStruct((T, H * Dv), jnp.float32), jax.ShapeDtypeStruct((H, Dk, Dv), jnp.float32)),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")),
        name=CHUNK_NAME,
        interpret=_interpret(),
    )(jnp.reshape(n_real, (1,)).astype(jnp.int32), q.reshape(T, Hk * Dk), k.reshape(T, Hk * Dk),
      v.reshape(T, H * Dv), column(beta), column(G), jnp.swapaxes(G.reshape(T // C, C, H), 1, 2), quarters)
    return o.reshape(T, H, Dv), state


def gdn_chunk_reference(q, k, v, beta, g, n_real, *, chunk: int = 64):
    """The chunk kernel's ``jnp`` twin: the same sums over whole arrays, ``T``
    by a triangular solve."""
    T, Hk, Dk = q.shape
    H, Dv = v.shape[1:]
    C = min(int(chunk), T)
    if T % C:
        raise ValueError(f"{T} positions are no whole chunks of {C}")
    N, f32, hi = T // C, jnp.float32, lax.Precision.HIGHEST
    beta, G = _prepared(beta, g, n_real, C)
    by_chunk = lambda a: jnp.repeat(a, H // a.shape[1], axis=1).reshape(N, C, H, -1)  # noqa: E731
    qc, kc, vc = by_chunk(q), by_chunk(k), by_chunk(v).astype(f32)
    bc, Gc = beta.reshape(N, C, H), G.reshape(N, C, H)
    i = jnp.arange(C)
    apart = Gc[:, :, None, :] - Gc[:, None, :, :]  # [N, C(i), C(j), H]
    decay = jnp.where((i[None, :] <= i[:, None])[None, :, :, None], jnp.exp(jnp.minimum(apart, 0.0)), 0.0)
    kk = jnp.einsum("nihd,njhd->nijh", kc, kc, preferred_element_type=f32)
    qk = jnp.einsum("nihd,njhd->nijh", qc, kc, preferred_element_type=f32)
    a = jnp.where((i[None, :] < i[:, None])[None, :, :, None], bc[:, :, None, :] * kk * decay, 0.0)
    lower = jnp.moveaxis(a, 3, 1) + jnp.eye(C, dtype=f32)  # [N, H, C, C]
    kf = kc.astype(f32)
    rhs = jnp.concatenate([(bc * jnp.exp(Gc))[..., None] * kf, bc[..., None] * vc], -1)  # [N, C, H, Dk + Dv]
    wu = jax.scipy.linalg.solve_triangular(lower, jnp.moveaxis(rhs, 2, 1), lower=True, unit_diagonal=True)
    w, u0 = jnp.moveaxis(wu[..., :Dk], 1, 2), jnp.moveaxis(wu[..., Dk:], 1, 2)  # [N, C, H, D]
    last = Gc[:, -1]  # [N, H]

    def carry(M, x):
        w_n, u0_n, q_n, k_n, dec_n, qk_n, G_n, last_n = x
        u = u0_n - jnp.einsum("chk,hkv->chv", w_n, M, precision=hi)
        o = (jnp.einsum("chk,hkv->chv", q_n.astype(f32) * jnp.exp(G_n)[..., None], M, precision=hi)
             + jnp.einsum("ijh,jhv->ihv", qk_n * dec_n, u, precision=hi))
        M = (jnp.exp(last_n)[:, None, None] * M
             + jnp.einsum("chk,chv->hkv", k_n.astype(f32) * jnp.exp(last_n - G_n)[..., None], u, precision=hi))
        return M, o

    state, o = lax.scan(carry, jnp.zeros((H, Dk, Dv), f32), (w, u0, qc, kc, decay, qk, Gc, last))
    return o.reshape(T, H, Dv), state


def _step_kernel(hb, lens_ref, qt_ref, kt_ref, v_ref, b_ref, a_ref, s_ref, o_ref, so_ref):
    fresh = lens_ref[pl.program_id(0)] == 0
    qt, kt = qt_ref[...], kt_ref[...]  # [Dk, hb]
    v, beta, decay = v_ref[...], b_ref[...], a_ref[...]  # [hb, Dv] each: beta and e^g spread along the lanes
    for i in range(hb):
        s = jnp.where(fresh, 0.0, s_ref[i]) * decay[i:i + 1, :]  # [Dk, Dv]
        key = kt[:, i:i + 1]
        u = beta[i:i + 1, :] * (v[i:i + 1, :] - jnp.sum(key * s, axis=0, keepdims=True))
        s = s + key * u
        so_ref[i] = s
        o_ref[i:i + 1, :] = jnp.sum(qt[:, i:i + 1] * s, axis=0, keepdims=True)


def gdn_step(
    q: jax.Array,  # [S, Hk, Dk] the step's queries (normed, scaled)
    k: jax.Array,  # [S, Hk, Dk] (normed)
    v: jax.Array,  # [S, H, Dv]
    beta: jax.Array,  # [S, H] fp32
    g: jax.Array,  # [S, H] fp32: the log decay
    state: jax.Array,  # [L, S, H, Dk, Dv] fp32
    lens: jax.Array,  # [S] int32: a slot at position 0 starts from nought
    *,
    layer: int,
):
    """-> (``o [S, H, Dv]`` fp32 = ``M_new^T q``, the state with ``layer``'s
    part stepped; the state is aliased to the output)."""
    S, Hk, Dk = q.shape
    H, Dv = v.shape[1:]
    hb = _heads_a_step(H, H // Hk)
    f32 = jnp.float32
    layer = int(layer)
    by_group = lambda a: jnp.swapaxes(  # noqa: E731
        jnp.repeat(a.astype(f32), H // Hk, axis=1).reshape(S, H // hb, hb, Dk), 2, 3)
    col = pl.BlockSpec((None, None, Dk, hb), lambda s, j, lens: (s, j, 0, 0))
    lanes = lambda a: jnp.broadcast_to(a.astype(f32)[..., None], (S, H, Dv))  # noqa: E731 - Mosaic spreads no [1, 1]
    heads = lambda width: pl.BlockSpec((None, hb, width), lambda s, j, lens: (s, j, 0))  # noqa: E731
    a_state = pl.BlockSpec((None, None, hb, Dk, Dv), lambda s, j, lens: (layer, s, j, 0, 0))
    o, state = pl.pallas_call(
        functools.partial(_step_kernel, hb),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(S, H // hb),
            in_specs=[col, col, heads(Dv), heads(Dv), heads(Dv), a_state],
            out_specs=(heads(Dv), a_state),
        ),
        out_shape=(jax.ShapeDtypeStruct((S, H, Dv), f32), jax.ShapeDtypeStruct(state.shape, state.dtype)),
        input_output_aliases={6: 1},
        name=STEP_NAME,
        interpret=_interpret(),
    )(lens.astype(jnp.int32), by_group(q), by_group(k), v.astype(f32), lanes(beta), lanes(jnp.exp(g.astype(f32))),
      state)
    return o, state


def gdn_step_reference(q, k, v, beta, g, state, lens, *, layer: int):
    """The step kernel's ``jnp`` twin."""
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    H, Hk = v.shape[1], q.shape[1]
    q, k = (jnp.repeat(a.astype(f32), H // Hk, axis=1) for a in (q, k))
    s = jnp.where((lens == 0)[:, None, None, None], 0.0, state[layer])
    s = s * jnp.exp(g.astype(f32))[..., None, None]
    u = beta.astype(f32)[..., None] * (v.astype(f32) - jnp.einsum("shk,shkv->shv", k, s, precision=hi))
    s = s + k[..., :, None] * u[..., None, :]
    return jnp.einsum("shk,shkv->shv", q, s, precision=hi), state.at[layer].set(s)


def gdn_reference(q, k, v, beta, g, n_real=None):
    """The recurrence, one position at a time, fp32. -> (``o [T, H, Dv]``,
    the state ``[H, Dk, Dv]`` after position ``n_real - 1``)."""
    T, Hk, Dk = q.shape
    H, Dv = v.shape[1:]
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    n_real = T if n_real is None else n_real
    q, k = (jnp.repeat(a.astype(f32), H // Hk, axis=1) for a in (q, k))

    def one(M, x):
        t, q_t, k_t, v_t, b_t, g_t = x
        M_in = jnp.exp(g_t)[:, None, None] * M
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, M_in, precision=hi))
        M_new = jnp.where(t < n_real, M_in + k_t[:, :, None] * u[:, None, :], M)
        return M_new, jnp.einsum("hk,hkv->hv", q_t, M_new, precision=hi)

    last, o = lax.scan(one, jnp.zeros((H, Dk, Dv), f32),
                       (jnp.arange(T), q, k, v.astype(f32), beta.astype(f32), g.astype(f32)))
    return o, last
