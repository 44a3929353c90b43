"""Absorbed latent attention for one decode step over a paged latent cache.

BEYOND-PARITY EXTENSION (the reference has no attention; SURVEY.md §5.7).
A latent-attention layer (MLA) caches, a position, ONE normed latent row
``c`` (``[R]``) and ONE rotated key row ``r`` (``[Dr]``) shared by all
heads. In a decode step the per-head keys and values are never rebuilt:
the query is carried into the latent space (``q_lat = q_nope W_uk``) and

    score[h, t] = (q_lat[h] . c[t] + q_rope[h] . r[t]) * scale
    o_lat[h]    = sum_t softmax(score)[h, t] c[t]

after which the caller maps ``o_lat`` back through ``W_uv``. ``mla_decode``
computes this for every batch slot against the paged pools

    c_pool [L, n_pages + 1, page, R]     r_pool [L, n_pages + 1, Dr, page]

(the rotated rows lie TRANSPOSED in their pages: ``Dr`` is 64 and a page's
positions are the minor dimension, so nothing is padded to the 128 lanes;
with ``[page, Dr]`` pages the TPU's compiler keeps the pool in this order
anyway and copies it whole to the padded one before every kernel call)

walking the slot's page table (scalar prefetch) and reading ONLY the pages
its real length covers: past the last live page the block index repeats
(no copy) and the body does nothing, so a slot of no cached position (an
inactive one) costs its grid steps alone. The step's own position is not
in the pools yet: its rows ``c_new`` / ``r_new`` come as operands and join
the softmax last. ``mla_cache_write`` then puts the new rows of ALL layers
into the pools in one call after the last layer: each slot's current page
(``write_page[s]``, offset ``lens[s] % page``; the scratch page for an
inactive slot) is read, the row put in, the page written back, and the
pools are that kernel's aliased outputs. So every read of a pool precedes
its one write, a donated pool is updated in place, and the decode program
has no scatter into a pool (the TPU's compiler re-lays a whole pool out,
twice, around a scatter of single positions; and it copies a pool that one
kernel call both reads through several operands and writes).

Numerics: products in the input dtype on the MXU with fp32 accumulation,
online softmax in fp32, probabilities cast to the input dtype for the
second product. Off-TPU the kernel runs through the Pallas interpreter;
:func:`mla_decode_reference` is its ``jnp`` twin (gather through the table,
masked), the CPU tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

DECODE_NAME = "mla_decode"  # the kernels' names in a device trace
WRITE_NAME = "mla_cache_write"
_PAGES_A_STEP = 8  # pages a grid step reads (one block each): fewer, longer steps
_NEG = -1e30


def _kernel(scale, page, pps, tables_ref, lens_ref, qlat_ref, qrope_ref, cnew_ref,
            rnew_ref, *rest):
    del tables_ref  # read by the index maps
    c_refs, r_refs = rest[:pps], rest[pps:2 * pps]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * pps:]
    j = pl.program_id(1)
    n = lens_ref[pl.program_id(0)]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    qlat, qrope = qlat_ref[...], qrope_ref[...]  # [H, R], [H, Dr]
    for i in range(pps):
        start = (j * pps + i) * page

        @pl.when(start < n)
        def _page(i=i, start=start):
            c, r = c_refs[i][...], r_refs[i][...]  # [page, R], [Dr, page]
            s = (lax.dot_general(qlat, c, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
                 + lax.dot_general(qrope, r, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)) * scale
            seen = start + lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
            s = jnp.where(seen, s, _NEG)
            m = m_ref[...]
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
            corr = jnp.exp(m - m_new)
            l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
                p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _own_position_and_out():
        cn = cnew_ref[...].astype(jnp.float32)  # [1, R]
        rn = rnew_ref[...].astype(jnp.float32)
        s = (jnp.sum(qlat.astype(jnp.float32) * cn, axis=-1, keepdims=True)
             + jnp.sum(qrope.astype(jnp.float32) * rn, axis=-1, keepdims=True)) * scale
        m = m_ref[...]
        m_new = jnp.maximum(m, s)
        corr, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        l = l_ref[...] * corr + p
        o_ref[...] = ((acc_ref[...] * corr + p * cn) / l).astype(o_ref.dtype)


def mla_decode(
    q_lat: jax.Array,  # [S, H, R] the queries in the latent space
    q_rope: jax.Array,  # [S, H, Dr] their rotated part
    c_new: jax.Array,  # [S, R] the step's own latent row
    r_new: jax.Array,  # [S, Dr] and rotated key row
    c_pool: jax.Array,  # [L, n_pages + 1, page, R]
    r_pool: jax.Array,  # [L, n_pages + 1, Dr, page]
    tables: jax.Array,  # [S, M] int32 page table of every slot
    lens: jax.Array,  # [S] int32 cached positions of every slot
    *,
    layer: int,
    scale: float,
) -> jax.Array:
    """``o_lat [S, H, R]``: every slot's heads attending over the slot's
    ``lens[s]`` cached positions of ``layer`` and over its own new row."""
    from jax.experimental.pallas import tpu as pltpu

    S, H, R = q_lat.shape
    Dr = q_rope.shape[-1]
    M, page = tables.shape[1], c_pool.shape[2]
    pps = min(_PAGES_A_STEP, M)
    layer = int(layer)

    def page_at(i):
        def at(s, j, tables, lens):
            last = jnp.maximum((lens[s] + page - 1) // page - 1, 0)
            return (layer, tables[s, jnp.minimum(jnp.minimum(j * pps + i, last), M - 1)], 0, 0)
        return at

    slot = lambda s, j, tables, lens: (s, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, float(scale), page, pps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, -(-M // pps)),
            in_specs=[
                pl.BlockSpec((None, H, R), slot),
                pl.BlockSpec((None, H, Dr), slot),
                pl.BlockSpec((None, 1, R), slot),
                pl.BlockSpec((None, 1, Dr), slot),
                *[pl.BlockSpec((None, None, page, R), page_at(i)) for i in range(pps)],
                *[pl.BlockSpec((None, None, Dr, page), page_at(i)) for i in range(pps)],
            ],
            out_specs=pl.BlockSpec((None, H, R), slot),
            scratch_shapes=[pltpu.VMEM((H, 1), jnp.float32), pltpu.VMEM((H, 1), jnp.float32),
                            pltpu.VMEM((H, R), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, R), q_lat.dtype),
        name=DECODE_NAME,
        interpret=_interpret(),
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q_lat, q_rope,
      c_new[:, None, :], r_new[:, None, :], *[c_pool] * pps, *[r_pool] * pps)


def _write_kernel(page, wpage_ref, lens_ref, cnew_ref, rcol_ref, cw_ref, rw_ref, co_ref, ro_ref):
    del wpage_ref  # read by the index maps
    off = lens_ref[pl.program_id(1)] % page
    cw, rw = cw_ref[...], rw_ref[...]  # [page, R], [Dr, page]
    co_ref[...] = jnp.where(lax.broadcasted_iota(jnp.int32, cw.shape, 0) == off,
                            cnew_ref[...].astype(cw.dtype), cw)
    ro_ref[...] = jnp.where(lax.broadcasted_iota(jnp.int32, rw.shape, 1) == off,
                            rcol_ref[...].astype(rw.dtype), rw)


def mla_cache_write(
    c_pool: jax.Array,  # [L, n_pages + 1, page, R]
    r_pool: jax.Array,  # [L, n_pages + 1, Dr, page]
    c_rows: jax.Array,  # [L, S, R] every layer's new latent row of every slot
    r_rows: jax.Array,  # [L, S, Dr] and rotated key row
    write_page: jax.Array,  # [S] int32 the page they go to (scratch for an inactive slot)
    lens: jax.Array,  # [S] int32 their position; the offset in the page is lens % page
):
    """-> the pools with the rows in place (the pools are aliased to the
    outputs: in place when the caller's pools are donated). A slot's page is
    read, the row (a column of the transposed rotated page) put in, and the
    page written back: 2 pages a slot a layer, no scatter."""
    from jax.experimental.pallas import tpu as pltpu

    L, S, R = c_rows.shape
    Dr, page = r_rows.shape[-1], c_pool.shape[2]
    row = lambda l, s, wpage, lens: (l, s, 0, 0)  # noqa: E731
    own = lambda l, s, wpage, lens: (l, wpage[s], 0, 0)  # noqa: E731
    c_page, r_page = pl.BlockSpec((None, None, page, R), own), pl.BlockSpec((None, None, Dr, page), own)
    return pl.pallas_call(
        functools.partial(_write_kernel, page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(L, S),
            in_specs=[pl.BlockSpec((None, None, 1, R), row), pl.BlockSpec((None, None, Dr, 1), row),
                      c_page, r_page],
            out_specs=(c_page, r_page),
        ),
        out_shape=(jax.ShapeDtypeStruct(c_pool.shape, c_pool.dtype),
                   jax.ShapeDtypeStruct(r_pool.shape, r_pool.dtype)),
        input_output_aliases={4: 0, 5: 1},
        name=WRITE_NAME,
        interpret=_interpret(),
    )(write_page.astype(jnp.int32), lens.astype(jnp.int32), c_rows[:, :, None, :],
      r_rows[:, :, :, None], c_pool, r_pool)


def mla_decode_reference(q_lat, q_rope, c_new, r_new, c_pool, r_pool, tables, lens,
                         *, layer: int, scale: float):
    """The kernel's ``jnp`` twin: every slot's pages gathered through its
    table to the longest context, masked past ``lens``; fp32 throughout."""
    S, M = tables.shape
    f32 = jnp.float32
    page = c_pool.shape[2]
    c = c_pool[layer][tables].reshape(S, M * page, -1).astype(f32)
    r = jnp.swapaxes(r_pool[layer][tables], 2, 3).reshape(S, M * page, -1).astype(f32)
    ql, qr, cn, rn = (a.astype(f32) for a in (q_lat, q_rope, c_new, r_new))
    hi = lax.Precision.HIGHEST
    s = (jnp.einsum("shr,str->sht", ql, c, precision=hi)
         + jnp.einsum("shd,std->sht", qr, r, precision=hi)) * scale
    seen = jnp.arange(c.shape[1])[None, :] < lens[:, None]
    s = jnp.where(seen[:, None, :], s, -jnp.inf)
    own = (jnp.einsum("shr,sr->sh", ql, cn, precision=hi)
           + jnp.einsum("shd,sd->sh", qr, rn, precision=hi)) * scale
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1), axis=-1)
    o = jnp.einsum("sht,str->shr", p[..., :-1], c, precision=hi) + p[..., -1:] * cn[:, None, :]
    return o.astype(q_lat.dtype)
