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

reading ONLY the pages its real length covers. The grid is the slots. The
pools stay in HBM (``memory_space=pl.ANY``: one operand each, no block, no
copy of a pool) and the kernel brings a slot's pages itself: the slot's
table is cut into the fewest equal steps of at most ``_STEP_BYTES`` of rows
(the served cell's 69-page table: 3 steps of 23 pages), and a step's live
pages are copied, a page each of ``c`` and ``r``, into one half of a
two-halved VMEM buffer while the step before is computed from the other
half; a slot's last step starts the first step of the next slot, so the
copies do not stop at a slot's end. A step is ONE softmax pass over all its
positions: the scores ``q_lat c^T + q_rope r`` as one ``[H, pages * page]``
tile (two products), one mask (a page past the length is not copied: its
place holds zeros or an earlier step's rows and is masked, not branched
on), one max / exp / sum, one rescale of the accumulator and one second
product; ``m``, ``l`` and the accumulator are the loop's values. A slot of
no cached position (an inactive one) runs no step. The step's own position
is not in the pools yet: its rows ``c_new`` / ``r_new`` come as operands and join
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
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

DECODE_NAME = "mla_decode"  # the kernels' names in a device trace
WRITE_NAME = "mla_cache_write"
_STEP_BYTES = 2 << 20  # of cached rows a step brings at most (VMEM holds two such halves)
_NEG = -1e30


def _kernel(scale, page, pps, layer, tables_ref, lens_ref, qlat_ref, qrope_ref, cnew_ref, rnew_ref,
            cpool_ref, rpool_ref, o_ref, cbuf_ref, rbuf_ref, sem_ref, par_ref):
    s, S = pl.program_id(0), pl.num_programs(0)
    span = pps * page
    n = lens_ref[s]
    steps = (n + span - 1) // span

    def pages(slot, step, buf, act):
        """``act`` on the copies, ``c`` and ``r``, of every live page of
        ``slot``'s step into half ``buf`` (a copy is waited for as it was started)."""
        for i in range(pps):
            k = step * pps + i
            pg = tables_ref[slot, jnp.minimum(k, tables_ref.shape[1] - 1)]

            @pl.when(k * page < lens_ref[slot])
            def _(i=i, pg=pg):
                act(pltpu.make_async_copy(cpool_ref.at[layer, pg],
                                          cbuf_ref.at[buf, pl.ds(i * page, page)], sem_ref.at[buf, 0]))
                act(pltpu.make_async_copy(rpool_ref.at[layer, pg],
                                          rbuf_ref.at[buf, :, pl.ds(i * page, page)], sem_ref.at[buf, 1]))

    def start(slot, step, buf):
        pages(slot, step, buf, lambda copy: copy.start())

    def wait(slot, step, buf):
        pages(slot, step, buf, lambda copy: copy.wait())

    @pl.when(s == 0)
    def _first():
        cbuf_ref[...] = jnp.zeros_like(cbuf_ref)  # a dead page's rows are masked, so must be finite:
        rbuf_ref[...] = jnp.zeros_like(rbuf_ref)  # zeros now, an earlier step's rows later
        par_ref[0] = 0  # the half that holds the slot's first step

    par = par_ref[0]

    @pl.when(jnp.logical_or(s == 0, lens_ref[jnp.maximum(s - 1, 0)] == 0))
    def _own_first_step():  # else the slot before started it from its last step
        start(s, 0, par)

    nxt = jnp.minimum(s + 1, S - 1)
    has_next = s + 1 < S
    qlat, qrope = qlat_ref[...], qrope_ref[...]  # [H, R], [H, Dr]
    H, R = qlat.shape

    def step(t, carry):
        m, l, acc = carry
        buf = (par + t) % 2
        last = t + 1 == steps

        @pl.when(jnp.logical_or(jnp.logical_not(last), has_next))
        def _next_step():  # this slot's, or the first of the next slot
            start(jnp.where(last, nxt, s), jnp.where(last, 0, t + 1), 1 - buf)

        wait(s, t, buf)
        c, r = cbuf_ref[buf], rbuf_ref[buf]  # [span, R], [Dr, span]
        sc = (lax.dot_general(qlat, c, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
              + lax.dot_general(qrope, r, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)) * scale  # [H, span]
        sc = jnp.where(t * span + lax.broadcasted_iota(jnp.int32, sc.shape, 1) < n, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + lax.dot_general(p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
                                           preferred_element_type=jnp.float32)
        return m_new, l, acc

    m, l, acc = lax.fori_loop(0, steps, step, (jnp.full((H, 1), _NEG, jnp.float32),
                                               jnp.zeros((H, 1), jnp.float32),
                                               jnp.zeros((H, R), jnp.float32)))

    par_ref[0] = (par + steps) % 2
    cn = cnew_ref[...].astype(jnp.float32)  # [1, R]
    rn = rnew_ref[...].astype(jnp.float32)
    sc = (jnp.sum(qlat.astype(jnp.float32) * cn, axis=-1, keepdims=True)
          + jnp.sum(qrope.astype(jnp.float32) * rn, axis=-1, keepdims=True)) * scale
    m_new = jnp.maximum(m, sc)
    corr, p = jnp.exp(m - m_new), jnp.exp(sc - m_new)
    o_ref[...] = ((acc * corr + p * cn) / (l * corr + p)).astype(o_ref.dtype)


def _pages_a_step(M: int, page_bytes: int) -> int:
    """The table's ``M`` pages in the fewest equal steps of at most ``_STEP_BYTES``."""
    steps = -(-M // max(1, min(M, _STEP_BYTES // page_bytes)))
    return -(-M // steps)


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
    S, H, R = q_lat.shape
    Dr = q_rope.shape[-1]
    M, page = tables.shape[1], c_pool.shape[2]
    pps = _pages_a_step(M, page * (R + Dr) * c_pool.dtype.itemsize)
    slot = lambda s, tables, lens: (s, 0, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_kernel, float(scale), page, pps, int(layer)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S,),
            in_specs=[
                pl.BlockSpec((None, H, R), slot),
                pl.BlockSpec((None, H, Dr), slot),
                pl.BlockSpec((None, 1, R), slot),
                pl.BlockSpec((None, 1, Dr), slot),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, H, R), slot),
            scratch_shapes=[pltpu.VMEM((2, pps * page, R), c_pool.dtype),
                            pltpu.VMEM((2, Dr, pps * page), r_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, H, R), q_lat.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name=DECODE_NAME,
        interpret=_interpret(),
    )(tables.astype(jnp.int32), lens.astype(jnp.int32), q_lat, q_rope,
      c_new[:, None, :], r_new[:, None, :], c_pool, r_pool)


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
