"""Attention over blocks of keys chosen by the data: the decode step's walk
over a CHOSEN list of pages, the prefill's flash pass under a per-row block
mask, and the one write of a step's new rows into two paged pools.

BEYOND-PARITY EXTENSION (the reference has no attention; SURVEY.md §5.7).
A block-sparse layer (InfLLM-V2 as MiniCPM4 ships it) lets the query at
position ``t`` see a few BLOCKS of keys: the first, those of a local window,
and the best-scored of the rest (the choosing is the model's,
``models/minicpm_sala.py``). Queries come in groups of ``R`` heads that share
one K/V head and one choice. The pools hold one block a page,

    k_pool, v_pool  [L, n_pages + 1, page, G * D]

a position's ``G`` K/V heads side by side in one lane-dense row, so a kernel
takes head ``g`` of a page as the block ``(page, D)`` at lane block ``g``.

- :func:`sparse_decode`: one new position a slot. The chosen pages of every
  (slot, K/V head) reach the index maps as prefetched scalars (physical page
  ids, their logical block numbers, how many there are); the kernel reads
  THOSE pages only, ``_PAGES_A_STEP`` a grid step, and keeps an online
  softmax in fp32 over them. Past the last chosen page the block index
  repeats (no copy) and the body does nothing. The step's own position is
  not in the pools yet: its K and V rows come as operands and join the
  softmax last. Positions at or past ``lens[s]`` (the tail of the newest
  block) are masked.
- :func:`sparse_cache_write`: the new K and V rows of ALL block-sparse
  layers into the pools in one call after the last layer, the pools that
  call's aliased outputs (``ops/pallas_mla.py`` says why: every read of a
  pool precedes its one write, a donated pool is updated in place, no
  scatter of single positions).
- :func:`sparse_prefill`: one prompt, every row its own set of visible
  blocks (``blockmask [G, T, NB]``, 0 or 1, the block-level causal rule
  already in it). A flash pass over ``(K/V head, query tile, K/V tile)``
  whose K/V tiles are the LIST of tiles that some row of the query tile sees
  (:func:`tile_lists`, prefetched scalars): the others are neither read nor
  computed. Inside a tile the row's blocks are widened to positions by one
  small product with a 0/1 matrix made from iotas, and the causal rule by
  position is added. No ``[T, T]`` array exists.

Numerics: products in the input dtype on the MXU with fp32 accumulation,
softmax statistics in fp32, probabilities cast to the input dtype for the
second product. Off-TPU the kernels run through the Pallas interpreter; each
has a ``jnp`` twin below (``*_reference``), the CPU tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

DECODE_NAME = "sparse_decode"  # the kernels' names in a device trace
PREFILL_NAME = "sparse_prefill"
WRITE_NAME = "sparse_cache_write"
_PAGES_A_STEP = 8  # pages a grid step of the decode kernel reads (one block each)
_NEG = -1e30


def _online_softmax_step(s, seen, v, m_ref, l_ref, acc_ref):
    """Fold one tile of masked scores ``s`` (fp32) and its values into the
    running maximum, normaliser and accumulator."""
    s = jnp.where(seen, s, _NEG)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((p.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _decode_kernel(scale, page, pps, G, sp_ref, sb_ref, cnt_ref, lens_ref, q_ref, kn_ref,
                   vn_ref, *rest):
    del sp_ref  # read by the index maps
    k_refs, v_refs = rest[:pps], rest[pps:2 * pps]
    o_ref, m_ref, l_ref, acc_ref = rest[2 * pps:]
    j = pl.program_id(2)
    row = pl.program_id(0) * G + pl.program_id(1)
    n, cnt = lens_ref[pl.program_id(0)], cnt_ref[row]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...]  # [R, D]
    for i in range(pps):
        at = j * pps + i

        @pl.when(at < cnt)
        def _page(i=i, at=at):
            k, v = k_refs[i][...], v_refs[i][...]  # [page, D]
            s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
            start = sb_ref[row, at] * page
            seen = start + lax.broadcasted_iota(jnp.int32, s.shape, 1) < n
            _online_softmax_step(s, seen, v, m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _own_position_and_out():
        kn = kn_ref[...].astype(jnp.float32)  # [1, D]
        vn = vn_ref[...].astype(jnp.float32)
        s = jnp.sum(q.astype(jnp.float32) * kn, axis=-1, keepdims=True) * scale
        m = m_ref[...]
        m_new = jnp.maximum(m, s)
        corr, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        o_ref[...] = ((acc_ref[...] * corr + p * vn) / (l_ref[...] * corr + p)).astype(o_ref.dtype)


def sparse_decode(
    q: jax.Array,  # [S, G, R, D] the slot's queries, R heads to a K/V head
    k_new: jax.Array,  # [S, G, D] the step's own key row
    v_new: jax.Array,  # [S, G, D] and value row
    k_pool: jax.Array,  # [L, n_pages + 1, page, G * D]
    v_pool: jax.Array,
    sel_pages: jax.Array,  # [S, G, N] int32 the chosen pages (physical ids), in block order
    sel_blocks: jax.Array,  # [S, G, N] int32 their logical block numbers
    counts: jax.Array,  # [S, G] int32 how many of the N are chosen
    lens: jax.Array,  # [S] int32 cached positions of every slot
    *,
    layer: int,
    scale: float,
) -> jax.Array:
    """``o [S, G, R, D]``: every group of heads attending over the cached
    positions (below ``lens[s]``) of its chosen pages of ``layer`` and over
    the step's own row."""
    from jax.experimental.pallas import tpu as pltpu

    S, G, R, D = q.shape
    N, page = sel_pages.shape[-1], k_pool.shape[2]
    pps = min(_PAGES_A_STEP, N)
    layer = int(layer)

    def page_at(i):
        def at(s, g, j, sp, sb, cnt, lens):
            row = s * G + g
            last = jnp.maximum(cnt[row] - 1, 0)
            return (layer, sp[row, jnp.minimum(jnp.minimum(j * pps + i, last), N - 1)], 0, g)
        return at

    own = lambda s, g, j, *_: (s, g, 0, 0)  # noqa: E731
    a_page = [pl.BlockSpec((None, None, page, D), page_at(i)) for i in range(pps)]
    flat = lambda a: a.reshape(S * G, -1).astype(jnp.int32)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_decode_kernel, float(scale), page, pps, G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(S, G, -(-N // pps)),
            in_specs=[pl.BlockSpec((None, None, R, D), own),
                      pl.BlockSpec((None, None, 1, D), own),
                      pl.BlockSpec((None, None, 1, D), own), *a_page, *a_page],
            out_specs=pl.BlockSpec((None, None, R, D), own),
            scratch_shapes=[pltpu.VMEM((R, 1), jnp.float32), pltpu.VMEM((R, 1), jnp.float32),
                            pltpu.VMEM((R, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name=DECODE_NAME,
        interpret=_interpret(),
    )(flat(sel_pages), flat(sel_blocks), counts.reshape(S * G).astype(jnp.int32),
      lens.astype(jnp.int32), q, k_new[:, :, None, :], v_new[:, :, None, :],
      *[k_pool] * pps, *[v_pool] * pps)


def sparse_decode_reference(q, k_new, v_new, k_pool, v_pool, sel_pages, sel_blocks, counts, lens,
                            *, layer: int, scale: float):
    """The decode kernel's ``jnp`` twin: the chosen pages gathered, masked
    past ``counts`` and past ``lens``; fp32 throughout."""
    S, G, R, D = q.shape
    N, page = sel_pages.shape[-1], k_pool.shape[2]
    f32, hi = jnp.float32, lax.Precision.HIGHEST

    def rows(pool):  # [S, G, N, page, D]: head g's lanes of the pages chosen for (s, g)
        got = pool[layer][sel_pages].reshape(S, G, N, page, G, D)
        return jnp.stack([got[:, g, :, :, g] for g in range(G)], axis=1).astype(f32)

    k, v = rows(k_pool), rows(v_pool)
    pos = sel_blocks[..., None] * page + jnp.arange(page)  # [S, G, N, page]
    seen = (jnp.arange(N)[None, None, :, None] < counts[..., None, None]) & (pos < lens[:, None, None, None])
    s = jnp.einsum("sgrd,sgnpd->sgrnp", q.astype(f32), k, precision=hi) * scale
    s = jnp.where(seen[:, :, None], s, -jnp.inf).reshape(S, G, R, N * page)
    own = jnp.einsum("sgrd,sgd->sgr", q.astype(f32), k_new.astype(f32), precision=hi) * scale
    p = jax.nn.softmax(jnp.concatenate([s, own[..., None]], axis=-1), axis=-1)
    o = jnp.einsum("sgrt,sgtd->sgrd", p[..., :-1], v.reshape(S, G, N * page, D), precision=hi)
    return (o + p[..., -1:] * v_new.astype(f32)[:, :, None, :]).astype(q.dtype)


def _write_kernel(page, wpage_ref, lens_ref, kn_ref, vn_ref, kw_ref, vw_ref, ko_ref, vo_ref):
    del wpage_ref  # read by the index maps
    off = lens_ref[pl.program_id(1)] % page
    kw, vw = kw_ref[...], vw_ref[...]  # [page, W]
    here = lax.broadcasted_iota(jnp.int32, kw.shape, 0) == off
    ko_ref[...] = jnp.where(here, kn_ref[...].astype(kw.dtype), kw)
    vo_ref[...] = jnp.where(here, vn_ref[...].astype(vw.dtype), vw)


def sparse_cache_write(
    k_pool: jax.Array,  # [L, n_pages + 1, page, W]
    v_pool: jax.Array,
    k_rows: jax.Array,  # [L, S, W] every paged layer's new key row of every slot
    v_rows: jax.Array,
    write_page: jax.Array,  # [S] int32 the page they go to (scratch for an inactive slot)
    lens: jax.Array,  # [S] int32 their position; the offset in the page is lens % page
):
    """-> the pools with the rows in place (the pools are aliased to the
    outputs: in place when the caller's pools are donated). A slot's page is
    read, the row put in, and the page written back: no scatter."""
    from jax.experimental.pallas import tpu as pltpu

    L, S, W = k_rows.shape
    page = k_pool.shape[2]
    row = lambda l, s, wpage, lens: (l, s, 0, 0)  # noqa: E731
    own = lambda l, s, wpage, lens: (l, wpage[s], 0, 0)  # noqa: E731
    a_row, a_page = pl.BlockSpec((None, None, 1, W), row), pl.BlockSpec((None, None, page, W), own)
    return pl.pallas_call(
        functools.partial(_write_kernel, page),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L, S),
            in_specs=[a_row, a_row, a_page, a_page], out_specs=(a_page, a_page)),
        out_shape=(jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)),
        input_output_aliases={4: 0, 5: 1},
        name=WRITE_NAME,
        interpret=_interpret(),
    )(write_page.astype(jnp.int32), lens.astype(jnp.int32), k_rows[:, :, None, :],
      v_rows[:, :, None, :], k_pool, v_pool)


# -- prefill ------------------------------------------------------------------
def tile_lists(blockmask, tq: int, tk: int, block: int):
    """``blockmask [G, T, NB]`` (0 or 1; columns past ``T / block`` are
    padding) -> (``tiles [G, T / tq, T / tk]`` int32: the K/V tiles that some
    row of the query tile sees, in order, first; ``counts [G, T / tq]``)."""
    G, T, _ = blockmask.shape
    nq, nk, per = T // tq, T // tk, tk // block
    seen = blockmask[:, :, :nk * per].reshape(G, nq, tq, nk, per) > 0
    seen = jnp.any(seen, axis=(2, 4))  # [G, nq, nk]
    order = jnp.argsort(~seen, axis=-1, stable=True)
    return order.astype(jnp.int32), jnp.sum(seen, axis=-1).astype(jnp.int32)


def _prefill_kernel(scale, block, tq, tk, R, nq, tiles_ref, cnt_ref, q_ref, k_ref, v_ref, bm_ref,
                    o_ref, m_ref, l_ref, acc_ref):
    i, step = pl.program_id(1), pl.program_id(2)
    row = pl.program_id(0) * nq + i
    D = q_ref.shape[-1]

    @pl.when(step == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(step < cnt_ref[row])
    def _tile():
        first = tiles_ref[row, step] * tk  # the tile's first key position
        q = q_ref[...].reshape(R * tq, D)
        k, v = k_ref[...], v_ref[...]  # [tk, D]
        s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        # the row's visible blocks widened to this tile's positions: one
        # product with the 0/1 matrix "position c lies in block b"
        bm = bm_ref[...]  # [tq, NB]
        nb = bm.shape[-1]
        at = first + lax.broadcasted_iota(jnp.int32, (nb, tk), 1)
        lo = lax.broadcasted_iota(jnp.int32, (nb, tk), 0) * block
        widen = ((at >= lo) & (at < lo + block)).astype(bm.dtype)
        by_block = lax.dot_general(bm, widen, (((1,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32) > 0.5
        key_pos = first + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        row_pos = i * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        seen = by_block & (key_pos <= row_pos)  # [tq, tk], the same for the R heads
        seen = jnp.broadcast_to(seen[None], (R, tq, tk)).reshape(R * tq, tk)
        _online_softmax_step(s, seen, v, m_ref, l_ref, acc_ref)

    @pl.when(step == pl.num_programs(2) - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / l_ref[...]).reshape(R, tq, D).astype(o_ref.dtype)


def sparse_prefill(
    q: jax.Array,  # [G, R, T, D]
    k: jax.Array,  # [G, T, D]
    v: jax.Array,
    blockmask: jax.Array,  # [G, T, NB] 0 or 1 in q's dtype: row t of head g sees block b
    tiles: jax.Array,  # [G, T / tq, T / tk] int32 (tile_lists)
    counts: jax.Array,  # [G, T / tq] int32
    *,
    scale: float,
    block: int,
    tq: int,
    tk: int,
) -> jax.Array:
    """``o [G, R, T, D]``: row ``t`` attends over the positions ``<= t`` of
    its visible blocks. Every row must see its own block (the kernel divides
    by the row's normaliser)."""
    from jax.experimental.pallas import tpu as pltpu

    G, R, T, D = q.shape
    NB = blockmask.shape[-1]
    if T % tq or T % tk or tk % block:
        raise ValueError(f"{T} positions are no whole tiles of {tq} rows and {tk} keys of blocks of {block}")
    nq, nk = T // tq, T // tk

    def kv_at(g, i, j, tiles, cnt):
        row = g * nq + i
        return (g, tiles[row, jnp.minimum(j, jnp.maximum(cnt[row] - 1, 0))], 0)

    rows = lambda g, i, j, *_: (g, 0, i, 0)  # noqa: E731
    return pl.pallas_call(
        functools.partial(_prefill_kernel, float(scale), int(block), tq, tk, R, nq),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(G, nq, nk),
            in_specs=[pl.BlockSpec((None, R, tq, D), rows),
                      pl.BlockSpec((None, tk, D), kv_at), pl.BlockSpec((None, tk, D), kv_at),
                      pl.BlockSpec((None, tq, NB), lambda g, i, j, *_: (g, i, 0))],
            out_specs=pl.BlockSpec((None, R, tq, D), rows),
            scratch_shapes=[pltpu.VMEM((R * tq, 1), jnp.float32), pltpu.VMEM((R * tq, 1), jnp.float32),
                            pltpu.VMEM((R * tq, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name=PREFILL_NAME,
        interpret=_interpret(),
    )(tiles.reshape(G * nq, nk).astype(jnp.int32), counts.reshape(G * nq).astype(jnp.int32),
      q, k, v, blockmask)


def sparse_prefill_reference(q, k, v, blockmask, *, scale: float, block: int):
    """The prefill kernel's ``jnp`` twin: the whole ``[T, T]`` mask (small
    sizes only); fp32 throughout."""
    G, R, T, D = q.shape
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    pos = jnp.arange(T)
    by_block = jnp.take(blockmask > 0, pos // block, axis=-1)  # [G, T, T]
    seen = by_block & (pos[None, :] <= pos[:, None])[None]
    s = jnp.einsum("grtd,gsd->grts", q.astype(f32), k.astype(f32), precision=hi) * scale
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("grts,gsd->grtd", p, v.astype(f32), precision=hi).astype(q.dtype)
