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
  already in it, every real row seeing its own block). A flash pass whose
  grid is the LIST of live (K/V head, query tile, K/V tile) pairs,
  :func:`tile_lists`' prefetched scalars: a pair is live where some row below
  the prompt's real length ``n_real`` sees a block of the K/V tile, and no
  other pair is read, computed or stepped over. Rows at or past ``n_real``
  come out ZERO. A tile's mask is made only where it is needed: the row's
  blocks widened to positions by one small product with a 0/1 matrix made
  from iotas where some row misses a block of the tile, the causal rule by
  position where the tile crosses the diagonal, one select over the scores
  of the ``R`` heads; none on a tile every row sees whole. No ``[T, T]``
  array exists.

Numerics: products in the input dtype on the MXU with fp32 accumulation
(the prefill takes the softmax's scale in ``q``), softmax statistics in
fp32, probabilities cast to the input dtype for the second product.
Off-TPU the kernels run through the Pallas interpreter; each has a ``jnp``
twin below (``*_reference``), the CPU tests' oracle.
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
# a grid step's flags: the low _FLAG_BITS of its ``cols`` entry, the K/V tile above them
_LIVE = 1  # a tile to fold
_FIRST, _LAST = 2, 4  # the query tile's first and last step
_BLOCKS = 8  # some row misses a block of the tile: the blocks widened to positions
_CAUSAL = 16  # a key of the tile lies past a row: the causal compare
_FLAG_BITS = 5
_TILE_BITS = 16  # ``rows``: the K/V head above them, the query tile below (shifts, no division)


def causal_steps(T: int, tq: int, tk: int) -> int:
    """The prefill grid's length a K/V head: the (query tile, K/V tile)
    pairs on or below the diagonal, the most a block-causal mask can make
    live."""
    return sum(min(T // tk, (i * tq + tq - 1) // tk + 1) for i in range(T // tq))


def tile_lists(blockmask, n_real, tq: int, tk: int, block: int):
    """The prefill's schedule. ``blockmask [G, T, NB]`` (0 or 1, block-causal;
    columns past ``T / block`` are padding), ``n_real`` the prompt's real
    length -> ``(rows, cols, live, masked)``: ``rows, cols [G * causal_steps]``
    int32, one grid step each: ``rows`` the K/V head and the query tile,
    ``g << 16 | i``, ``cols`` the K/V tile and the step's flags, ``j << 5 |
    flags``. The live pairs come query tile by query tile, K/V tiles in
    order: a pair is live where some row below ``n_real`` sees a block of the
    tile. A query tile whose
    first row is at or past ``n_real`` takes one step that writes zeros; the
    steps past the last repeat its tiles and do nothing. ``live``: the live
    pairs; ``masked``: those that need a mask (a row misses a block of the
    tile, or the tile crosses the diagonal)."""
    G, T, _ = blockmask.shape
    nq, nk, per = T // tq, T // tk, tk // block
    first_row = jnp.arange(nq) * tq
    bm = blockmask[:, :, :nk * per].reshape(G, nq, tq, nk, per) > 0
    real = (jnp.arange(T) < n_real).reshape(1, nq, tq, 1, 1)
    under = jnp.arange(nk)[None, :] * tk <= first_row[:, None] + tq - 1  # [nq, nk] on or below the diagonal
    live = (jnp.any(bm & real, axis=(2, 4)) & under).reshape(G * nq, nk)
    diag = (jnp.arange(nk)[None, :] + 1) * tk - 1 > first_row[:, None]
    masked = (~jnp.all(bm, axis=(2, 4)) * _BLOCKS + diag * _CAUSAL).reshape(G * nq, nk)
    padding = jnp.tile(first_row >= n_real, G)  # [G * nq]
    count = jnp.where(padding, 1, jnp.sum(live, axis=-1))
    end = jnp.cumsum(count)
    step = jnp.arange(G * causal_steps(T, tq, tk))
    tile = jnp.minimum(jnp.searchsorted(end, step, side="right"), G * nq - 1)
    at = step - (end - count)[tile]
    j = jnp.argsort(~live, axis=-1, stable=True)[tile, jnp.minimum(at, count[tile] - 1)]
    flags = (_FIRST * (at == 0) + _LAST * (at == count[tile] - 1)
             + jnp.where(padding[tile], 0, _LIVE + masked[tile, j]))
    flags = jnp.where(step < end[-1], flags, 0)
    rows = (tile // nq << _TILE_BITS | tile % nq).astype(jnp.int32)
    return (rows, (j << _FLAG_BITS | flags).astype(jnp.int32), jnp.sum(live).astype(jnp.int32),
            jnp.sum(live & (masked > 0)).astype(jnp.int32))


_LANES = 128  # the prefill's running maximum and normaliser, the same in every lane


def _lanes(x, width: int):
    """``[M, 128]`` the same in every lane -> ``[M, width]``."""
    return jnp.tile(x, (1, width // _LANES)) if width % _LANES == 0 else x[:, :width]


def _prefill_kernel(block, tq, tk, R, rows_ref, cols_ref, n_ref, q_ref, k_ref, v_ref, bm_ref,
                    o_ref, m_ref, l_ref, acc_ref, vis_ref):
    step = pl.program_id(0)
    i, col = rows_ref[step] & ((1 << _TILE_BITS) - 1), cols_ref[step]
    j, flags = col >> _FLAG_BITS, col & ((1 << _FLAG_BITS) - 1)
    D = q_ref.shape[-1]

    @pl.when((flags & _FIRST) != 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def fold(s, v):
        """One tile of scores into the running statistics, which are held
        the same in every lane (no lane broadcast a tile). A masked score is
        ``_NEG``: exp of it is 0 once the row has seen a key, and a row that
        has seen none yet keeps ``m = _NEG``, so what it sums is wiped by
        ``corr = 0`` at its first seen key."""
        m = m_ref[...]  # [R tq, 128]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, s.shape[-1]))
        corr = jnp.exp(m - m_new)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * _lanes(corr, D) + lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when((flags & _LIVE) != 0)
    def _tile():
        q = q_ref[...].reshape(R * tq, D)  # the softmax's scale is in q
        v = v_ref[...]
        s = lax.dot_general(q, k_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [R tq, tk]

        @pl.when((flags & (_BLOCKS | _CAUSAL)) == 0)
        def _whole():
            fold(s, v)

        @pl.when((flags & (_BLOCKS | _CAUSAL)) != 0)
        def _masked():
            vis_ref[...] = jnp.ones_like(vis_ref)

            @pl.when((flags & _BLOCKS) != 0)
            def _blocks():
                # the row's blocks widened to the tile's positions: one product
                # with the 0/1 matrix "position c lies in block b"
                bm = bm_ref[...]  # [tq, NB]
                in_block = j * (tk // block) + lax.broadcasted_iota(jnp.int32, (bm.shape[-1], tk), 1) // block
                widen = (lax.broadcasted_iota(jnp.int32, in_block.shape, 0) == in_block).astype(bm.dtype)
                vis_ref[...] = lax.dot_general(bm, widen, (((1,), (0,)), ((), ())),
                                               preferred_element_type=jnp.float32)

            @pl.when((flags & _CAUSAL) != 0)
            def _causal():
                key = j * tk + lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
                row = i * tq + lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
                vis_ref[...] = jnp.where(key <= row, vis_ref[...], 0.0)

            seen = vis_ref[...] > 0.5  # [tq, tk], the same for the R heads
            fold(jnp.where(seen[None], s.reshape(R, tq, tk), _NEG).reshape(R * tq, tk), v)

    @pl.when((flags & _LAST) != 0)
    def _out():
        o = (acc_ref[...] / _lanes(l_ref[...], D)).reshape(R, tq, D)
        row = i * tq + lax.broadcasted_iota(jnp.int32, o.shape, 1)
        o_ref[...] = jnp.where(row < n_ref[0], o, 0.0).astype(o_ref.dtype)


def sparse_prefill(
    q: jax.Array,  # [G, R, T, D] times the softmax's scale
    k: jax.Array,  # [G, T, D]
    v: jax.Array,
    blockmask: jax.Array,  # [G, T, NB] 0 or 1 in q's dtype: row t of head g sees block b
    n_real,  # int32: rows at or past it come out zero
    steps,  # (rows, cols, ...) of tile_lists
    *,
    block: int,
    tq: int,
    tk: int,
) -> jax.Array:
    """``o [G, R, T, D]``: row ``t < n_real`` attends over the positions
    ``<= t`` of its visible blocks; rows at or past ``n_real`` are zero.
    Every real row must see its own block (the kernel divides by the row's
    normaliser). One grid step a live (query tile, K/V tile) pair."""
    from jax.experimental.pallas import tpu as pltpu

    G, R, T, D = q.shape
    NB = blockmask.shape[-1]
    if T % tq or T % tk or tk % block:
        raise ValueError(f"{T} positions are no whole tiles of {tq} rows and {tk} keys of blocks of {block}")
    rows, cols = steps[0], steps[1]
    low = (1 << _TILE_BITS) - 1

    def q_at(s, rows, cols, n):
        return (rows[s] >> _TILE_BITS, 0, rows[s] & low, 0)

    def kv_at(s, rows, cols, n):
        return (rows[s] >> _TILE_BITS, cols[s] >> _FLAG_BITS, 0)

    def bm_at(s, rows, cols, n):
        return (rows[s] >> _TILE_BITS, rows[s] & low, 0)

    return pl.pallas_call(
        functools.partial(_prefill_kernel, int(block), tq, tk, R),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows.shape[0],),
            in_specs=[pl.BlockSpec((None, R, tq, D), q_at),
                      pl.BlockSpec((None, tk, D), kv_at), pl.BlockSpec((None, tk, D), kv_at),
                      pl.BlockSpec((None, tq, NB), bm_at)],
            out_specs=pl.BlockSpec((None, R, tq, D), q_at),
            scratch_shapes=[pltpu.VMEM((R * tq, _LANES), jnp.float32), pltpu.VMEM((R * tq, _LANES), jnp.float32),
                            pltpu.VMEM((R * tq, D), jnp.float32), pltpu.VMEM((tq, tk), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name=PREFILL_NAME,
        interpret=_interpret(),
    )(rows, cols, jnp.asarray(n_real, jnp.int32).reshape(1), q, k, v, blockmask)


def sparse_prefill_reference(q, k, v, blockmask, n_real, *, block: int):
    """The prefill kernel's ``jnp`` twin: the whole ``[T, T]`` mask (small
    sizes only); fp32 throughout; ``q`` holds the scale, rows at or past
    ``n_real`` are zero."""
    G, R, T, D = q.shape
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    pos = jnp.arange(T)
    by_block = jnp.take(blockmask > 0, pos // block, axis=-1)  # [G, T, T]
    seen = by_block & (pos[None, :] <= pos[:, None])[None]
    s = jnp.einsum("grtd,gsd->grts", q.astype(f32), k.astype(f32), precision=hi)
    p = jax.nn.softmax(jnp.where(seen[:, None], s, -jnp.inf), axis=-1)
    o = jnp.einsum("grts,gsd->grtd", p, v.astype(f32), precision=hi)
    return jnp.where((pos < n_real)[:, None], o, 0.0).astype(q.dtype)
