"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
ICLR 2023; EvaByte's ``attention_class`` ``eva``) over a served cache: the
decode step's walk over a slot's compressed rows and its window's ring, the
prefill's flash pass over a window block and the compressed rows before it,
the compression of a chunk, and the one write of a step's new rows.

BEYOND-PARITY EXTENSION (the reference has no attention; SURVEY.md §5.7).
The query at position ``t`` (window block ``w = t // W``) sees, in ONE
softmax, the exact keys ``wW .. t`` of its own block and ONE compressed key
and value per chunk of ``C`` positions of every earlier block. The cache
holds the two kinds apart (``models/evabyte.py cache_spec``):

    ring     [L, S, 2, W, H * D]        K and V of the current block a slot
    k_pages  [L, n_pages + 1, W / C, H * D]   compressed rows, a page a block
    v_pages  (the same)

a position's heads side by side in one lane-dense row. The ring is written
at ``t % W``; its live rows are ``0 .. t % W - 1`` (a block-aligned window,
so a new block starts at row 0 and no row is ever "the previous window's"
by content: rows past the length are simply not read). Page ``b`` of a slot
holds the ``W / C`` compressed rows of its block ``b``, written as each
chunk completes and read only once the query has moved to a later block.

- :func:`eva_decode`: one new position a slot. Grid ``(slot, step)``; the
  slot's length is a prefetched scalar, its page table another. Steps
  ``0 .. w - 1`` each read one whole page of compressed K and V (all
  visible), steps ``w .. w + ceil(r / tile) - 1`` one ring tile (rows
  ``< r = t % W`` visible); past them the block indices repeat (no copy)
  and the body does nothing. All heads at once: the query is held as the
  block-diagonal ``[H, H D]`` (head ``h``'s row carries ``q_h`` in its own
  lanes, zeros elsewhere), so a tile's scores are ONE product ``[H, rows]``
  and its values ONE product ``[H, H D]`` whose diagonal blocks are the
  heads' outputs. The step's own position is not cached yet: its K and V
  rows come as operands and join the softmax last.
- :func:`eva_cache_write`: the new ring rows of ALL layers and the
  compressed rows of the chunks that completed, in one call after the last
  layer; the pools are the call's aliased outputs, a 16-row block read,
  the row put in, the block written back (no scatter of single positions:
  ``ops/pallas_mla.py`` says why).
- :func:`eva_prefill`: one prompt, heads ``[H, T, D]``. A flash pass over
  ``(head, query tile, step)``: a query tile (inside one window block
  ``w``) visits the compressed tiles holding rows ``0 .. w W / C - 1``,
  then the exact tiles of its block up to its diagonal. The counts follow
  from the tile's index alone: no prefetch, no ``[T, T]`` array.
- :func:`eva_compress`: a chunk's compressed key and value from its ``C``
  (rotated) keys and values: ``softmax_j(mu . x_j)`` and
  ``softmax_j(phi . x_j - |x_j|^2 / 2)`` over ``x_j = D^-1/4 k_j``, in fp32
  (plain ``jnp`` under the scope ``eva_compress``: ``C`` rows of ``H D``).

Numerics: products in the input dtype on the MXU with fp32 accumulation,
softmax statistics in fp32, probabilities cast to the input dtype for the
second product. Off-TPU the kernels run through the Pallas interpreter; each
has a ``jnp`` twin below (``*_reference``), the CPU tests' oracle.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

DECODE_NAME = "eva_decode"  # the kernels' names in a device trace
PREFILL_NAME = "eva_prefill"
WRITE_NAME = "eva_cache_write"
COMPRESS_SCOPE = "eva_compress"
_WRITE_ROWS = 16  # rows of the block the write kernel reads and writes back (a bf16 sublane tile)
_NEG = -1e30


def _fold(s, seen, v, m_ref, l_ref, acc_ref):
    """Fold one tile of scores ``s`` (fp32, masked by ``seen``) and its
    values into the running maximum, normaliser and accumulator."""
    s = jnp.where(seen, s, _NEG)
    m = m_ref[...]
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
    corr = jnp.exp(m - m_new)
    l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
        p.astype(v.dtype), v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new


def _scores(q, k, scale):
    return lax.dot_general(q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32) * scale


# -- decode -------------------------------------------------------------------
def _decode_kernel(scale, W, tile, D, n_steps, tables_ref, lens_ref, q_ref, kn_ref, vn_ref,
                   rk_ref, rv_ref, ck_ref, cv_ref, o_ref, qh_ref, m_ref, l_ref, acc_ref):
    del tables_ref  # read by the index maps
    j = pl.program_id(1)
    t = lens_ref[pl.program_id(0)]
    w, r = t // W, t % W
    H, HD = qh_ref.shape

    @pl.when(j == 0)
    def _init():
        lane = lax.broadcasted_iota(jnp.int32, (H, HD), 1) // D
        head = lax.broadcasted_iota(jnp.int32, (H, HD), 0)
        q = jnp.broadcast_to(q_ref[...].astype(jnp.float32), (H, HD))  # selected in fp32: a bf16 mask re-lays out
        qh_ref[...] = jnp.where(lane == head, q, 0.0).astype(qh_ref.dtype)
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < w)
    def _compressed_page():
        s = _scores(qh_ref[...], ck_ref[...], scale)  # [H, W / C]: every row of an earlier block is seen
        _fold(s, jnp.ones(s.shape, jnp.bool_), cv_ref[...], m_ref, l_ref, acc_ref)

    @pl.when((j >= w) & ((j - w) * tile < r))
    def _ring_tile():
        s = _scores(qh_ref[...], rk_ref[...], scale)  # [H, tile]
        seen = (j - w) * tile + lax.broadcasted_iota(jnp.int32, s.shape, 1) < r
        _fold(s, seen, rv_ref[...], m_ref, l_ref, acc_ref)

    @pl.when(j == n_steps - 1)
    def _own_position_and_out():
        qf = qh_ref[...].astype(jnp.float32)
        s = jnp.sum(qf * kn_ref[...].astype(jnp.float32), axis=-1, keepdims=True) * scale  # [H, 1]
        m = m_ref[...]
        m_new = jnp.maximum(m, s)
        corr, p = jnp.exp(m - m_new), jnp.exp(s - m_new)
        o = (acc_ref[...] * corr + p * vn_ref[...].astype(jnp.float32)) / (l_ref[...] * corr + p)
        mine = lax.broadcasted_iota(jnp.int32, (H, HD), 1) // D == lax.broadcasted_iota(jnp.int32, (H, HD), 0)
        o_ref[...] = jnp.sum(jnp.where(mine, o, 0.0), axis=0, keepdims=True).astype(o_ref.dtype)


def eva_decode(
    q: jax.Array,  # [S, H * D] the slots' queries, rotated
    k_new: jax.Array,  # [S, H * D] the step's own key row, rotated
    v_new: jax.Array,  # [S, H * D] and value row
    ring: jax.Array,  # [L, S, 2, W, H * D] K (0) and V (1) of each slot's current block
    k_pages: jax.Array,  # [L, n_pages + 1, W / C, H * D] compressed keys, a page a block
    v_pages: jax.Array,  # and values
    tables: jax.Array,  # [S, M] int32 each slot's pages, block by block
    lens: jax.Array,  # [S] int32 each slot's position t (cached positions)
    *,
    layer: int,
    n_heads: int,
    scale: float,
    tile: int,
) -> jax.Array:
    """``o [S, H * D]``: every slot's heads over the compressed rows of its
    earlier blocks, the ring rows ``< t % W`` and its own row, one softmax.
    Reads those rows only."""
    from jax.experimental.pallas import tpu as pltpu

    S, HD = q.shape
    W, rp, M = ring.shape[3], k_pages.shape[2], tables.shape[1]
    if W % tile:
        raise ValueError(f"a window of {W} is no whole tiles of {tile} rows")
    n_steps = (M - 1) + W // tile  # t < M W: at most M - 1 earlier blocks, and the ring
    layer = int(layer)

    def _wr(t):
        return t // W, (t % W + tile - 1) // tile

    def page_at(s, j, tables, lens):
        w, _ = _wr(lens[s])
        return (layer, tables[s * M + jnp.minimum(j, jnp.maximum(w - 1, 0))], 0, 0)

    def ring_at(half):
        def at(s, j, tables, lens):
            w, nr = _wr(lens[s])
            return (layer, s, half, jnp.clip(j - w, 0, jnp.maximum(nr - 1, 0)), 0)
        return at

    own = lambda s, j, *_: (s, 0, 0)  # noqa: E731
    a_row = pl.BlockSpec((None, 1, HD), own)
    a_page = pl.BlockSpec((None, None, rp, HD), page_at)
    o = pl.pallas_call(
        functools.partial(_decode_kernel, float(scale), W, tile, HD // n_heads, n_steps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(S, n_steps),
            in_specs=[a_row, a_row, a_row,
                      pl.BlockSpec((None, None, None, tile, HD), ring_at(0)),
                      pl.BlockSpec((None, None, None, tile, HD), ring_at(1)), a_page, a_page],
            out_specs=a_row,
            scratch_shapes=[pltpu.VMEM((n_heads, HD), q.dtype), pltpu.VMEM((n_heads, 1), jnp.float32),
                            pltpu.VMEM((n_heads, 1), jnp.float32), pltpu.VMEM((n_heads, HD), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, HD), q.dtype),
        name=DECODE_NAME,
        interpret=_interpret(),
    )(tables.reshape(-1).astype(jnp.int32), lens.astype(jnp.int32), q[:, None], k_new[:, None],
      v_new[:, None], ring, ring, k_pages, v_pages)
    return o[:, 0]


def eva_decode_reference(q, k_new, v_new, ring, k_pages, v_pages, tables, lens, *, layer: int,
                         n_heads: int, scale: float, tile: int = 0):
    """The decode kernel's ``jnp`` twin: the slot's whole ring and every page
    of its table gathered, masked by length; fp32 throughout."""
    del tile
    S, HD = q.shape
    W, rp, M = ring.shape[3], k_pages.shape[2], tables.shape[1]
    D, f32, hi = HD // n_heads, jnp.float32, lax.Precision.HIGHEST
    heads = lambda a: a.reshape(*a.shape[:-1], n_heads, D).astype(f32)  # noqa: E731
    w, r = lens // W, lens % W
    keys = jnp.concatenate([heads(k_pages[layer][tables].reshape(S, M * rp, HD)), heads(ring[layer, :, 0])], 1)
    vals = jnp.concatenate([heads(v_pages[layer][tables].reshape(S, M * rp, HD)), heads(ring[layer, :, 1])], 1)
    seen = jnp.concatenate([jnp.arange(M * rp)[None] < (w * rp)[:, None], jnp.arange(W)[None] < r[:, None]], 1)
    qh = heads(q)
    s = jnp.einsum("shd,sjhd->shj", qh, keys, precision=hi) * scale
    s = jnp.where(seen[:, None], s, -jnp.inf)
    own = jnp.einsum("shd,shd->sh", qh, heads(k_new), precision=hi)[..., None] * scale
    p = jax.nn.softmax(jnp.concatenate([s, own], -1), axis=-1)
    o = jnp.einsum("shj,sjhd->shd", p[..., :-1], vals, precision=hi) + p[..., -1:] * heads(v_new)
    return o.reshape(S, HD).astype(q.dtype)


# -- the one write of a decode step -------------------------------------------
def _write_kernel(W, C, rows, comp_page_ref, lens_ref, kv_ref, ck_ref, cv_ref, ring_ref, kp_ref, vp_ref,
                  ring_out, kp_out, vp_out):
    del comp_page_ref  # read by the index maps
    f32 = jnp.float32  # the rows are selected in fp32 (exact for the cache's bf16): a bf16 mask re-lays out
    r = lens_ref[pl.program_id(1)] % W
    for half in (0, 1):
        ring = ring_ref[half].astype(f32)  # [rows, HD]
        here = lax.broadcasted_iota(jnp.int32, ring.shape, 0) == r % rows
        ring_out[half] = jnp.where(here, kv_ref[half].astype(f32), ring).astype(ring_out.dtype)
    crows = kp_ref.shape[0]
    here = lax.broadcasted_iota(jnp.int32, kp_ref.shape, 0) == (r // C) % crows
    kp_out[...] = jnp.where(here, ck_ref[...].astype(f32), kp_ref[...].astype(f32)).astype(kp_out.dtype)
    vp_out[...] = jnp.where(here, cv_ref[...].astype(f32), vp_ref[...].astype(f32)).astype(vp_out.dtype)


def eva_cache_write(
    ring: jax.Array,  # [L, S, 2, W, HD]
    k_pages: jax.Array,  # [L, n_pages + 1, W / C, HD]
    v_pages: jax.Array,
    kv_rows: jax.Array,  # [L, S, 2, HD] every layer's new K and V row of every slot
    ck_rows: jax.Array,  # [L, S, HD] the compressed key of the chunk the step completes
    cv_rows: jax.Array,  # [L, S, HD]
    comp_page: jax.Array,  # [S] int32 its page (the scratch page where no chunk completes)
    lens: jax.Array,  # [S] int32 the step's position t: ring row t % W, compressed row (t % W) // C
    *,
    chunk: int,
):
    """-> ``(ring, k_pages, v_pages)`` with the rows in place (aliased to the
    inputs: in place when the caller's pools are donated)."""
    from jax.experimental.pallas import tpu as pltpu

    L, S, _, HD = kv_rows.shape
    W, rp = ring.shape[3], k_pages.shape[2]
    rows, crows = min(_WRITE_ROWS, W), min(_WRITE_ROWS, rp)
    C = int(chunk)
    a_row = pl.BlockSpec((None, None, 1, HD), lambda l, s, *_: (l, s, 0, 0))
    a_ring = pl.BlockSpec((None, None, 2, rows, HD), lambda l, s, cp, lens: (l, s, 0, (lens[s] % W) // rows, 0))
    a_page = pl.BlockSpec((None, None, crows, HD), lambda l, s, cp, lens: (l, cp[s], (lens[s] % W) // C // crows, 0))
    return pl.pallas_call(
        functools.partial(_write_kernel, W, C, rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(L, S),
            in_specs=[pl.BlockSpec((None, None, 2, 1, HD), lambda l, s, *_: (l, s, 0, 0, 0)), a_row, a_row,
                      a_ring, a_page, a_page],
            out_specs=(a_ring, a_page, a_page)),
        out_shape=(jax.ShapeDtypeStruct(ring.shape, ring.dtype),
                   jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
                   jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype)),
        input_output_aliases={5: 0, 6: 1, 7: 2},
        name=WRITE_NAME,
        interpret=_interpret(),
    )(comp_page.astype(jnp.int32), lens.astype(jnp.int32), kv_rows[:, :, :, None, :], ck_rows[:, :, None, :],
      cv_rows[:, :, None, :], ring, k_pages, v_pages)


# -- prefill ------------------------------------------------------------------
def _prefill_plan(i, W, rp, tq, tk, tkc):
    """Query tile ``i``'s visit: (compressed tiles, first exact tile, exact
    tiles, compressed rows seen). Python ints or traced scalars alike."""
    w = (i * tq) // W
    n_rows = w * rp
    nc = (n_rows + tkc - 1) // tkc
    first = (w * W) // tk
    return nc, first, (i * tq + tq - 1) // tk - first + 1, n_rows


def _prefill_kernel(scale, W, rp, tq, tk, tkc, q_ref, k_ref, v_ref, kc_ref, vc_ref, o_ref, m_ref, l_ref, acc_ref):
    i, j = pl.program_id(1), pl.program_id(2)
    nc, first, ne, n_rows = _prefill_plan(i, W, rp, tq, tk, tkc)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(j < nc)
    def _compressed_tile():
        s = _scores(q_ref[...], kc_ref[...], scale)  # [tq, tkc]
        seen = j * tkc + lax.broadcasted_iota(jnp.int32, s.shape, 1) < n_rows
        _fold(s, seen, vc_ref[...], m_ref, l_ref, acc_ref)

    @pl.when((j >= nc) & (j < nc + ne))
    def _exact_tile():
        s = _scores(q_ref[...], k_ref[...], scale)  # [tq, tk]
        key = (first + j - nc) * tk + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = key <= i * tq + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        _fold(s, seen, v_ref[...], m_ref, l_ref, acc_ref)

    @pl.when(j == pl.num_programs(2) - 1)
    def _out():
        o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def eva_prefill(
    q: jax.Array,  # [H, T, D] rotated
    k: jax.Array,  # [H, T, D] rotated
    v: jax.Array,
    kc: jax.Array,  # [H, T / C, D] the compressed keys of every chunk
    vc: jax.Array,
    *,
    window: int,
    chunk: int,
    scale: float,
    tq: int,
    tk: int,
) -> jax.Array:
    """``o [H, T, D]``: row ``t`` over the keys ``w W .. t`` of its window
    block ``w`` and the compressed rows of every chunk before ``w W``, one
    softmax. ``T`` is whole windows; a window whole query and key tiles."""
    from jax.experimental.pallas import tpu as pltpu

    H, T, D = q.shape
    W, C = int(window), int(chunk)
    rp, nC = W // C, kc.shape[1]
    tq, tk = min(tq, W), min(tk, W)
    tkc = math.gcd(tk, nC)
    if T % W or W % tq or W % tk or W % C or nC != T // C:
        raise ValueError(f"{T} positions: no whole windows of {W}, tiles of {tq} / {tk} or chunks of {C}")
    n_steps = max(sum(_prefill_plan(i, W, rp, tq, tk, tkc)[::2]) for i in range(T // tq))

    def kv_at(h, i, j):
        nc, first, ne, _ = _prefill_plan(i, W, rp, tq, tk, tkc)
        return (h, first + jnp.clip(j - nc, 0, ne - 1), 0)

    def kc_at(h, i, j):
        nc = _prefill_plan(i, W, rp, tq, tk, tkc)[0]
        return (h, jnp.clip(j, 0, jnp.maximum(nc - 1, 0)), 0)

    rows = pl.BlockSpec((None, tq, D), lambda h, i, j: (h, i, 0))
    return pl.pallas_call(
        functools.partial(_prefill_kernel, float(scale), W, rp, tq, tk, tkc),
        grid=(H, T // tq, n_steps),
        in_specs=[rows, pl.BlockSpec((None, tk, D), kv_at), pl.BlockSpec((None, tk, D), kv_at),
                  pl.BlockSpec((None, tkc, D), kc_at), pl.BlockSpec((None, tkc, D), kc_at)],
        out_specs=rows,
        scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32), pltpu.VMEM((tq, 1), jnp.float32),
                        pltpu.VMEM((tq, D), jnp.float32)],
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        name=PREFILL_NAME,
        interpret=_interpret(),
    )(q, k, v, kc, vc)


def eva_prefill_reference(q, k, v, kc, vc, *, window: int, chunk: int, scale: float, tq: int = 0, tk: int = 0):
    """The prefill kernel's ``jnp`` twin: the whole ``[T, T + T / C]`` mask
    (small sizes only); fp32 throughout."""
    del tq, tk
    H, T, D = q.shape
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    t = jnp.arange(T)[:, None]
    start = (t // window) * window
    exact = (jnp.arange(T)[None] >= start) & (jnp.arange(T)[None] <= t)
    compressed = jnp.arange(kc.shape[1])[None] < start // chunk
    seen = jnp.concatenate([compressed, exact], 1)
    keys, vals = jnp.concatenate([kc, k], 1).astype(f32), jnp.concatenate([vc, v], 1).astype(f32)
    s = jnp.einsum("htd,hjd->htj", q.astype(f32), keys, precision=hi) * scale
    p = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("htj,hjd->htd", p, vals, precision=hi).astype(q.dtype)


# -- compression ----------------------------------------------------------------
def eva_compress(k, v, mu, phi):
    """``k, v [..., C, H, D]`` (rotated keys, values) -> the chunk's
    compressed key and value ``[..., H, D]`` in fp32:
    ``sum_j softmax_j(mu . x_j) k_j`` and ``sum_j softmax_j(phi . x_j -
    |x_j|^2 / 2) v_j`` with ``x_j = D^-1/4 k_j`` and ``mu, phi [H, D]``.
    Products summed on the VPU in fp32 (no matmul: nothing rounds them)."""
    f32 = jnp.float32
    with jax.named_scope(COMPRESS_SCOPE):
        k, v = k.astype(f32), v.astype(f32)
        x = k * k.shape[-1] ** -0.25
        a = jax.nn.softmax(jnp.sum(x * mu.astype(f32), -1), axis=-2)  # [..., C, H]: over the chunk
        b = jax.nn.softmax(jnp.sum(x * phi.astype(f32), -1) - 0.5 * jnp.sum(x * x, -1), axis=-2)
        return jnp.sum(a[..., None] * k, axis=-3), jnp.sum(b[..., None] * v, axis=-3)
