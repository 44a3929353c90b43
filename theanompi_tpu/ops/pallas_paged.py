"""The walk over a slot's live pages that the paged decode kernels share, and
``kv_decode``: one decode step's attention over the plain K/V cache.

BEYOND-PARITY EXTENSION (the reference has no attention; SURVEY.md §5.7).

:func:`page_walk` is the skeleton of a decode kernel whose grid is the
slots, whose page tables and lengths are prefetched scalars and whose pools
stay in HBM (``memory_space=pl.ANY``: one operand each, no block, no copy of
a pool). The kernel brings a slot's pages itself: the slot's table is cut
into the fewest equal steps of at most ``_STEP_BYTES`` of rows
(:func:`pages_a_step`), and a step's live pages (those below the slot's
length) are copied into one half of two-halved VMEM buffers while the step
before is computed from the other half; a slot's last step starts the first
step of the next slot, so the copies do not stop at a slot's end. A page
past the length is not copied: its place holds zeros or an earlier step's
rows and the kernel masks it, not branches on it. A slot of no cached
position (an inactive one) runs no step. What a page brings (latent rows
and transposed rotated keys for ``ops/pallas_mla.py mla_decode``, K and V
rows here) is the kernel's: it names the copies of one page.

:func:`kv_decode` walks the pools of the ``kv`` cache kind

    k_pool, v_pool  [L, n_pages + 1, page, H * hd]

a position's heads side by side in one lane-dense row. A step is ONE softmax
pass over all its positions for all heads at once: the query is held as the
block-diagonal ``[H, H * hd]`` (head ``h``'s row carries ``q_h`` in its own
lanes, zeros elsewhere), so the scores are one ``[H, span]`` product against
the step's K rows, and the values one ``[H, span] x [span, H * hd]`` product
whose diagonal blocks are the heads' outputs. The step's own position is
not in the pools yet: its K and V rows come as operands and join the
softmax last. The caller writes the new rows of all layers after the last
layer.

Numerics: the same products as the gather it replaces
(:func:`kv_decode_reference`). The cached rows are values of the compute
dtype (the query's) kept in the pool's, so the cast back to it is exact;
scores are products of that dtype with fp32 accumulation, the softmax is
fp32, and the probabilities stay fp32 in the second product: for a
bfloat16 query as three bfloat16 parts (24 bits, an fp32 significand) each
against the exact bfloat16 values, for an fp32 query at ``HIGHEST``. Off-TPU
the kernel runs through the Pallas interpreter; :func:`kv_decode_reference`
is its ``jnp`` twin, the CPU tests' oracle.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from theanompi_tpu.ops.pallas_util import interpret_mode as _interpret

DECODE_NAME = "kv_decode"  # the kernel's name in a device trace
_STEP_BYTES = 2 << 20  # of cached rows a step brings at most (VMEM holds two such halves)
_NEG = -1e30


def pages_a_step(M: int, page_bytes: int) -> int:
    """The table's ``M`` pages in the fewest equal steps of at most ``_STEP_BYTES``."""
    steps = -(-M // max(1, min(M, _STEP_BYTES // page_bytes)))
    return -(-M // steps)


def page_walk(page, pps, tables_ref, lens_ref, bufs, copies, par_ref):
    """Begin grid step ``s``'s slot: -> ``(n, run)``, the slot's cached
    positions and ``run(body, carry)``, which folds ``body(t, buf, carry)``
    over the slot's steps ``t`` once step ``t``'s pages are in half ``buf``
    of the buffers and returns the carry.

    ``bufs`` are the two-halved VMEM buffers (zeroed before the first slot:
    a dead page's rows are masked, so must be finite), ``copies(pg, buf, i)``
    the async copies of pool page ``pg`` into place ``i`` of half ``buf``,
    and ``par_ref`` an SMEM int32 that carries, from slot to slot, the half
    holding the slot's first step. Read the operands the steps need between
    this call and ``run``: the first copies are in flight by then."""
    s, S = pl.program_id(0), pl.num_programs(0)
    span = pps * page
    n = lens_ref[s]
    steps = (n + span - 1) // span

    def pages(slot, step, buf, act):
        """``act`` on the copies of every live page of ``slot``'s step into
        half ``buf`` (a copy is waited for as it was started)."""
        for i in range(pps):
            k = step * pps + i
            pg = tables_ref[slot, jnp.minimum(k, tables_ref.shape[1] - 1)]

            @pl.when(k * page < lens_ref[slot])
            def _(i=i, pg=pg):
                for copy in copies(pg, buf, i):
                    act(copy)

    def start(slot, step, buf):
        pages(slot, step, buf, lambda copy: copy.start())

    def wait(slot, step, buf):
        pages(slot, step, buf, lambda copy: copy.wait())

    @pl.when(s == 0)
    def _first():
        for buf in bufs:
            buf[...] = jnp.zeros_like(buf)  # zeros now, an earlier step's rows later
        par_ref[0] = 0

    par = par_ref[0]

    @pl.when(jnp.logical_or(s == 0, lens_ref[jnp.maximum(s - 1, 0)] == 0))
    def _own_first_step():  # else the slot before started it from its last step
        start(s, 0, par)

    nxt = jnp.minimum(s + 1, S - 1)
    has_next = s + 1 < S

    def run(body, carry):
        def step(t, carry):
            buf = (par + t) % 2
            last = t + 1 == steps

            @pl.when(jnp.logical_or(jnp.logical_not(last), has_next))
            def _next_step():  # this slot's, or the first of the next slot
                start(jnp.where(last, nxt, s), jnp.where(last, 0, t + 1), 1 - buf)

            wait(s, t, buf)
            return body(t, buf, carry)

        carry = lax.fori_loop(0, steps, step, carry)
        par_ref[0] = (par + steps) % 2
        return carry

    return n, run


def _dot(a, b, dims):
    """``a . b`` summed in fp32, fp32 operands at ``HIGHEST`` (Mosaic takes
    no precision for bfloat16 ones: their products are exact)."""
    hi = lax.Precision.HIGHEST if a.dtype == jnp.float32 else None
    return lax.dot_general(a, b, dims, precision=hi, preferred_element_type=jnp.float32)


def _p_times_v(p, v):
    """``p [H, span]`` (fp32) times ``v [span, D]`` with ``p`` kept in fp32:
    one product for fp32 ``v``, else ``p`` as three parts of ``v``'s dtype."""
    dims = (((1,), (0,)), ((), ()))
    if v.dtype == jnp.float32:
        return _dot(p, v, dims)
    out = None
    for _ in range(3):
        part = p.astype(v.dtype)
        term = _dot(part, v, dims)
        out = term if out is None else out + term
        p = p - part.astype(jnp.float32)
    return out


def _kernel(scale, page, pps, H, layer_ref, tables_ref, lens_ref, q_ref, kn_ref, vn_ref, kpool_ref, vpool_ref,
            o_ref, kbuf_ref, vbuf_ref, sem_ref, par_ref):
    layer = layer_ref[0]

    def copies(pg, buf, i):
        rows = pl.ds(i * page, page)
        return (pltpu.make_async_copy(kpool_ref.at[layer, pg], kbuf_ref.at[buf, rows], sem_ref.at[buf, 0]),
                pltpu.make_async_copy(vpool_ref.at[layer, pg], vbuf_ref.at[buf, rows], sem_ref.at[buf, 1]))

    n, run = page_walk(page, pps, tables_ref, lens_ref, (kbuf_ref, vbuf_ref), copies, par_ref)
    span = pps * page
    q = q_ref[...]  # [1, D]
    D = q.shape[-1]
    mine = lax.broadcasted_iota(jnp.int32, (H, D), 1) // (D // H) == lax.broadcasted_iota(jnp.int32, (H, D), 0)
    # the block-diagonal query, selected in fp32 (a bfloat16 select fails to lower)
    qh = jnp.where(mine, jnp.broadcast_to(q.astype(jnp.float32), (H, D)), 0.0)
    qc = qh.astype(q.dtype)

    def body(t, buf, carry):
        m, l, acc = carry
        k, v = kbuf_ref[buf].astype(q.dtype), vbuf_ref[buf].astype(q.dtype)  # [span, D], exact
        sc = _dot(qc, k, (((1,), (1,)), ((), ()))) * scale  # [H, span]
        sc = jnp.where(t * span + lax.broadcasted_iota(jnp.int32, sc.shape, 1) < n, sc, _NEG)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * corr + _p_times_v(p, v)
        return m_new, l, acc

    m, l, acc = run(body, (jnp.full((H, 1), _NEG, jnp.float32), jnp.zeros((H, 1), jnp.float32),
                           jnp.zeros((H, D), jnp.float32)))
    kn = kn_ref[...].astype(jnp.float32)  # [1, D]
    vn = vn_ref[...].astype(jnp.float32)
    sc = jnp.sum(qh * kn, axis=-1, keepdims=True) * scale  # [H, 1]
    m_new = jnp.maximum(m, sc)
    corr, p = jnp.exp(m - m_new), jnp.exp(sc - m_new)
    o = (acc * corr + p * vn) / (l * corr + p)  # head h's output lies in head h's lanes
    o_ref[...] = jnp.sum(jnp.where(mine, o, 0.0), axis=0, keepdims=True).astype(o_ref.dtype)


def kv_decode(
    q: jax.Array,  # [S, H * hd] the slots' queries, in the compute dtype
    k_new: jax.Array,  # [S, H * hd] the step's own key row, as the pool will hold it
    v_new: jax.Array,  # [S, H * hd] and value row
    k_pool: jax.Array,  # [L, n_pages + 1, page, H * hd]
    v_pool: jax.Array,  # [L, n_pages + 1, page, H * hd]
    tables: jax.Array,  # [S, M] int32 page table of every slot
    lens: jax.Array,  # [S] int32 cached positions of every slot
    *,
    layer: int,
    n_heads: int,
    scale: float,
) -> jax.Array:
    """``o [S, H * hd]`` in ``q``'s dtype: every slot's heads attending over
    the slot's ``lens[s]`` cached positions of ``layer`` and over its own new
    row. Reads those pages only. ``layer`` is a prefetched scalar, so one
    kernel serves every layer. The pages a step (``_STEP_BYTES``) and the
    interpreter are chosen here, at every call, and the kernel is traced
    once for each choice: a caller outside ``jit`` does not trace it anew
    at every call."""
    M, page, D = tables.shape[1], k_pool.shape[2], q.shape[1]
    pps = pages_a_step(M, page * D * (k_pool.dtype.itemsize + v_pool.dtype.itemsize))
    return _kv_decode(jnp.asarray(layer, jnp.int32).reshape(1), tables.astype(jnp.int32),
                      lens.astype(jnp.int32), q, k_new, v_new, k_pool, v_pool,
                      n_heads=int(n_heads), scale=float(scale), pps=pps, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("n_heads", "scale", "pps", "interpret"))
def _kv_decode(layer, tables, lens, q, k_new, v_new, k_pool, v_pool, *, n_heads, scale, pps, interpret):
    S, D = q.shape
    page = k_pool.shape[2]
    row = pl.BlockSpec((None, 1, D), lambda s, layer, tables, lens: (s, 0, 0))
    o = pl.pallas_call(
        functools.partial(_kernel, scale, page, pps, n_heads),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(S,),
            in_specs=[row, row, row, pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=row,
            scratch_shapes=[pltpu.VMEM((2, pps * page, D), k_pool.dtype),
                            pltpu.VMEM((2, pps * page, D), v_pool.dtype),
                            pltpu.SemaphoreType.DMA((2, 2)), pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((S, 1, D), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        name=DECODE_NAME,
        interpret=interpret,
    )(layer, tables, lens, q[:, None], k_new[:, None], v_new[:, None], k_pool, v_pool)
    return o[:, 0]


def kv_decode_reference(q, k_new, v_new, k_pool, v_pool, tables, lens, *, layer: int, n_heads: int,
                        scale: float):
    """The kernel's ``jnp`` twin: every slot's pages gathered through its
    table to the whole table's length, masked past ``lens``; both products
    over whole rows, head ``h``'s query zero outside its own lanes and its
    output taken from them."""
    S, D = q.shape
    M, page = tables.shape[1], k_pool.shape[2]
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    k_ctx = k_pool[layer][tables].reshape(S, M * page, D)
    v_ctx = v_pool[layer][tables].reshape(S, M * page, D)
    mine = jnp.arange(D)[None, :] // (D // n_heads) == jnp.arange(n_heads)[:, None]  # [H, D]
    qh = jnp.where(mine, q[:, None, :], 0)  # [S, H, D]
    # every cached row was a compute-dtype value when it was written, so the
    # cast back is exact: products of such values, summed in fp32
    s = jnp.einsum("shD,stD->sht", qh, k_ctx.astype(q.dtype), precision=hi, preferred_element_type=f32) * scale
    cached = (jnp.arange(M * page)[None, :] < lens[:, None])[:, None, :]
    own = jnp.einsum("shD,sD->sh", qh.astype(f32), k_new.astype(f32), precision=hi) * scale
    p = jax.nn.softmax(jnp.concatenate([jnp.where(cached, s, _NEG), own[..., None]], axis=-1), axis=-1)
    o = (jnp.einsum("sht,stD->shD", p[..., :-1], v_ctx.astype(f32), precision=hi)
         + p[..., -1:] * v_new[:, None, :].astype(f32))
    return jnp.where(mine, o, 0).sum(axis=1).astype(q.dtype)
