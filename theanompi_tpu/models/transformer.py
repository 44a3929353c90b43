"""N-D parallel transformer LM — the long-context / multi-axis demonstrator.

BEYOND-PARITY EXTENSION (the reference is a 2016 CNN framework;
SURVEY.md §5.7). This module proves the framework's named-mesh design
carries every classic parallelism axis, composably, in ONE SPMD program:

- **SP** (sequence/context): tokens sharded over a ``seq`` axis; attention
  is :func:`~theanompi_tpu.ops.ring_attention.ring_attention` (K/V ring)
  or :func:`~theanompi_tpu.ops.ring_attention.ulysses_attention`
  (head<->sequence all-to-all) — activations never materialize the full
  sequence on one chip.
- **TP** (tensor/model, Megatron-style): attention heads and FFN hidden
  units column/row-sharded over a ``model`` axis, with ONE psum after the
  attention projection and one after the FFN per block; the vocabulary
  head is vocab-sharded with a distributed softmax cross-entropy (max and
  normalizer psum'd over the axis) so full logits never exist anywhere.
- **DP**: batch sharded over ``data``; gradients psum'd — exactly
  parallel/bsp.py's rule, composed with the above.

``make_nd_train_step`` builds the train step for any subset of
``(dp, tp, sp)`` axes on one mesh; ``make_sp_train_step`` is the
seq-only convenience used by the long-context tests. Pipeline (``pipe``)
and expert (``expert``) axes live in :mod:`theanompi_tpu.parallel.pipeline`
and :mod:`theanompi_tpu.ops.moe`, reusing this model's blocks.

Deliberately small and self-contained (the image zoo's ``Model``
contract is classifier-shaped); the point is the PARALLELISM patterns.
"""

from __future__ import annotations

import functools
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from theanompi_tpu.ops.pallas_attention import flash_attention, ring_flash_attention
from theanompi_tpu.ops.ring_attention import (
    full_attention_reference,
    ring_attention,
    ulysses_attention,
)

PyTree = Any

SEQ_AXIS = "seq"
MODEL_AXIS = "model"


def _rms(x, g, eps=1e-6):
    # statistics in fp32 even when x is bf16 (the normalizer is a
    # variance sweep — bf16's 8-bit mantissa visibly degrades it);
    # output returns to x's compute dtype for the next matmul
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps) * g
    return y.astype(x.dtype)


def cast_block_params(blk: dict, dtype) -> dict:
    """Mixed-precision cast for one block's param dict: matmul weights
    to the compute ``dtype`` (XLA fuses the cast into the MXU op; AD
    accumulates their grads back in fp32), norm gains left fp32 — they
    are consumed inside :func:`_rms`'s fp32 statistics path. No-op for
    fp32 compute. Works for dense and MoE blocks (any non-``ln*`` leaf
    is a matmul operand). The skip-by-name list below is THIS block's;
    the kinds-built block (models/afmoe.py) says its precision where a
    tensor is used and does not come through here."""
    if dtype == jnp.float32:
        return blk
    # 'gate' (MoE router) also stays fp32: routing is an argmax over its
    # logits and the d x E matmul is negligible next to the experts
    skip = ("ln1", "ln2", "gate")
    return {k: (v if k in skip else v.astype(dtype)) for k, v in blk.items()}


def attention_block(blk, x, attn: str, sp_axis: Optional[str]):
    """Pre-norm attention sub-block shared by the dense and MoE LMs:
    qkv projection (TP-native ``[d, 3, H, hd]`` layout), causal
    (ring | ulysses | flash | local full) attention, output projection.
    Returns the residual delta BEFORE any tp-axis psum (the caller owns
    that)."""
    hin = _rms(x, blk["ln1"])
    qkv = jnp.einsum("btd,dchk->btchk", hin, blk["qkv"])
    q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [B, T, H_local, hd]
    if sp_axis is not None:
        if attn == "flash":
            raise ValueError(
                "attn='flash' is the fused LOCAL kernel; under sequence "
                "parallelism pick attn='ring_flash' (K/V rotation, each "
                "hop folded by the fused kernel) or attn='ulysses_flash' "
                "(all-to-all with the fused local step) — 'ring'/'ulysses' "
                "are their unfused variants"
            )
        sp_attn = {
            "ring": ring_attention,
            "ring_flash": ring_flash_attention,
            "ulysses": ulysses_attention,
            "ulysses_flash": functools.partial(
                ulysses_attention, local_fn=flash_attention
            ),
        }[attn]
        att = sp_attn(q, k, v, sp_axis, causal=True)
    elif attn in ("flash", "ulysses_flash", "ring_flash"):
        # no SP axis: both SP schemes degenerate to their local step —
        # the fused kernel
        att = flash_attention(q, k, v, causal=True)
    else:
        att = full_attention_reference(q, k, v, causal=True)
    return jnp.einsum("bthk,hkd->btd", att, blk["proj"])


def global_positions(sp_axis: Optional[str], T: int) -> jax.Array:
    """Global position ids for a (possibly sequence-sharded) window of
    ``T`` local positions — THE shard-offset rule, shared by the dense,
    MoE, and pipeline forwards (changing position handling changes all
    three at once)."""
    if sp_axis is not None:
        return lax.axis_index(sp_axis) * T + jnp.arange(T)
    return jnp.arange(T)


def next_token_loss(tokens, sp_axis: Optional[str], nll_fn):
    """Next-token objective plumbing shared by the dense and MoE LMs:
    builds the target sequence (the target of a shard's last position is
    the NEXT shard's first token, fetched with one backward ppermute),
    masks the final global position (no target), and reduces to the mean
    over this device's batch rows x the GLOBAL sequence. ``nll_fn(targets)
    -> [B, T]`` supplies the per-position negative log-likelihood."""
    B, T = tokens.shape
    if sp_axis is not None:
        n = lax.psum(1, sp_axis)
        rank = lax.axis_index(sp_axis)
        nxt = lax.ppermute(
            tokens[:, 0], sp_axis, [((i + 1) % n, i) for i in range(n)]
        )
        targets = jnp.concatenate([tokens[:, 1:], nxt[:, None]], axis=1)
        last_shard = rank == n - 1
    else:
        targets = jnp.concatenate(
            [tokens[:, 1:], tokens[:, :1]], axis=1
        )  # wrapped value is masked out below
        last_shard = True
    valid = jnp.where(
        last_shard & (jnp.arange(T) == T - 1)[None, :], 0.0, 1.0
    ) * jnp.ones((B, T))
    nll = nll_fn(targets)
    total = jnp.sum(nll * valid)
    count = jnp.sum(valid)
    if sp_axis is not None:
        total = lax.psum(total, sp_axis)
        count = lax.psum(count, sp_axis)
    return total / count


def chunked_nll(x, head, chunk: int, dtype):
    """Per-position NLL computed per sequence CHUNK: each chunk's
    ``[B, C, V]`` logits are built (head matmul), reduced to the
    logsumexp-form NLL, and — via ``jax.checkpoint`` on the chunk body —
    DISCARDED; the backward recomputes them chunk by chunk. Peak memory
    for the loss drops from O(T x V) to O(chunk x V) in both passes
    (at T=16k x 32k-vocab that is the difference between 2 x 2.1 GB
    fp32 and 2 x 132 MB at chunk=1024). The math is exactly
    :func:`softmax_nll` on the full logits — pinned by an equality
    test."""

    def nll_fn(targets):
        B, T = targets.shape
        if T % chunk:
            raise ValueError(
                f"loss_chunk={chunk} must divide the local sequence "
                f"length {T}"
            )
        nC = T // chunk
        xc = x.reshape(B, nC, chunk, x.shape[-1]).swapaxes(0, 1)
        tc = targets.reshape(B, nC, chunk).swapaxes(0, 1)
        hd = head.astype(dtype)

        @jax.checkpoint
        def body(carry, inp):
            xb, tb = inp  # [B, C, d], [B, C]
            lf = (xb @ hd).astype(jnp.float32)
            lse = jax.scipy.special.logsumexp(lf, axis=-1)
            tl = jnp.take_along_axis(lf, tb[..., None], axis=-1)[..., 0]
            return carry, lse - tl

        _, nll = lax.scan(body, 0.0, (xc, tc))
        return nll.swapaxes(0, 1).reshape(B, T)

    return nll_fn


def softmax_nll(logits):
    """Standard per-position NLL from full (unsharded) logits, computed
    as ``logsumexp(logits) - logits[target]`` in fp32 regardless of the
    compute dtype (softmax statistics are the one place bf16 rounding
    visibly moves the loss). The logsumexp form skips materializing the
    full [B, T, V] log-probability tensor the naive
    ``log_softmax``-then-gather does — measured +6% tokens/s on the
    136M/32k-vocab config on v5e; the gradient (softmax - onehot) is
    identical."""

    def nll_fn(targets):
        lf = logits.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(lf, axis=-1)
        tl = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
        return lse - tl

    return nll_fn


class TransformerLM(NamedTuple):
    """Architecture config (params live in a plain dict pytree).

    ``attn`` picks the attention scheme: ``"ring"`` (K/V rotation,
    O(T/n) memory under SP; plain full attention without an SP axis),
    ``"ring_flash"`` (same ring, each hop folded by the fused Pallas
    flash kernel — no per-hop score materialization either),
    ``"ulysses"`` (head<->sequence all-to-all; needs ``n_heads``
    divisible by the seq-axis size), ``"ulysses_flash"`` (same, with
    the local step fused via the Pallas flash kernel), or ``"flash"``
    (single-device / DP-TP-only: the fused Pallas kernel,
    ops/pallas_attention.py).
    ``remat=True`` checkpoints each block (jax.checkpoint): backward
    recomputes block activations instead of storing them — combine with
    the seq axis for long-context training beyond HBM.

    Param layout is TP-native: ``qkv`` is ``[d, 3, H, hd]`` and ``proj``
    ``[H, hd, d]`` so sharding their head dim over the ``model`` axis is
    a plain PartitionSpec (no resharding); the FFN shards ``d_ff``; the
    head shards the vocab."""

    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    max_len: int = 1024
    attn: str = "ring"
    remat: bool = False
    # compute dtype: params are STORED fp32; activations and matmul
    # weights are cast to this at use (cast_block_params), softmax /
    # norm statistics stay fp32. bfloat16 doubles MXU throughput on TPU.
    dtype: Any = jnp.float32
    # chunked loss: apply head + CE per sequence chunk of this many
    # positions (rematerialized — backward recomputes each chunk's
    # logits), so the full [B, T, V] logits NEVER materialize. At
    # T=16384 x 32k vocab the logits + their softmax cotangent are
    # 2 x 2.1 GB fp32 — the dominant long-context memory after remat.
    # None = whole-sequence logits (short-T default); ignored under
    # tp_axis (the vocab-sharded CE already avoids full logits).
    loss_chunk: Optional[int] = None

    def init(self, key: jax.Array) -> PyTree:
        ks = jax.random.split(key, 3 + 4 * self.n_layers)
        d, h = self.d_model, self.d_ff
        nh, hd = self.n_heads, self.d_model // self.n_heads
        s = 0.02
        params = {
            "tok_emb": s * jax.random.normal(ks[0], (self.vocab, d)),
            "pos_emb": s * jax.random.normal(ks[1], (self.max_len, d)),
            "head": s * jax.random.normal(ks[2], (d, self.vocab)),
            "blocks": [],
        }
        for i in range(self.n_layers):
            k0, k1, k2, k3 = ks[3 + 4 * i : 7 + 4 * i]
            params["blocks"].append(
                {
                    "qkv": s * jax.random.normal(k0, (d, 3, nh, hd)),
                    "proj": s * jax.random.normal(k1, (nh, hd, d)),
                    "mlp_in": s * jax.random.normal(k2, (d, h)),
                    "mlp_out": s * jax.random.normal(k3, (h, d)),
                    "ln1": jnp.ones((d,)),
                    "ln2": jnp.ones((d,)),
                }
            )
        return params

    # -- parallel forward/loss ------------------------------------------

    def forward(
        self,
        params: PyTree,
        tokens: jax.Array,  # [B_local, T_local]
        *,
        sp_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
    ) -> jax.Array:
        """``tokens -> logits [B_local, T_local, V_local]``.

        Runs inside ``shard_map``. With ``sp_axis``, the sequence dim is
        sharded over it (global positions come from the axis index); with
        ``tp_axis``, ``params`` leaves arrive pre-sharded per
        :meth:`tp_param_specs` and the returned logits are sharded over
        the vocab (use :meth:`loss` for the distributed cross-entropy).
        """
        return self.forward_hidden(
            params, tokens, sp_axis=sp_axis, tp_axis=tp_axis
        ) @ params["head"].astype(self.dtype)

    def forward_hidden(
        self,
        params: PyTree,
        tokens: jax.Array,
        *,
        sp_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
    ) -> jax.Array:
        """:meth:`forward` without the vocabulary head: ``tokens ->
        hidden [B, T, d]`` — the hook for the chunked loss
        (:func:`chunked_nll`), which applies head + cross-entropy per
        sequence chunk so the full ``[B, T, V]`` logits never
        materialize."""
        B, T = tokens.shape
        pos = global_positions(sp_axis, T)
        # cast AFTER the gathers (cheaper than casting the [V, d] table)
        x = (params["tok_emb"][tokens] + params["pos_emb"][pos][None]).astype(
            self.dtype
        )

        def block(x, blk):
            blk = cast_block_params(blk, self.dtype)
            delta = attention_block(blk, x, self.attn, sp_axis)
            if tp_axis is not None:
                delta = lax.psum(delta, tp_axis)  # row-parallel proj
            x = x + delta
            hin = _rms(x, blk["ln2"])
            delta = jax.nn.gelu(hin @ blk["mlp_in"]) @ blk["mlp_out"]
            if tp_axis is not None:
                delta = lax.psum(delta, tp_axis)  # row-parallel mlp_out
            return x + delta

        if self.remat:
            # rematerialize per block: backward recomputes the block's
            # activations (incl. its collectives) instead of keeping
            # them — O(sqrt-ish) activation memory for long sequences,
            # the standard jax.checkpoint trade of FLOPs for HBM
            block = jax.checkpoint(block)
        for blk in params["blocks"]:
            x = block(x, blk)
        return x

    def loss(
        self,
        params: PyTree,
        tokens: jax.Array,
        axis_name: Optional[str] = SEQ_AXIS,
        *,
        tp_axis: Optional[str] = None,
    ) -> jax.Array:
        """Next-token cross-entropy over the GLOBAL sequence.

        With ``axis_name`` (the seq axis): the target of a shard's last
        position is the NEXT shard's first token — fetched with one
        backward ppermute; the final global position has no target and
        is masked. With ``tp_axis``: logits arrive vocab-sharded and the
        log-softmax runs distributed (pmax/psum over the axis) — full
        logits never materialize. Returns the mean loss over this
        device's batch rows x the global sequence (identical on every
        sp/tp peer)."""
        sp_axis = axis_name
        if self.loss_chunk and tp_axis is None:
            x = self.forward_hidden(params, tokens, sp_axis=sp_axis)
            nll_fn = chunked_nll(
                x, params["head"], self.loss_chunk, self.dtype
            )
            return next_token_loss(tokens, sp_axis, nll_fn)
        logits = self.forward(params, tokens, sp_axis=sp_axis, tp_axis=tp_axis)
        return next_token_loss(tokens, sp_axis, pick_nll(logits, tp_axis))

    # -- TP sharding spec ------------------------------------------------

    def tp_param_specs(self, tp_axis: str = MODEL_AXIS) -> PyTree:
        """PartitionSpec pytree for Megatron-style tensor parallelism:
        attention heads column-sharded in ``qkv`` / row-sharded in
        ``proj``, FFN hidden col/row-sharded, vocab head col-sharded;
        embeddings and layernorms replicated."""
        blk = {
            "qkv": P(None, None, tp_axis, None),   # heads
            "proj": P(tp_axis, None, None),        # heads (row side)
            "mlp_in": P(None, tp_axis),            # d_ff columns
            "mlp_out": P(tp_axis, None),           # d_ff rows
            "ln1": P(),
            "ln2": P(),
        }
        return {
            "tok_emb": P(),
            "pos_emb": P(),
            "head": P(None, tp_axis),              # vocab columns
            "blocks": [blk] * self.n_layers,
        }

    # -- paged-KV incremental decode (serve/decode subsystem) ------------

    def prefill_cache(self, params, tokens, pages, k_pool, v_pool, *,
                      page_size: int):
        """See :func:`paged_prefill` — dense-FFN binding."""
        return paged_prefill(
            self, params, tokens, pages, k_pool, v_pool, page_size
        )

    def decode_step(self, params, k_pool, v_pool, page_tables, seq_lens,
                    last_tokens, active, temperature, key, *,
                    page_size: int):
        """See :func:`paged_decode_step` — dense-FFN binding."""
        return paged_decode_step(
            self, params, k_pool, v_pool, page_tables, seq_lens,
            last_tokens, active, temperature, key, page_size
        )


# -- paged-KV incremental decode ----------------------------------------
#
# The serving-side counterpart of the training forward above
# (serve/decode: continuous batching over a paged KV-cache). Two
# programs, compiled ONCE each for fixed shapes:
#   * paged_prefill — one padded prompt per call, one static bucket
#     length per compiled program; writes per-layer K/V pages.
#   * paged_decode_step — ONE token per active batch slot, every slot
#     every iteration; reads the cache through per-slot page tables,
#     writes the current position's K/V, samples the next token.
# Both take an ``ffn(blk, hin) -> delta`` hook so the MoE LM
# (models/moe.py) reuses the attention/cache plumbing unchanged.
#
# The pools are ``[L, n_pages + 1, page_size, H * hd]``: a position's
# heads side by side in ONE row (768 lanes at 12 heads of 64). A TPU
# array lives in (8, 128) tiles over its two minor dimensions; minor
# ``[H, hd] = [12, 64]`` fills no tile, so the compiler kept a second,
# padded copy of a pool, re-laid it on the way in and out of every
# program and copied a layer of it around every write (52 pool-sized
# copies a decode step, PERF.md section 6, PR 34). ``[page_size, H * hd]``
# is whole tiles, and each program touches a pool twice and no more:
# reads by page, ONE write after the last layer.


def dense_ffn(blk, hin):
    """The dense block's FFN residual delta (shared with the training
    forward's MLP; ``blk`` arrives already cast)."""
    return jax.nn.gelu(hin @ blk["mlp_in"]) @ blk["mlp_out"]


def paged_prefill(arch, params, tokens, pages, k_pool, v_pool,
                  page_size: int, ffn=dense_ffn):
    """Cache one prompt's per-layer K/V into the paged pools.

    ``tokens`` is ONE padded prompt ``[T_b] int32`` (``T_b`` a static
    bucket length, a multiple of ``page_size``), ``pages
    [T_b/page_size] int32`` routes each page-worth of positions to its
    physical page (the scratch index for the padding tail), and the
    pools are ``[L, n_pages + 1, page_size, H * hd]`` (lane-dense rows:
    the ``(8, 128)`` tile, see above). Runs the full causal forward
    minus the vocabulary head, so every position below the true prompt
    length produces K/V bit-identical to the training forward —
    causality means the padding tail cannot contaminate them, and its
    garbage K/V land on read-masked offsets or the scratch page. All
    layers' pages go to each pool in ONE write. Returns ``(k_pool,
    v_pool)`` updated.
    """
    T = tokens.shape[0]
    x = (params["tok_emb"][tokens] + params["pos_emb"][:T]).astype(arch.dtype)
    x = x[None]  # [1, T, d]
    ks, vs = [], []
    for blk in params["blocks"]:
        blk = cast_block_params(blk, arch.dtype)
        hin = _rms(x, blk["ln1"])
        qkv = jnp.einsum("btd,dchk->btchk", hin, blk["qkv"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]  # [1, T, H, hd]
        ks.append(k.reshape(T // page_size, page_size, -1))
        vs.append(v.reshape(T // page_size, page_size, -1))
        att = full_attention_reference(q, k, v, causal=True)
        x = x + jnp.einsum("bthk,hkd->btd", att, blk["proj"])
        x = x + ffn(blk, _rms(x, blk["ln2"]))
    return (k_pool.at[:, pages].set(jnp.stack(ks).astype(k_pool.dtype)),
            v_pool.at[:, pages].set(jnp.stack(vs).astype(v_pool.dtype)))


def paged_decode_step(arch, params, k_pool, v_pool, page_tables, seq_lens,
                      last_tokens, active, temperature, key,
                      page_size: int, ffn=dense_ffn):
    """One continuous-batching decode iteration over ALL batch slots.

    Per slot ``s``: embed ``last_tokens[s]`` at position ``seq_lens[s]``
    and attend over positions ``0..seq_lens[s]`` inclusive: the cached
    rows below ``seq_lens[s]``, gathered through the slot's page table
    from the pool AS IT STOOD (viewed ``[L * (n_pages + 1), page_size,
    H * hd]`` and indexed ``page_tables + li * (n_pages + 1)``: no layer
    is cut out of it), and the step's own K/V row, an operand whose score
    joins the cached ones under one fp32 softmax (same ``1/sqrt(hd)``
    scale as :func:`full_attention_reference`). The new rows of ALL
    layers go to (page ``page_tables[s, pos//page_size]``, offset
    ``pos % page_size``) in ONE scatter a pool after the last layer —
    inactive slots' to the scratch page. Sample: greedy argmax where
    ``temperature[s] == 0``, else categorical on ``logits/temperature``
    under ``key``. All shapes are static in ``(S, M)`` so ONE compiled
    program serves every iteration.

    The pools are ``[L, n_pages + 1, page_size, H * hd]`` (lane-dense
    rows: the ``(8, 128)`` tile, see above) and the gathered rows are
    never reshaped to ``[H, hd]``, which would re-lay them into padded
    tiles layer by layer (7.6 ms of a 22.7 ms step on a v5e, PERF.md
    section 6, PR 34): both attention products run over whole rows, head
    ``h``'s query zero outside its own ``hd`` lanes and its output taken
    from them.

    Returns ``(next_tokens [S] int32, logits [S, V] fp32, k_pool,
    v_pool)``.
    """
    S, M = page_tables.shape
    L, n_phys = k_pool.shape[:2]  # n_pages + 1: the last is the scratch page
    H, D = arch.n_heads, k_pool.shape[-1]
    hd = D // H
    f32, hi = jnp.float32, lax.Precision.HIGHEST
    sc = 1.0 / math.sqrt(hd)
    pos = jnp.clip(seq_lens, 0, params["pos_emb"].shape[0] - 1)
    x = (params["tok_emb"][last_tokens] + params["pos_emb"][pos]).astype(
        arch.dtype
    )
    k_pages = k_pool.reshape(L * n_phys, page_size, D)
    v_pages = v_pool.reshape(L * n_phys, page_size, D)
    cached = (jnp.arange(M * page_size)[None, :] < seq_lens[:, None])[:, None, :]
    head_lanes = jnp.arange(D)[None, :] // hd == jnp.arange(H)[:, None]  # [H, D]
    ks, vs = [], []
    for li, blk in enumerate(params["blocks"]):
        blk = cast_block_params(blk, arch.dtype)
        hin = _rms(x, blk["ln1"])
        qkv = jnp.einsum("sd,dchk->schk", hin, blk["qkv"])
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [S, H, hd]
        # the row as the pool will hold it, so that this step and the next
        # read the same K and V of this position
        k, v = k.astype(k_pool.dtype), v.astype(v_pool.dtype)
        ks.append(k.reshape(S, D))
        vs.append(v.reshape(S, D))
        k_ctx = k_pages[page_tables + li * n_phys].reshape(S, M * page_size, D)
        v_ctx = v_pages[page_tables + li * n_phys].reshape(S, M * page_size, D)
        # every cached row was a compute-dtype value when it was written, so
        # the cast back is exact: products of such values, summed in fp32
        q_heads = jnp.where(head_lanes, q.reshape(S, 1, D), 0)  # [S, H, D]
        s_ = jnp.einsum("shD,stD->sht", q_heads, k_ctx.astype(q.dtype),
                        precision=hi, preferred_element_type=f32) * sc
        s_own = jnp.einsum("shd,shd->sh", q.astype(f32), k.astype(f32)) * sc
        p = jax.nn.softmax(
            jnp.concatenate(
                [jnp.where(cached, s_, -1e30), s_own[..., None]], axis=-1
            ),
            axis=-1,
        )
        o = jnp.einsum("sht,stD->shD", p[..., :-1], v_ctx.astype(f32),
                       precision=hi)  # head h's output lies in head h's lanes
        att = jnp.where(head_lanes, o, 0).sum(axis=1).reshape(S, H, hd)
        att = (att + p[..., -1:] * v.astype(f32)).astype(x.dtype)
        x = x + jnp.einsum("shk,hkd->sd", att, blk["proj"])
        x = x + ffn(blk, _rms(x, blk["ln2"]))
    write_page = jnp.where(
        active,
        page_tables[jnp.arange(S), jnp.clip(seq_lens // page_size, 0, M - 1)],
        n_phys - 1,
    )
    rows = write_page + jnp.arange(L)[:, None] * n_phys  # [L, S] pages of the view
    write_off = seq_lens % page_size
    k_pool = k_pages.at[rows, write_off].set(jnp.stack(ks)).reshape(k_pool.shape)
    v_pool = v_pages.at[rows, write_off].set(jnp.stack(vs)).reshape(v_pool.shape)
    logits = (x @ params["head"].astype(arch.dtype)).astype(jnp.float32)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    safe_t = jnp.maximum(temperature, 1e-6)[:, None]
    sampled = jax.random.categorical(key, logits / safe_t).astype(jnp.int32)
    next_tokens = jnp.where(temperature > 0, sampled, greedy)
    return next_tokens, logits, k_pool, v_pool


def _vocab_sharded_nll(logits: jax.Array, targets: jax.Array, tp_axis: str):
    """-log softmax(target) with the vocab dim sharded over ``tp_axis``:
    the classic Megatron parallel cross-entropy (global max via pmax,
    normalizer via psum, target logit gathered on its owner shard).
    Statistics run in fp32 (logits may arrive bf16)."""
    logits = logits.astype(jnp.float32)
    V_local = logits.shape[-1]
    start = lax.axis_index(tp_axis) * V_local
    # stabilizer only — mathematically cancels in log z + m, so AD may
    # skip it (pmax also has no differentiation rule)
    m = lax.pmax(lax.stop_gradient(jnp.max(logits, axis=-1)), tp_axis)  # [B, T]
    z = lax.psum(jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), tp_axis)
    local_ids = targets - start
    in_range = (local_ids >= 0) & (local_ids < V_local)
    idx = jnp.clip(local_ids, 0, V_local - 1)
    tl = jnp.take_along_axis(logits, idx[..., None], axis=-1)[..., 0]
    tl = lax.psum(jnp.where(in_range, tl, 0.0), tp_axis)
    return jnp.log(z) + m - tl


def validate_tp_divisibility(model, tp_axis: str, ntp: int) -> None:
    """The Megatron sharding's divisibility contract, shared by every
    tp-capable setup (dense nd, MoE ep, pipeline): heads column/row
    split, FFN (or per-expert) hidden split, vocab head split."""
    if model.n_heads % ntp or model.d_ff % ntp or model.vocab % ntp:
        raise ValueError(
            f"the {tp_axis!r} axis size {ntp} must divide each of "
            f"n_heads/d_ff/vocab ({model.n_heads}/{model.d_ff}/"
            f"{model.vocab})"
        )


def pick_nll(logits, tp_axis: Optional[str]):
    """The per-position NLL function for (possibly vocab-sharded)
    logits — the dispatch shared by every tp-capable loss (dense LM,
    MoE, pipeline head): Megatron distributed CE when ``tp_axis`` is
    set, the logsumexp form otherwise."""
    if tp_axis is not None:
        return lambda t: _vocab_sharded_nll(logits, t, tp_axis)
    return softmax_nll(logits)


def validate_ulysses_heads(model, sp_axis, sizes, heads_local):
    """Friendly build-time error for the Ulysses all-to-all's head
    divisibility requirement (otherwise it surfaces as an opaque
    lax.all_to_all trace error deep inside the attention)."""
    if sp_axis and getattr(model, "attn", None) in ("ulysses", "ulysses_flash") and (
        heads_local % sizes[sp_axis]
    ):
        raise ValueError(
            f"ulysses attention needs local heads ({heads_local}) divisible "
            f"by the {sp_axis!r} axis size {sizes[sp_axis]}"
        )


def opt_state_specs(opt_template, param_specs):
    """PartitionSpec tree for an optimizer state: any sub-tree whose
    structure matches the params tree (accumulators built with
    zeros_like) inherits ``param_specs``; everything else (step
    counters, empty states like plain sgd's ``()``) replicates.
    ``opt_template`` may be abstract (from ``jax.eval_shape``)."""
    params_treedef = jax.tree_util.tree_structure(param_specs)

    def match(sub):
        if jax.tree_util.tree_structure(sub) == params_treedef:
            return param_specs
        if isinstance(sub, dict):
            return {k: match(v) for k, v in sub.items()}
        return jax.tree_util.tree_map(lambda _: P(), sub)

    return match(opt_template)


def build_spec_step(body, mesh, param_specs, tok_spec, lr, optimizer, init_fn,
                    donate: bool = False):
    """Shared plumbing for the spec-sharded train steps (nd/ep/pp):
    ``body(params, tokens) -> (loss, synced_grads)`` becomes a jitted
    shard_map step — ``(params, tokens) -> (params, loss)`` for plain
    SGD, or over ``(params, opt_state)`` when ``optimizer`` (registry
    name or Optimizer) is given. ``init_fn()`` supplies a params
    template for sizing the opt state (evaluated abstractly — nothing
    is materialized).

    ``donate`` (ISSUE 2 donation audit): when True the state argument's
    buffers are donated so a training loop threading state through the
    step holds ONE params(+opt) copy instead of two. Default False —
    these builders also serve the oracle tests and probes, which reuse
    the input state across calls (a donated input is deleted). The
    driver-facing engines (parallel/nd.py NDEngine) donate by default."""
    if optimizer is None:

        def sharded(params, tokens):
            loss, grads = body(params, tokens)
            new_params = jax.tree_util.tree_map(
                lambda p, g: p - lr * g, params, grads
            )
            return new_params, loss

        return jax.jit(
            jax.shard_map(
                sharded,
                mesh=mesh,
                in_specs=(param_specs, tok_spec),
                out_specs=(param_specs, P()),
                check_vma=False,
            ),
            donate_argnums=(0,) if donate else (),
        )

    from theanompi_tpu.ops.optimizers import apply_updates, get_optimizer

    opt = get_optimizer(optimizer) if isinstance(optimizer, str) else optimizer
    opt_template = jax.eval_shape(lambda: opt.init(init_fn()))
    opt_specs = opt_state_specs(opt_template, param_specs)

    def sharded_opt(state, tokens):
        params, opt_state = state
        loss, grads = body(params, tokens)
        updates, new_opt = opt.update(grads, opt_state, params, lr)
        return (apply_updates(params, updates), new_opt), loss

    return jax.jit(
        jax.shard_map(
            sharded_opt,
            mesh=mesh,
            in_specs=((param_specs, opt_specs), tok_spec),
            out_specs=((param_specs, opt_specs), P()),
            check_vma=False,
        ),
        donate_argnums=(0,) if donate else (),
    )


def sync_grads_by_spec(grads, param_specs, axes, n_total):
    """The universal gradient-sync rule for collective-containing losses
    under ``check_vma=False`` (see make_nd_train_step's docstring): psum
    each leaf over every participating axis its spec does NOT shard it
    on, then divide by the product of all participating axis sizes."""

    def per_leaf(g, spec):
        sharded_on = set()
        for entry in spec:
            if isinstance(entry, (tuple, list)):
                sharded_on.update(entry)
            elif entry is not None:
                sharded_on.add(entry)
        for a in axes:
            if a not in sharded_on:
                g = lax.psum(g, a)
        return g / n_total

    return jax.tree_util.tree_map(per_leaf, grads, param_specs)


def make_sp_train_step(model: TransformerLM, mesh: Mesh, lr: float = 1e-2):
    """Jitted sequence-parallel SGD step ``(params, tokens) -> (params,
    loss)``: params replicated, tokens ``[B, T]`` sharded over the seq
    axis, gradients psum'd over it and divided by the axis size (see
    make_nd_train_step — the per-device backward already carries the
    device-sum objective, so psum/n is the true global-loss gradient;
    earlier revisions applied the raw psum, i.e. an n x larger step at
    the same lr)."""
    return make_nd_train_step(model, mesh, lr=lr, sp_axis=SEQ_AXIS)


def nd_spec_setup(
    model: TransformerLM,
    mesh: Mesh,
    dp_axis: Optional[str],
    tp_axis: Optional[str],
    sp_axis: Optional[str],
):
    """Shared mesh/shape validation + sharding-spec construction for the
    dense N-D step builders (:func:`make_nd_train_step` and the
    launchable ``parallel.nd.NDEngine``). Returns ``(axes, n_total,
    param_specs)``."""
    axes = [a for a in (dp_axis, tp_axis, sp_axis) if a is not None]
    if not axes:
        raise ValueError("need at least one of dp_axis/tp_axis/sp_axis")
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for a in axes:
        if a not in sizes:
            raise ValueError(f"axis {a!r} not in mesh axes {mesh.axis_names}")
    if tp_axis:
        validate_tp_divisibility(model, tp_axis, sizes[tp_axis])
    validate_ulysses_heads(
        model, sp_axis, sizes, model.n_heads // (sizes[tp_axis] if tp_axis else 1)
    )
    n_total = 1
    for a in axes:
        n_total *= sizes[a]
    param_specs = (
        model.tp_param_specs(tp_axis)
        if tp_axis
        else jax.tree_util.tree_map(
            lambda _: P(),
            jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0))),
        )
    )
    return axes, n_total, param_specs


def make_nd_train_step(
    model: TransformerLM,
    mesh: Mesh,
    lr: float = 1e-2,
    *,
    dp_axis: Optional[str] = None,
    tp_axis: Optional[str] = None,
    sp_axis: Optional[str] = None,
    optimizer=None,
):
    """Jitted train step over any subset of (data, model, seq) axes of
    one mesh.

    With ``optimizer=None`` (plain SGD): ``(params, tokens) ->
    (new_params, loss)``. With ``optimizer`` (a name from
    ops.optimizers.get_optimizer or an Optimizer): ``((params,
    opt_state), tokens) -> ((params, opt_state), loss)`` — build the
    initial opt_state with ``get_optimizer(name).init(params)``;
    accumulators shard exactly like their parameters.

    Sharding: tokens ``[B, T]`` are ``P(dp_axis, sp_axis)``; params
    follow :meth:`TransformerLM.tp_param_specs` when ``tp_axis`` is set,
    else fully replicated.

    Gradient sync. Under ``check_vma=False`` the transpose of a forward
    psum is itself a psum (measured on jax 0.9 — NOT the identity), so
    each device's AD yields exactly ``d(sum over devices of
    loss_device)/d theta_local``: cotangents really flow across the
    collectives. With loss_device replicated over tp/sp within each dp
    group and the global objective the mean over dp groups, the true
    gradient of every leaf is therefore

        psum(g) over every participating axis the leaf is NOT sharded
        over, divided by the product of ALL participating axis sizes

    (a leaf sharded on an axis already carries that axis's full
    contribution; summing its copies over the axes it is replicated on
    completes the total, and the division converts the device-sum
    objective to the mean). The dp-only case reduces to BSP's classic
    psum-mean.
    """
    axes, n_total, param_specs = nd_spec_setup(
        model, mesh, dp_axis, tp_axis, sp_axis
    )
    init_fn = lambda: model.init(jax.random.PRNGKey(0))  # noqa: E731

    def body(params, tokens):
        loss, grads = jax.value_and_grad(model.loss)(
            params, tokens, sp_axis, tp_axis=tp_axis
        )
        grads = sync_grads_by_spec(grads, param_specs, axes, n_total)
        if dp_axis is not None:
            loss = lax.pmean(loss, dp_axis)  # report the global batch mean
        return loss, grads

    return build_spec_step(
        body, mesh, param_specs, P(dp_axis, sp_axis), lr, optimizer, init_fn
    )
