"""Block-sparse attention by selection beside lightning linear attention: the
``minicpm_sala`` stack (MiniCPM-SALA), under the zoo ``Model`` contract and
with the incremental decode surface of ``tmpi serve --decode``.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). RMS norms (``eps`` 1e-6, a gain),
no biases, MiniCPM's scalings with ``L`` the PUBLISHED depth:

    x = scale_emb * emb[token]
    x += (scale_depth / sqrt(L)) * mixer(norm_1(x))
    x += (scale_depth / sqrt(L)) * mlp(norm_2(x))        mlp(h) = (silu(h W_g) * (h W_u)) W_d
    logits = (norm_f(x) / (d_model / dim_model_base)) W_head

A layer's ``mixer`` is one of two kinds (``recipe.mixer_types``):

- ``lightning-attn``: ``H`` heads of ``D``; ``q, k`` RMS-normed over each head
  with a gain and rotated at the TRUE position (pairs ``(j, j + D/2)``); a
  state ``S [D, D]`` a head in fp32, ``S_t = l_h S_(t-1) + k_t^T v_t``,
  ``o_t = D^-0.5 q_t S_t`` with ``l_h = exp(-2^(-8 (h + 1) / H))``; then an
  RMS norm over all ``H D`` with a gain, a sigmoid gate ``h W_gate``, ``W_o``.
  A prompt runs the chunked kernel, a decode step the one-position kernel
  (``ops/pallas_lightning.py``).
- ``minicpm4``: ``H`` query heads over ``G`` K/V heads, ``q, k`` RMS-normed,
  NO rotary. The query at position ``t`` sees BLOCKS of ``block`` keys: the
  first ``init_blocks``, every block that overlaps the last ``window``
  positions, and the ``topk`` best-scored of the rest, scored per K/V head
  from compressed keys (:meth:`MiniCPMSALA._visible`); one softmax over the
  positions ``<= t`` of their union, a sigmoid gate, ``W_o``. The rule goes BY
  QUERY POSITION, so that a prompt's row and a decode step's are the same
  row. A prompt runs the flash pass under a per-row block mask, a decode step
  the walk over its chosen pages (``ops/pallas_sparse.py``).

The cache is of three kinds in one manager (:meth:`MiniCPMSALA.cache_spec`):
K and V PAGES for the ``minicpm4`` layers alone (page size = the sparse
block, so a chosen block is a page id), those layers' COMPRESSED KEYS a slot
(one mean-pooled key a ``stride`` positions) and the lightning layers' STATE
a slot. The engine's second pool is the pytree ``{"v", "compressed",
"state"}``. Both programs write every pool after their last read of it and
take the pools donated.

Leaves are bfloat16 and matmuls take them as they are, on bfloat16
activations with fp32 accumulation; the residual stream, the norms, the
selection (scores of compressed keys, their softmax, the choice) and the
attention softmaxes are fp32, the lightning state and its products fp32.
:class:`MiniCPM_SALA_Stage8` is the model cut to one pipeline stage of a
stated deployment (its docstring). Served, not trained: nothing here has a
backward pass.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.mistral4 import Arch
from theanompi_tpu.models.transformer import _rms, next_token_loss, softmax_nll
from theanompi_tpu.ops.pallas_lightning import lightning_chunk, lightning_step
from theanompi_tpu.ops.pallas_sparse import (
    sparse_cache_write, sparse_decode, sparse_prefill, tile_lists)

SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


@dataclasses.dataclass
class SALARecipe(Recipe):
    """``input_shape`` is ``(longest context served,)`` and ``num_classes``
    the vocabulary (as the other LM recipes). The sparse sizes and the layer
    list are fields because the CPU tests need small ones."""

    d_model: int = 64
    d_ff: int = 128
    n_heads: int = 4
    head_dim: int = 16
    n_kv_heads: int = 2  # of the minicpm4 layers
    mixer_types: Tuple[str, ...] = (SPARSE, LIGHTNING, LIGHTNING, LIGHTNING)
    depth_published: int = 32  # L of scale_depth / sqrt(L), whatever the cut
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    rope_theta: float = 10000.0
    rms_eps: float = 1e-6
    # the minicpm4 layers' selection (MiniCPM4's sparse_config)
    sparse_kernel: int = 4  # positions a compressed key is the mean of
    sparse_stride: int = 2  # positions between two compressed keys
    sparse_block: int = 8  # keys a block = the cache's page size
    sparse_topk: int = 2  # chosen blocks beside the forced ones
    sparse_init_blocks: int = 1
    sparse_window: int = 16  # the last positions always seen
    chunk: int = 8  # rows of a chunk of the lightning scan
    prefill_tq: int = 8  # query rows and keys of a tile of the sparse prefill
    prefill_tk: int = 8
    select_rows: int = 1024  # query rows scored at a time in a prefill
    param_dtype: object = jnp.bfloat16


def decay_rates(n_heads: int):
    """``[H]`` fp32: head ``h``'s state decays by ``exp(-2^(-8 (h + 1) / H))``
    a position (Lightning Attention-2's slopes), the same in every layer."""
    return jnp.asarray(2.0 ** (-8.0 * (np.arange(n_heads) + 1.0) / n_heads), jnp.float32)


def _turn(positions, head_dim: int, theta: float):
    """``(cos, sin) [..., T, D / 2]`` of ``positions [..., T]``: frequencies
    from float64, angles in fp32."""
    half = head_dim // 2
    freq = jnp.asarray(theta ** (-np.arange(half, dtype=np.float64) / half), jnp.float32)
    ang = positions.astype(jnp.float32)[..., None] * freq
    return jnp.cos(ang), jnp.sin(ang)


def _rotate(x, positions, theta: float):
    """``[..., T, H, D]`` in fp32 rotated by ``positions [..., T]``, pairs
    ``(j, j + D/2)``."""
    half = x.shape[-1] // 2
    cos, sin = (t[..., None, :] for t in _turn(positions, x.shape[-1], theta))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


class MiniCPMSALA(Model):
    """The ``minicpm_sala`` stack; its default recipe is a tiny preset for
    the CPU tests (from position 47 on the choice of blocks is real)."""

    name = "minicpm_sala_lm"
    is_lm = True
    supports_decode = True

    def __init__(self, recipe: SALARecipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        kinds = tuple(r.mixer_types)
        if not kinds or set(kinds) - {SPARSE, LIGHTNING}:
            raise ValueError(f"mixer_types {kinds}: each is {SPARSE!r} or {LIGHTNING!r}")
        if r.n_heads % r.n_kv_heads:
            raise ValueError(f"{r.n_heads} query heads over {r.n_kv_heads} K/V heads")
        if r.sparse_kernel % r.sparse_stride or r.sparse_block % r.sparse_stride:
            raise ValueError("the compression kernel and the block are whole strides")
        if r.sparse_kernel - r.sparse_stride > r.sparse_block or r.sparse_window < r.sparse_kernel:
            raise ValueError("a compressed key overlaps at most two blocks and lies inside the window")
        self.sparse_layers = tuple(i for i, k in enumerate(kinds) if k == SPARSE)
        self.lightning_layers = tuple(i for i, k in enumerate(kinds) if k == LIGHTNING)
        self.residual_scale = r.scale_depth / math.sqrt(r.depth_published)
        self.arch = Arch(len(kinds), r.n_heads, r.d_model, int(r.input_shape[0]), r.compute_dtype)

    @classmethod
    def default_recipe(cls) -> SALARecipe:
        return SALARecipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(4096,),
            num_classes=64, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
        )

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, 0.02) matrices cast to the parameter dtype, gains ones.
        Keys: ``split(key, 2 + layers)`` = embedding, head, then one a layer,
        itself split in eight: w_q w_k w_v w_gate w_o, the SwiGLU's w_g w_u
        w_d."""
        r = self.recipe
        d, V, dt = r.d_model, r.num_classes, r.param_dtype
        H, D = r.n_heads, r.head_dim

        def w(k, *shape):
            return (0.02 * jax.random.normal(k, shape)).astype(dt)

        ks = jax.random.split(key, 2 + len(r.mixer_types))
        params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
                  "norm_f": jnp.ones((d,), dt), "layers": []}
        for kind, kl in zip(r.mixer_types, ks[2:]):
            k = jax.random.split(kl, 8)
            kv = (r.n_kv_heads if kind == SPARSE else H) * D
            mixer = {"w_q": w(k[0], d, H * D), "w_k": w(k[1], d, kv), "w_v": w(k[2], d, kv),
                     "w_gate": w(k[3], d, H * D), "w_o": w(k[4], H * D, d),
                     "q_norm": jnp.ones((D,), dt), "k_norm": jnp.ones((D,), dt)}
            if kind == LIGHTNING:
                mixer["o_norm"] = jnp.ones((H * D,), dt)
            params["layers"].append({
                "mixer": mixer, "norm_1": jnp.ones((d,), dt), "norm_2": jnp.ones((d,), dt),
                "mlp": {"w_g": w(k[5], d, r.d_ff), "w_u": w(k[6], d, r.d_ff), "w_d": w(k[7], r.d_ff, d)},
            })
        return params, {}

    # -- the parts both programs share -----------------------------------
    def _qkv(self, p, h, n_kv):
        """``h [..., d]`` in fp32 -> ``q [..., H, D]`` and ``k [..., n_kv, D]``
        normed over each head, in fp32, ``v [..., n_kv, D]`` and the gate
        ``[..., H D]`` in the compute dtype."""
        r = self.recipe
        dt, f32 = r.compute_dtype, jnp.float32
        h = h.astype(dt)

        def heads(w, n):
            y = jnp.dot(h, w.astype(dt), preferred_element_type=f32)
            return y.reshape(*y.shape[:-1], n, r.head_dim)

        q = _rms(heads(p["w_q"], r.n_heads), p["q_norm"], r.rms_eps)
        k = _rms(heads(p["w_k"], n_kv), p["k_norm"], r.rms_eps)
        return q, k, heads(p["w_v"], n_kv).astype(dt), jax.nn.sigmoid(
            jnp.dot(h, p["w_gate"].astype(dt), preferred_element_type=f32))

    def _mlp(self, p, h):
        dt = self.recipe.compute_dtype
        h = h.astype(dt)
        mid = jax.nn.silu(h @ p["w_g"].astype(dt)) * (h @ p["w_u"].astype(dt))
        return jnp.dot(mid, p["w_d"].astype(dt), preferred_element_type=jnp.float32)

    def _out(self, p, o):
        dt = self.recipe.compute_dtype
        return jnp.dot(o.astype(dt), p["w_o"].astype(dt), preferred_element_type=jnp.float32)

    def _embed(self, params, tokens):
        return self.recipe.scale_emb * params["tok_emb"][tokens.astype(jnp.int32)].astype(jnp.float32)

    def _logits(self, params, x):
        r = self.recipe
        dt = r.compute_dtype
        h = _rms(x, params["norm_f"], r.rms_eps) / (r.d_model / r.dim_model_base)
        return h.astype(dt) @ params["head"].astype(dt)

    def _lightning_out(self, p, o, gate):
        """``o [..., H, D]`` fp32, unscaled -> the mixer's output ``[..., d]``."""
        r = self.recipe
        o = (o * r.head_dim ** -0.5).reshape(*o.shape[:-2], -1)
        return self._out(p, _rms(o, p["o_norm"], r.rms_eps) * gate)

    # -- selection --------------------------------------------------------
    @property
    def max_chosen(self) -> int:
        """The most blocks a query sees: the first, the window's, the chosen."""
        r = self.recipe
        return r.sparse_init_blocks + (r.sparse_window - 2) // r.sparse_block + 2 + r.sparse_topk

    def _visible(self, q, ck, t, n_blocks: int):
        """Steps 2-4 of the selection. ``q [..., G, R, D]`` (normed),
        ``ck [..., nC, G D]`` compressed keys as the cache holds them, a
        position's K/V heads side by side (``[nC, G D]`` where all rows share
        them), ``t [...]`` the rows' positions -> ``[..., G, n_blocks]``
        bool: the blocks each row sees. Per query head ``p = softmax`` over
        the compressed keys whose window ends at or before ``t``; per K/V
        head ``P`` = the sum over its heads; a block's score = the largest
        ``P`` of the windows that overlap it; seen are the first blocks, those
        of the window, and the ``topk`` best of the rest (ties to the lower
        index). fp32."""
        r = self.recipe
        f32 = jnp.float32
        st, B, topk = r.sparse_stride, r.sparse_block, r.sparse_topk
        per, over = B // st, r.sparse_kernel // st  # windows that start in a block; strides a window spans
        nC, D = ck.shape[-2], r.head_dim
        with jax.named_scope("sparse_select"):
            # head g's keys are lanes g D .. of a row: no [.., G, D] view, which
            # would re-lay the rows into padded tiles
            eq = "...rd,cd->...rc" if ck.ndim == 2 else "...rd,...cd->...rc"
            s = jnp.stack([jnp.einsum(eq, q[..., g, :, :], ck[..., g * D:(g + 1) * D],
                                      preferred_element_type=f32)
                           for g in range(r.n_kv_heads)], axis=-3) * D ** -0.5
            valid = jnp.arange(nC) < jnp.maximum((t[..., None] + 1 - r.sparse_kernel) // st + 1, 0)
            valid = valid[..., None, None, :]
            p = jax.nn.softmax(jnp.where(valid, s, -1e30), axis=-1)
            P = jnp.where(valid[..., 0, :], jnp.sum(p, axis=-2), -1.0)  # [..., G, nC]
            # block b is overlapped by the windows per*b - over + 1 .. per*b + per - 1
            lead, taken = P.shape[:-1], min(nC, per * n_blocks - over + 1)
            padded = jnp.concatenate([
                jnp.full((*lead, over - 1), -1.0, f32), P[..., :taken],
                jnp.full((*lead, per * (n_blocks + 1) - (over - 1) - taken), -1.0, f32)], axis=-1)
            score = jnp.max(padded[..., :per * n_blocks].reshape(*lead, n_blocks, per), axis=-1)
            if over > 1:
                nxt = padded[..., per:].reshape(*lead, n_blocks, per)[..., :over - 1]
                score = jnp.maximum(score, jnp.max(nxt, axis=-1))
            b = jnp.arange(n_blocks)
            tb = (t // B)[..., None, None]
            lo = (jnp.maximum(t - (r.sparse_window - 1), 0) // B)[..., None, None]
            forced = (b < r.sparse_init_blocks) | ((b >= lo) & (b <= tb))
            rest = (b >= r.sparse_init_blocks) & (b < lo)
            if n_blocks <= topk:
                return jnp.broadcast_to(forced | rest, (*lead, n_blocks))
            # the topk-th largest score of the rest, exactly and without a sort (a
            # sort of 256 blocks a row was a seventh of a 16k prefill): scores are
            # sums of probabilities, so their fp32 bit patterns order as they do;
            # the largest pattern that topk of them reach is found bit by bit
            bits = jnp.where(rest, jax.lax.bitcast_convert_type(jnp.maximum(score, 0.0), jnp.int32), -1)
            kth = jnp.zeros((*lead, 1), jnp.int32)
            for bit in range(30, -1, -1):
                trial = kth | (1 << bit)
                kth = jnp.where(jnp.sum(bits >= trial, axis=-1, keepdims=True) >= topk, trial, kth)
            above = bits > kth
            ties = rest & (bits == kth)  # fewer than topk left: kth stays 0 and all of them fit
            room = topk - jnp.sum(above, axis=-1, keepdims=True)
            return forced | above | (ties & (jnp.cumsum(ties, axis=-1) <= room))

    def visible_share(self, seq_lens) -> float:
        """Host side: the share of their context that the sparse layers' query
        at each of ``seq_lens`` (numpy) sees, the mean over them."""
        r = self.recipe
        t = np.asarray(seq_lens, np.int64)
        if t.size == 0:
            return 1.0
        B = r.sparse_block
        tb, lo = t // B, np.maximum(t - (r.sparse_window - 1), 0) // B
        rest = np.maximum(lo - r.sparse_init_blocks, 0)
        blocks = np.minimum(tb + 1, np.minimum(lo, r.sparse_init_blocks) + (tb - lo + 1)
                            + np.minimum(rest, r.sparse_topk))
        return float(np.mean((blocks * B - (B - 1 - t % B)) / (t + 1.0)))

    # -- the prompt's forms ------------------------------------------------
    def _prompt_blocks(self, q, k):
        """A prompt's selection: ``q [T, H, D]`` and ``k [T, G, D]`` (normed,
        the compute dtype) -> (the blocks each row sees, ``[T, G, T / block]``
        bool, and the compressed keys ``[nC, G D]``)."""
        r = self.recipe
        G, D, dt, T = r.n_kv_heads, r.head_dim, r.compute_dtype, q.shape[0]
        st, over = r.sparse_stride, r.sparse_kernel // r.sparse_stride
        strides = jnp.mean(k.astype(jnp.float32).reshape(T // st, st, G, D), axis=1)
        nC = T // st - over + 1
        ck = (sum(strides[i:i + nC] for i in range(over)) / over).astype(dt).reshape(nC, G * D)
        nB, rows = T // r.sparse_block, min(r.select_rows, T)
        qg = q.reshape(T // rows, rows, G, r.n_heads // G, D)

        def some_rows(x):
            q_rows, first = x
            return self._visible(q_rows, ck, first + jnp.arange(rows), nB)

        return jax.lax.map(some_rows, (qg, jnp.arange(0, T, rows))).reshape(T, G, nB), ck

    def _sparse_prompt(self, p, h, n_real, attend=sparse_prefill):
        """One prompt's ``minicpm4`` layer: ``h [T, d]`` -> (output ``[T, d]``,
        its rows at or past ``n_real`` zero, ``k``, ``v`` ``[T, G D]`` and the
        compressed keys ``[nC, G D]`` for the cache)."""
        r = self.recipe
        G, D, B, dt = r.n_kv_heads, r.head_dim, r.sparse_block, r.compute_dtype
        R, T = r.n_heads // G, h.shape[0]
        q, k, v, gate = self._qkv(p, h, G)
        scaled = (q * D ** -0.5).astype(dt)  # the softmax's scale on q in fp32, before its cast
        q, k = q.astype(dt), k.astype(dt)
        seen, ck = self._prompt_blocks(q, k)
        nB = T // B
        with jax.named_scope("sparse_prefill"):
            mask = jnp.swapaxes(seen, 0, 1).astype(dt)  # [G, T, nB]
            mask = jnp.pad(mask, ((0, 0), (0, 0), (0, -nB % 128)))  # whole lanes
            tq, tk = min(r.prefill_tq, T), min(r.prefill_tk, T)
            steps = tile_lists(mask, n_real, tq, tk, B)
            o = attend(jnp.transpose(scaled.reshape(T, G, R, D), (1, 2, 0, 3)), jnp.swapaxes(k, 0, 1),
                       jnp.swapaxes(v, 0, 1), mask, n_real, steps, block=B, tq=tq, tk=tk)
            o = jnp.transpose(o, (2, 0, 1, 3)).reshape(T, -1)
        return self._out(p, o.astype(jnp.float32) * gate), k.reshape(T, G * D), v.reshape(T, G * D), ck

    def _lightning_prompt(self, p, h, n_real, scan=lightning_chunk):
        """One prompt's ``lightning-attn`` layer: ``h [T, d]`` -> (output
        ``[T, d]``, the state ``[H, D, D]`` after position ``n_real - 1``)."""
        r = self.recipe
        q, k, v, gate = self._qkv(p, h, r.n_heads)
        turn = _turn(jnp.arange(h.shape[0]), r.head_dim, r.rope_theta)
        with jax.named_scope("lightning_chunk"):  # q and k rotated there
            o, state = scan(q, k, v, decay_rates(r.n_heads), n_real, min(r.chunk, h.shape[0]), turn)
        return self._lightning_out(p, o, gate), state

    def _prompt(self, params, tokens, n_real, attend=sparse_prefill, scan=lightning_chunk):
        """``tokens [T]`` -> (``x [T, d]`` after the last layer, the sparse
        layers' ``(k, v, compressed keys)``, the lightning layers' states)."""
        r = self.recipe
        x = self._embed(params, tokens)
        paged, states = [], []
        for kind, p in zip(r.mixer_types, params["layers"]):
            h = _rms(x, p["norm_1"], r.rms_eps)
            if kind == SPARSE:
                a, *cached = self._sparse_prompt(p["mixer"], h, n_real, attend)
                paged.append(cached)
            else:
                a, state = self._lightning_prompt(p["mixer"], h, n_real, scan)
                states.append(state)
            x = x + self.residual_scale * a
            x = x + self.residual_scale * self._mlp(p["mlp"], _rms(x, p["norm_2"], r.rms_eps))
        return x, paged, states

    # -- contract surface ---------------------------------------------------
    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T]`` (``T`` whole tiles, chunks and blocks) -> logits:
        the prompt's forms, a row of the batch at a time."""
        del train, rng  # no dropout
        T = tokens.shape[1]
        x = jnp.stack([self._prompt(params, row, T)[0] for row in tokens])
        return self._logits(params, x), state

    def loss(self, logits, labels):
        # labels ARE the token window [B, T]; shifted targets in-model
        return next_token_loss(labels.astype(jnp.int32), None, softmax_nll(logits))

    def metrics(self, logits, labels) -> dict:
        return {}

    # -- incremental decode surface (serve/decode DecodeEngine) -------------
    def cache_spec(self, page_size: int) -> dict:
        """Three kinds of state. PAGES, over the ``minicpm4`` layers alone:
        ``[page_size, G D]`` K or V rows, a position's K/V heads side by side
        (``page_size`` must be the sparse block, so that a chosen block is a
        page). A SLOT (``slots``: each ``[layers, max_seqs, ...]``, under its
        name in the second pool's pytree beside ``"v"``): ``compressed``, one
        mean-pooled key row ``[G D]`` a ``sparse_stride`` positions of the
        longest context, and ``state``, ``[H, D, D]`` fp32 a lightning layer.
        Donated: both programs update every pool in place."""
        r = self.recipe
        if page_size != r.sparse_block:
            raise ValueError(f"page_size {page_size} is not the sparse block {r.sparse_block}: "
                             "a chosen block must be a page")
        page = (page_size, r.n_kv_heads * r.head_dim)
        return {
            "kind": "kv", "k_page": page, "v_page": page, "dtype": r.compute_dtype, "donate": True,
            "paged_layers": len(self.sparse_layers),
            "slots": {
                "compressed": {"layers": len(self.sparse_layers), "row": page[1:],
                               "positions_per_row": r.sparse_stride, "dtype": r.compute_dtype},
                "state": {"layers": len(self.lightning_layers),
                          "row": (r.n_heads, r.head_dim, r.head_dim), "dtype": jnp.float32},
            },
        }

    def decode_prefill(self, params, tokens, pages, k_pool, pools, slot, n_real, *,
                       page_size: int, attend=sparse_prefill, scan=lightning_chunk):
        """Cache one padded prompt (``tokens [T_b]``, ``pages [T_b /
        page_size]``, the scratch index for the padding tail) for ``slot``,
        ``n_real`` of its positions real: the prompt's forms minus the head;
        K, V and the compressed keys of every position to the slot's pages
        and rows (those past ``n_real`` are never read: a later step
        overwrites them before its position makes them visible), the
        lightning state at ``n_real`` to the slot. Every pool written once."""
        _, paged, states = self._prompt(params, tokens, n_real, attend, scan)
        n = tokens.shape[0] // page_size
        by_pages = lambda rows: jnp.stack(rows).reshape(len(rows), n, page_size, -1)  # noqa: E731
        k = by_pages([c[0] for c in paged])
        v = by_pages([c[1] for c in paged])
        ck = jnp.stack([c[2] for c in paged])[:, None]  # [Ls, 1, nC, G D]
        at = (0, slot, 0, 0)
        return k_pool.at[:, pages].set(k.astype(k_pool.dtype)), {
            "v": pools["v"].at[:, pages].set(v.astype(k_pool.dtype)),
            "compressed": jax.lax.dynamic_update_slice(
                pools["compressed"], ck.astype(pools["compressed"].dtype), at),
            "state": jax.lax.dynamic_update_slice(pools["state"], jnp.stack(states)[:, None], (*at, 0)),
        }

    def _completed_key(self, k_pages, first_page, k_new, tables, t):
        """The compressed key whose window ends AT position ``t`` (``[S, G
        D]`` fp32, and whether there is one): the mean of the step's own key
        row and of the ``kernel - 1`` rows before it, read from the (at most
        two) pages that hold them (``k_pages``: the pool as ``[L (n_pages +
        1), page, G D]``, the layer's pages from ``first_page`` on: no layer is
        cut out of it)."""
        r = self.recipe
        B, K = r.sparse_block, r.sparse_kernel
        S, M = tables.shape
        first = jnp.maximum(t - (K - 1), 0)
        blocks = jnp.stack([first // B, t // B], axis=1)  # [S, 2]
        got = k_pages[first_page + jnp.take_along_axis(tables, jnp.clip(blocks, 0, M - 1), axis=1)]  # [S, 2, B, G D]
        pos = blocks[..., None] * B + jnp.arange(B)  # [S, 2, B]
        mine = (pos >= first[:, None, None]) & (pos < t[:, None, None])
        mine = mine.at[:, 0].set(mine[:, 0] & (blocks[:, 0] != blocks[:, 1])[:, None])  # one page, read once
        total = jnp.sum(jnp.where(mine[..., None], got.astype(jnp.float32), 0.0), axis=(1, 2))
        whole = (t >= K - 1) & ((t + 1 - K) % r.sparse_stride == 0)
        return (total + k_new.astype(jnp.float32)) / K, whole

    def decode_step(self, params, k_pool, pools, page_tables, seq_lens, last_tokens, active,
                    temperature, key, *, page_size: int, attend=sparse_decode, step=lightning_step):
        """One continuous-batching iteration over ALL slots: slot ``s`` embeds
        ``last_tokens[s]`` at position ``seq_lens[s]``. A ``minicpm4`` layer
        completes the compressed key whose window ends here, scores, chooses
        its pages and attends over them and its own row (``sparse_decode``);
        its new K and V rows go to (page ``page_tables[s, pos // page_size]``,
        offset ``pos % page_size``) in ONE write after the last layer,
        inactive slots' to the scratch page. A ``lightning-attn`` layer steps
        its slot's state, from nought where ``seq_lens[s] == 0``
        (``lightning_step``). Greedy where ``temperature[s] == 0``, else
        categorical under ``key``. -> ``(next_tokens [S], logits [S, V] fp32,
        k_pool, pools)``."""
        r = self.recipe
        S, M = page_tables.shape
        G, D, dt = r.n_kv_heads, r.head_dim, r.compute_dtype
        R, N = r.n_heads // G, min(self.max_chosen, M)
        scratch = k_pool.shape[1] - 1
        v_pool, compressed, state = pools["v"], pools["compressed"], pools["state"]
        rates = decay_rates(r.n_heads)
        x = self._embed(params, last_tokens)
        ks, vs, cks = [], [], []
        for li, (kind, p) in enumerate(zip(r.mixer_types, params["layers"])):
            h = _rms(x, p["norm_1"], r.rms_eps)
            mx = p["mixer"]
            if kind == SPARSE:
                pi = len(ks)  # this layer's place among the paged ones
                q, k, v, gate = self._qkv(mx, h, G)
                q, k = q.astype(dt).reshape(S, G, R, D), k.astype(dt)
                new, whole = self._completed_key(
                    k_pool.reshape(-1, *k_pool.shape[2:]), pi * k_pool.shape[1], k.reshape(S, G * D),
                    page_tables, seq_lens)
                at = (seq_lens + 1 - r.sparse_kernel) // r.sparse_stride
                ck = compressed[pi]
                ck = jnp.where(((jnp.arange(ck.shape[1]) == at[:, None]) & whole[:, None])[..., None],
                               new.astype(ck.dtype)[:, None, :], ck)  # [S, nC, G D]
                seen = self._visible(q, ck, seq_lens, M)  # [S, G, M]
                with jax.named_scope("sparse_decode"):
                    # the seen blocks in order, without a sort: block b is the list's entry number
                    # (seen blocks before it); at most N are seen, entries past them stay 0
                    place = jnp.where(seen, jnp.cumsum(seen, axis=-1) - 1, -1)  # [S, G, M]
                    blocks = jnp.sum(jnp.where(place[..., None] == jnp.arange(N), jnp.arange(M)[:, None], 0),
                                     axis=-2)
                    sel = jnp.take_along_axis(page_tables[:, None, :], blocks, axis=-1)
                    o = attend(q, k, v, k_pool, v_pool, sel, blocks, jnp.sum(seen, axis=-1), seq_lens,
                               layer=pi, scale=D ** -0.5)
                a = self._out(mx, o.reshape(S, -1).astype(jnp.float32) * gate)
                ks.append(k.reshape(S, G * D))
                vs.append(v.reshape(S, G * D))
                cks.append(ck)
            else:
                q, k, v, gate = self._qkv(mx, h, r.n_heads)
                q, k = _rotate(q, seq_lens, r.rope_theta).astype(dt), _rotate(k, seq_lens, r.rope_theta).astype(dt)
                with jax.named_scope("lightning_step"):
                    o, state = step(q, k, v, rates, state, seq_lens, layer=li - len(ks))
                a = self._lightning_out(mx, o, gate)
            x = x + self.residual_scale * a
            x = x + self.residual_scale * self._mlp(p["mlp"], _rms(x, p["norm_2"], r.rms_eps))
        write_page = jnp.where(
            active, page_tables[jnp.arange(S), jnp.clip(seq_lens // page_size, 0, M - 1)], scratch)
        k_pool, v_pool = sparse_cache_write(k_pool, v_pool, jnp.stack(ks), jnp.stack(vs), write_page, seq_lens)
        logits = self._logits(params, x).astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)[:, None]).astype(jnp.int32)
        return (jnp.where(temperature > 0, sampled, greedy), logits, k_pool,
                {"v": v_pool, "compressed": jnp.stack(cks), "state": state})


class MiniCPM_SALA_Stage8(MiniCPMSALA):
    """MiniCPM-SALA (9B, ``model_type`` minicpm_sala;
    huggingface.co/openbmb/MiniCPM-SALA config.json) at its published widths,
    cut to ONE pipeline stage of this deployment: four stages of 8 layers,
    each layer whole on its chip. Held here: two periods of ``(minicpm4,
    lightning-attn x 3)`` (the published ratio 1 : 3 and, as published, a
    sparse first layer; the published order is irregular), with embedding and
    head so that the stage serves alone; the whole vocabulary, every head,
    every width; depth 8 of 32, the residual scale that of 32.
    2,820,569,088 parameters, 5.64 GB in bfloat16. The sparse sizes are
    MiniCPM4's ``sparse_config`` (``benchmark/configs/minicpm-sala-decode.json``
    states them under ``assumed``). Served, not trained, here: 16 bytes a
    parameter of training state do not fit one chip at the guide's floors."""

    name = "minicpm_sala_stage8"

    @classmethod
    def default_recipe(cls) -> SALARecipe:
        return SALARecipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(524288,),
            num_classes=73448, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
            d_model=4096, d_ff=16384, n_heads=32, head_dim=128, n_kv_heads=2,
            mixer_types=(SPARSE, LIGHTNING, LIGHTNING, LIGHTNING) * 2, depth_published=32,
            scale_emb=12.0, scale_depth=1.4, dim_model_base=256, rope_theta=10000.0, rms_eps=1e-6,
            sparse_kernel=32, sparse_stride=16, sparse_block=64, sparse_topk=64,
            sparse_init_blocks=1, sparse_window=2048, chunk=128, prefill_tq=64, prefill_tk=512,
            select_rows=1024,
        )
