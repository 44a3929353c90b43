"""A byte-level LM under EVA attention: the ``evabyte`` stack (EvaByte),
under the zoo ``Model`` contract and with the incremental decode surface of
``tmpi serve --decode``.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). A vocabulary of bytes and
specials, no biases, RMS norms whose gain is ``1 + g`` (``g`` starts at
nought), a float32 residual stream under bfloat16 compute:

    x = emb[byte]                                       (float32)
    x += attn(norm_1(x));  x += mlp(norm_2(x))           mlp(h) = (silu(h W_g) * (h W_u)) W_d
    logits_i = norm_f(x) W_head_i,  i = 0 .. n_pred - 1  (float32; head i predicts the byte at t + 1 + i)

``norm(x) = x / sqrt(mean(x^2) + eps) * (1 + g)``, the mean square in
float32, the output in the compute dtype. ``attn`` is EVA attention
(``ops/pallas_eva.py``): ``q, k, v = h W_q, h W_k, h W_v``, MHA, ``q`` and
``k`` rotated at the TRUE position (pairs ``(j, j + D/2)``). The query at
``t`` (window block ``w = t // W``) sees in ONE float32 softmax (scale
``D^-1/2``) the exact keys ``wW .. t`` and, for every chunk ``c`` of ``C``
positions before ``wW``, one compressed key ``k~_c = sum_j softmax_j(mu_h .
x_j) k_j`` and value ``v~_c = sum_j softmax_j(phi_h . x_j - |x_j|^2 / 2)
v_j`` over its positions (``x_j = D^-1/4 k_j``; ``mu_h, phi_h`` learned, a
pair a head). Below ``W`` there is no compressed row and the layer is causal
softmax attention. A prompt runs the flash pass ``eva_prefill``, a decode
step ``eva_decode``; a chunk is compressed as its last position is cached.

The cache is of two kinds in one manager (:meth:`EvaByte.cache_spec`): the
COMPRESSED rows as pages (a page = one window block's ``W / C`` rows, the
engine's ``page_size`` = ``W`` positions, so a slot's page table is its
list of blocks and admission reserves them for prompt + new bytes), and a
RING a slot: the raw K and V of the current block, ``[layers, slots, 2, W,
H D]``, written at ``t % W`` and read below that length (a slot's next
occupant, and its next block, are invisible by length, not by content).
The engine's second pool is the pytree ``{"v", "ring"}``. Both programs
write every pool after their last read of it and take the pools donated.

Served, the step decodes ONE byte from head 0 (EvaByte's own multi-byte
decoding, verified by the model, is a decoding strategy: ROADMAP R7);
:meth:`apply` computes all heads. Leaves are bfloat16 and matmuls take them
as they are, on bfloat16 activations with fp32 accumulation; the residual
stream, the norms' statistics, the compression and the attention softmax are
fp32. :class:`EvaByte_Stage4` is the model cut to one pipeline stage of a
stated deployment (its docstring). Served, not trained: nothing here has a
backward pass.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.minicpm_sala import _rotate
from theanompi_tpu.models.mistral4 import Arch
from theanompi_tpu.models.transformer import next_token_loss, softmax_nll
from theanompi_tpu.ops.pallas_eva import eva_cache_write, eva_compress, eva_decode, eva_prefill


@dataclasses.dataclass
class EvaRecipe(Recipe):
    """``input_shape`` is ``(longest context served,)`` and ``num_classes``
    the vocabulary (as the other LM recipes). Window, chunk and tiles are
    fields because the CPU tests need small ones."""

    d_model: int = 64
    d_ff: int = 128
    n_heads: int = 4
    head_dim: int = 16
    n_layers: int = 4
    n_pred_heads: int = 8
    window: int = 32  # W: positions of a window block, exact
    chunk: int = 4  # C: positions a compressed key and value stand for
    rope_theta: float = 100000.0
    rms_eps: float = 1e-5
    init_std: float = 0.01275
    prefill_tq: int = 8  # query rows and keys of a tile of eva_prefill
    prefill_tk: int = 8
    decode_tile: int = 8  # ring rows a grid step of eva_decode
    param_dtype: object = jnp.bfloat16


class EvaByte(Model):
    """The ``evabyte`` stack; its default recipe is a tiny preset for the CPU
    tests (window 32, chunks of 4: both kinds of key are real from position
    32 on)."""

    name = "evabyte_lm"
    is_lm = True
    supports_decode = True

    def __init__(self, recipe: EvaRecipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        if r.window % r.chunk or r.window % r.decode_tile:
            raise ValueError(f"a window of {r.window} is no whole chunks of {r.chunk} or tiles of {r.decode_tile}")
        self.arch = Arch(r.n_layers, r.n_heads, r.d_model, int(r.input_shape[0]), r.compute_dtype)

    @classmethod
    def default_recipe(cls) -> EvaRecipe:
        return EvaRecipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(4096,),
            num_classes=320, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
        )

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, init_std) matrices and normal(0, D^-1/2) EVA vectors cast
        to the parameter dtype, norm gains ``g`` nought. Keys: ``split(key, 2
        + layers)`` = embedding, heads, then one a layer, itself split in
        nine: w_q w_k w_v w_o, the SwiGLU's w_g w_u w_d, mu, phi."""
        r = self.recipe
        d, V, dt, HD = r.d_model, r.num_classes, r.param_dtype, r.n_heads * r.head_dim

        def w(k, *shape, std=r.init_std):
            return (std * jax.random.normal(k, shape)).astype(dt)

        ks = jax.random.split(key, 2 + r.n_layers)
        params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, r.n_pred_heads * V),
                  "norm_f": jnp.zeros((d,), dt), "layers": []}
        for kl in ks[2:]:
            k = jax.random.split(kl, 9)
            params["layers"].append({
                "attn": {"w_q": w(k[0], d, HD), "w_k": w(k[1], d, HD), "w_v": w(k[2], d, HD),
                         "w_o": w(k[3], HD, d),
                         "mu": w(k[7], r.n_heads, r.head_dim, std=r.head_dim ** -0.5),
                         "phi": w(k[8], r.n_heads, r.head_dim, std=r.head_dim ** -0.5)},
                "norm_1": jnp.zeros((d,), dt), "norm_2": jnp.zeros((d,), dt),
                "mlp": {"w_g": w(k[4], d, r.d_ff), "w_u": w(k[5], d, r.d_ff), "w_d": w(k[6], r.d_ff, d)},
            })
        return params, {}

    # -- the parts both programs share -----------------------------------
    def _norm(self, x, g):
        r = self.recipe
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + r.rms_eps) * (1.0 + g.astype(jnp.float32))
        return y.astype(r.compute_dtype)

    def _qkv(self, p, h, positions):
        """``h [..., d]`` -> ``q, k, v [..., H, D]`` in the compute dtype,
        ``q`` and ``k`` rotated at ``positions``."""
        r = self.recipe
        dt = r.compute_dtype

        def heads(w):
            y = jnp.dot(h, w.astype(dt), preferred_element_type=jnp.float32)
            return y.reshape(*y.shape[:-1], r.n_heads, r.head_dim)

        q = _rotate(heads(p["w_q"]), positions, r.rope_theta)
        k = _rotate(heads(p["w_k"]), positions, r.rope_theta)
        return q.astype(dt), k.astype(dt), heads(p["w_v"]).astype(dt)

    def _mlp(self, p, h):
        dt = self.recipe.compute_dtype
        mid = jax.nn.silu(h @ p["w_g"].astype(dt)) * (h @ p["w_u"].astype(dt))
        return jnp.dot(mid, p["w_d"].astype(dt), preferred_element_type=jnp.float32)

    def _out(self, p, o):
        dt = self.recipe.compute_dtype
        return jnp.dot(o.astype(dt), p["w_o"].astype(dt), preferred_element_type=jnp.float32)

    def _logits(self, params, x, heads: int):
        """The first ``heads`` prediction heads on the final norm, fp32:
        ``[..., heads, V]``."""
        r = self.recipe
        V, dt = r.num_classes, r.compute_dtype
        y = jnp.dot(self._norm(x, params["norm_f"]), params["head"][:, :heads * V].astype(dt),
                    preferred_element_type=jnp.float32)
        return y.reshape(*y.shape[:-1], heads, V)

    def _compress(self, p, k, v):
        """``k, v [..., C, H, D]`` -> the compressed rows ``[..., H D]`` in the
        compute dtype (what the cache holds)."""
        r = self.recipe
        kc, vc = eva_compress(k, v, p["mu"], p["phi"])
        flat = lambda a: a.reshape(*a.shape[:-2], -1).astype(r.compute_dtype)  # noqa: E731
        return flat(kc), flat(vc)

    # -- the prompt's forms ------------------------------------------------
    def _attn_prompt(self, p, h, attend=eva_prefill):
        """One prompt's attention: ``h [T, d]`` (``T`` whole windows) ->
        (output ``[T, d]``, ``k``, ``v`` ``[T, H D]`` and the compressed rows
        ``[T / C, H D]`` twice)."""
        r = self.recipe
        T, HD = h.shape[0], r.n_heads * r.head_dim
        q, k, v = self._qkv(p, h, jnp.arange(T))
        chunks = lambda a: a.reshape(T // r.chunk, r.chunk, r.n_heads, r.head_dim)  # noqa: E731
        kc, vc = self._compress(p, chunks(k), chunks(v))
        with jax.named_scope("eva_prefill"):
            by_head = lambda a: jnp.swapaxes(a.reshape(a.shape[0], r.n_heads, r.head_dim), 0, 1)  # noqa: E731
            o = attend(by_head(q.reshape(T, HD)), by_head(k.reshape(T, HD)), by_head(v.reshape(T, HD)),
                       by_head(kc), by_head(vc), window=r.window, chunk=r.chunk, scale=r.head_dim ** -0.5,
                       tq=r.prefill_tq, tk=r.prefill_tk)
            o = jnp.swapaxes(o, 0, 1).reshape(T, HD)
        return self._out(p, o), k.reshape(T, HD), v.reshape(T, HD), kc, vc

    def _prompt(self, params, tokens, attend=eva_prefill):
        """``tokens [T]`` -> (``x [T, d]`` after the last layer, every
        layer's ``(k, v, kc, vc)``)."""
        x = params["tok_emb"][tokens.astype(jnp.int32)].astype(jnp.float32)
        cached = []
        for p in params["layers"]:
            a, *kv = self._attn_prompt(p["attn"], self._norm(x, p["norm_1"]), attend)
            cached.append(kv)
            x = x + a
            x = x + self._mlp(p["mlp"], self._norm(x, p["norm_2"]))
        return x, cached

    # -- contract surface ---------------------------------------------------
    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T]`` -> logits ``[B, T, n_pred_heads, V]`` fp32: the
        prompt's forms, a row of the batch at a time (padded at the end to
        whole windows: causality keeps the padding out of every real row)."""
        del train, rng  # no dropout
        r = self.recipe
        B, T = tokens.shape
        padded = jnp.pad(tokens, ((0, 0), (0, -T % r.window)))
        x = jnp.stack([self._prompt(params, row)[0][:T] for row in padded])
        return self._logits(params, x, r.n_pred_heads), state

    def loss(self, logits, labels):
        # head 0 is the next-byte head; labels ARE the byte window [B, T]
        return next_token_loss(labels.astype(jnp.int32), None, softmax_nll(logits[:, :, 0]))

    def metrics(self, logits, labels) -> dict:
        return {}

    # -- incremental decode surface (serve/decode DecodeEngine) -------------
    def cache_spec(self, page_size: int) -> dict:
        """Two kinds of state. PAGES of compressed rows, ``[W / C, H D]`` K~
        or V~ rows (``page_size`` must be the window: a page is one block's
        rows, so ``W`` positions). A RING a slot (``slots``: ``[layers,
        max_seqs, 2, W, H D]``, under ``"ring"`` in the second pool's pytree
        beside ``"v"``), K and V of the current block: bytes a slot, not a
        position. Donated: both programs update every pool in place."""
        r = self.recipe
        if page_size != r.window:
            raise ValueError(f"page_size {page_size} is not the window {r.window}: a page is one block's rows")
        HD = r.n_heads * r.head_dim
        page = (r.window // r.chunk, HD)
        return {
            "kind": "compressed", "k_page": page, "v_page": page, "dtype": r.compute_dtype, "donate": True,
            "slots": {"ring": {"layers": r.n_layers, "row": (2, r.window, HD), "dtype": r.compute_dtype}},
        }

    def cache_rows(self, seq_lens) -> dict:
        """Host side: what the query at each of ``seq_lens`` (numpy) reads in
        every layer: ``eva_window_positions`` (its block's exact keys, itself
        included) and ``eva_compressed_rows`` (one a chunk of every earlier
        block)."""
        r = self.recipe
        t = np.asarray(seq_lens, np.int64)
        return {"eva_window_positions": t % r.window + 1,
                "eva_compressed_rows": (t // r.window) * (r.window // r.chunk)}

    def visible_share(self, seq_lens) -> float:
        """Host side: rows a query reads (exact and compressed) over its
        context, the mean over ``seq_lens`` (numpy)."""
        t = np.asarray(seq_lens, np.int64)
        if t.size == 0:
            return 1.0
        rows = self.cache_rows(t)
        return float(np.mean((rows["eva_window_positions"] + rows["eva_compressed_rows"]) / (t + 1.0)))

    def decode_prefill(self, params, tokens, pages, k_pool, pools, slot, n_real, *,
                       page_size: int, attend=eva_prefill):
        """Cache one padded prompt (``tokens [T_b]``, ``pages [T_b / W]``, the
        scratch index past the prompt's blocks) for ``slot``, ``n_real`` of its
        positions real: the prompt's forms minus the heads; the compressed rows
        of every chunk COMPLETE below ``n_real`` to the slot's pages (a trailing
        partial chunk's row is nought until the decode step that completes it
        writes it; rows of later blocks go to the scratch page), and to the
        slot's ring K and V of the block ``n_real // W`` (empty where
        ``n_real`` ends a block: its rows are past the length). Every pool
        written once."""
        r = self.recipe
        T, W, HD = tokens.shape[0], r.window, r.n_heads * r.head_dim
        _, cached = self._prompt(params, tokens, attend)
        whole = (jnp.arange(T // r.chunk) < n_real // r.chunk)[:, None]
        by_pages = lambda i: jnp.stack([jnp.where(whole, c[i], 0) for c in cached]).reshape(  # noqa: E731
            len(cached), T // W, W // r.chunk, HD).astype(k_pool.dtype)
        start = jnp.minimum((n_real // W) * W, T - W)
        ring = jnp.stack([jnp.stack([jax.lax.dynamic_slice_in_dim(c[i], start, W) for i in (0, 1)])
                          for c in cached])[:, None]  # [L, 1, 2, W, HD]
        return k_pool.at[:, pages].set(by_pages(2)), {
            "v": pools["v"].at[:, pages].set(by_pages(3)),
            "ring": jax.lax.dynamic_update_slice(pools["ring"], ring.astype(pools["ring"].dtype),
                                                 (0, slot, 0, 0, 0)),
        }

    def decode_step(self, params, k_pool, pools, page_tables, seq_lens, last_tokens, active,
                    temperature, key, *, page_size: int, attend=eva_decode, write=eva_cache_write):
        """One continuous-batching iteration over ALL slots: slot ``s`` embeds
        ``last_tokens[s]`` at position ``t = seq_lens[s]``. Each layer attends
        (``eva_decode``) over the slot's compressed rows of blocks ``< t //
        W``, its ring rows ``< t % W`` and its own row; where ``t % C = C -
        1`` it compresses the chunk (the ring's ``C - 1`` rows before ``t``
        and its own). The new ring rows of every layer, and the completed
        compressed rows (to page ``page_tables[s, t // W]``, row ``(t % W) //
        C``; the scratch page where no chunk completes or the slot is
        inactive) go in ONE write after the last layer. Greedy on head 0
        where ``temperature[s] == 0``, else categorical under ``key``. ->
        ``(next_tokens [S], head 0's logits [S, V] fp32, k_pool, pools)``."""
        r = self.recipe
        S, M = page_tables.shape
        W, C, HD = r.window, r.chunk, r.n_heads * r.head_dim
        ring, v_pool = pools["ring"], pools["v"]
        t = seq_lens
        x = params["tok_emb"][last_tokens.astype(jnp.int32)].astype(jnp.float32)
        # the chunk's earlier rows in the ring: C - 1 rows ending before t % W (whole where a chunk completes)
        rows = jnp.clip(t % W - (C - 1), 0, W - (C - 1))[:, None] + jnp.arange(C - 1)[None]
        complete = active & (t % C == C - 1)
        kvs, cks, cvs = [], [], []
        for li, p in enumerate(params["layers"]):
            a = p["attn"]
            q, k, v = self._qkv(a, self._norm(x, p["norm_1"]), t)
            q, k, v = q.reshape(S, HD), k.reshape(S, HD), v.reshape(S, HD)
            with jax.named_scope("eva_decode"):
                o = attend(q, k, v, ring, k_pool, v_pool, page_tables, t, layer=li, n_heads=r.n_heads,
                           scale=r.head_dim ** -0.5, tile=r.decode_tile)
            # [S, C, H, D]: the ring's rows gathered K and V apart (a gather across the K/V axis re-lays the
            # whole ring out), then the step's own
            chunk = lambda half, new: jnp.concatenate(  # noqa: E731
                [ring[li, jnp.arange(S)[:, None], half, rows], new[:, None]], 1).reshape(S, C, r.n_heads, r.head_dim)
            ck, cv = self._compress(a, chunk(0, k), chunk(1, v))
            kvs.append(jnp.stack([k, v], 1))
            cks.append(ck)
            cvs.append(cv)
            x = x + self._out(a, o)
            x = x + self._mlp(p["mlp"], self._norm(x, p["norm_2"]))
        comp_page = jnp.where(complete, page_tables[jnp.arange(S), jnp.clip(t // W, 0, M - 1)], k_pool.shape[1] - 1)
        ring, k_pool, v_pool = write(ring, k_pool, v_pool, jnp.stack(kvs), jnp.stack(cks), jnp.stack(cvs),
                                     comp_page, t, chunk=C)
        logits = self._logits(params, x, 1)[:, 0]
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)[:, None]).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy), logits, k_pool, {"v": v_pool, "ring": ring}


class EvaByte_Stage4(EvaByte):
    """EvaByte (6.5B, ``model_type`` evabyte; huggingface.co/EvaByte/EvaByte
    config.json) at its published widths, cut to ONE pipeline stage of this
    deployment: eight stages of 4 layers, each layer whole on its chip. Held
    here: 4 layers (the pattern's period is one layer), with the embedding and
    all 8 prediction heads so that the stage serves alone; the whole
    vocabulary of 320, every head, every width: hidden 4,096, 32 heads of 128
    (32 K/V heads), SwiGLU 11,008, window 2,048, chunks of 16, rotary theta
    100,000, context 32,768. 821,366,784 parameters, 1.64 GB in bfloat16.
    The compression's weights are stated under ``assumed`` in
    ``benchmark/configs/evabyte-decode.json``. Served, not trained, here: 16
    bytes a parameter of training state leave no room at the guide's floor
    of 4 layers."""

    name = "evabyte_stage4"

    @classmethod
    def default_recipe(cls) -> EvaRecipe:
        return EvaRecipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(32768,),
            num_classes=320, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
            d_model=4096, d_ff=11008, n_heads=32, head_dim=128, n_layers=4, n_pred_heads=8,
            window=2048, chunk=16, rope_theta=100000.0, rms_eps=1e-5, init_std=0.01275,
            prefill_tq=512, prefill_tk=512, decode_tile=128,
        )
