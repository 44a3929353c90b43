"""Mamba-1 selective state-space layers beside attention layers: the
``jamba`` stack (Jamba), under the zoo ``Model`` contract and with the
incremental decode surface of ``tmpi serve --decode``.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). Pre-norm layers, RMS norms with a
gain (``eps`` 1e-6), no positional encoding of any kind, a tied embedding:

    x = emb[token]                                      (float32)
    x += mixer(norm_1(x));  x += mlp(norm_2(x))          mlp(h) = (silu(h W_g) * (h W_u)) W_d
    logits = norm_f(x) emb^T                            (float32)

Layer ``i`` is ATTENTION where ``i % attn_period == attn_offset`` and MAMBA
otherwise (:func:`layer_kinds`).

- attention: ``H`` query heads over ``n_kv_heads`` K/V heads of ``D``, no
  bias, causal softmax at scale ``D^-1/2``; a prompt runs ``flash_attention``,
  a decode step ``sparse_decode`` with every page of the slot chosen
  (``ops/pallas_sparse.py``).
- Mamba-1 (``E = expand d``; ``N`` states, a window of ``K`` taps, ``dt``
  of rank ``R``; no projection bias, a convolution bias):

      x, z = split(h W_in)
      x = silu(causal_depthwise_conv_K(x) + b_conv)
      dt_r, B, C = split(x W_x);  each RMS-normed with a gain
      dt = softplus(dt_r W_dt + b_dt);  A = -exp(A_log)
      h_t = exp(dt_t A) h_(t-1) + (dt_t x_t) B_t;  y_t = C_t . h_t + D x_t
      out = (y * silu(z)) W_out

  A prompt runs ``mamba_scan`` (one Pallas kernel a layer), a decode step
  ``mamba_step`` (``ops/pallas_mamba.py``).

The cache is of three kinds in one manager (:meth:`Jamba.cache_spec`): K and V
PAGES for the attention layers alone, and a SLOT each Mamba layer: ``conv``,
the last ``K - 1`` pre-convolution rows ``[K - 1, E]``, and ``ssm``, the
state ``[N, E]`` fp32 (states on sublanes, channels on lanes). The engine's
second pool is the pytree ``{"v", "conv", "ssm"}``. Both programs write every
pool after their last read of it and take the pools donated.

Leaves are bfloat16 and matmuls take them as they are, on bfloat16
activations with fp32 accumulation; the residual stream, the norms, ``dt``,
``exp(dt A)``, the state, ``y``, the convolution's sum and the attention
softmax are fp32. :class:`Jamba2_3B_Stage2` is the model cut to one pipeline
stage of a stated deployment (its docstring). Served, not trained: nothing
here has a backward pass.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.mistral4 import Arch
from theanompi_tpu.models.transformer import next_token_loss, softmax_nll
from theanompi_tpu.ops.pallas_attention import flash_attention
from theanompi_tpu.ops.pallas_mamba import mamba_scan, mamba_step
from theanompi_tpu.ops.pallas_sparse import sparse_cache_write, sparse_decode

ATTENTION, MAMBA = "attention", "mamba"
INIT_STD = 0.02  # every matrix, the convolution's taps and bias
DT_INIT = (1e-3, 1e-1)  # b_dt: the inverse softplus of dt drawn log-uniform in this range (Mamba's)


@dataclasses.dataclass
class JambaRecipe(Recipe):
    """``input_shape`` is ``(longest context served,)`` and ``num_classes``
    the vocabulary (as the other LM recipes). Sizes and tiles are fields
    because the CPU tests need small ones."""

    d_model: int = 64
    d_ff: int = 128
    n_heads: int = 4
    n_kv_heads: int = 1
    head_dim: int = 16
    n_layers: int = 4
    attn_period: int = 4
    attn_offset: int = 2
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 8
    rms_eps: float = 1e-6
    scan_chunk: int = 32  # rows a grid step of mamba_scan
    scan_block: int = 128  # channels a grid step of mamba_scan
    param_dtype: object = jnp.bfloat16


def layer_kinds(n_layers: int, period: int, offset: int):
    """Layer ``i`` is attention where ``i % period == offset``, else Mamba."""
    return tuple(ATTENTION if i % period == offset else MAMBA for i in range(n_layers))


def _norm(x, g, eps):
    """RMS norm in fp32 with a gain; fp32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g.astype(jnp.float32)


class Jamba(Model):
    """The ``jamba`` stack; its default recipe is a tiny preset for the CPU
    tests (one attention layer in four, at index 2)."""

    name = "jamba_lm"
    is_lm = True
    supports_decode = True

    def __init__(self, recipe: JambaRecipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        if r.n_heads % r.n_kv_heads:
            raise ValueError(f"{r.n_heads} query heads over {r.n_kv_heads} K/V heads")
        self.kinds = layer_kinds(r.n_layers, r.attn_period, r.attn_offset)
        self.attn_layers = tuple(i for i, k in enumerate(self.kinds) if k == ATTENTION)
        self.mamba_layers = tuple(i for i, k in enumerate(self.kinds) if k == MAMBA)
        self.arch = Arch(r.n_layers, r.n_heads, r.d_model, int(r.input_shape[0]), r.compute_dtype)

    @classmethod
    def default_recipe(cls) -> JambaRecipe:
        return JambaRecipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(4096,),
            num_classes=256, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
        )

    @property
    def d_inner(self) -> int:
        return self.recipe.expand * self.recipe.d_model

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, INIT_STD) matrices, convolution taps and bias cast to the
        parameter dtype, gains ones; Mamba's ``A_log = log(1 .. N)`` a channel,
        ``D`` ones and ``b_dt`` the inverse softplus of ``dt`` drawn
        log-uniform in ``DT_INIT`` (floored at 1e-4). Keys: ``split(key, 1 + layers)``
        = embedding, then one a layer, itself split in ten: an attention layer
        w_q w_k w_v w_o, a Mamba layer w_in conv_w conv_b w_x w_dt b_dt w_out
        (the first seven), the SwiGLU's w_g w_u w_d (the last three)."""
        r = self.recipe
        d, V, dt, E = r.d_model, r.num_classes, r.param_dtype, self.d_inner
        N, R = r.d_state, r.dt_rank

        def w(k, *shape):
            return (INIT_STD * jax.random.normal(k, shape)).astype(dt)

        ks = jax.random.split(key, 1 + r.n_layers)
        params = {"tok_emb": w(ks[0], V, d), "norm_f": jnp.ones((d,), dt), "layers": []}
        for kind, kl in zip(self.kinds, ks[1:]):
            k = jax.random.split(kl, 10)
            if kind == ATTENTION:
                kv = r.n_kv_heads * r.head_dim
                mixer = {"w_q": w(k[0], d, r.n_heads * r.head_dim), "w_k": w(k[1], d, kv),
                         "w_v": w(k[2], d, kv), "w_o": w(k[3], r.n_heads * r.head_dim, d)}
            else:
                u = jax.random.uniform(k[5], (E,))
                lo, hi = np.log(DT_INIT[0]), np.log(DT_INIT[1])
                step = jnp.maximum(jnp.exp(u * (hi - lo) + lo), 1e-4)
                mixer = {"w_in": w(k[0], d, 2 * E), "conv_w": w(k[1], r.d_conv, E), "conv_b": w(k[2], E),
                         "w_x": w(k[3], E, R + 2 * N), "w_dt": w(k[4], R, E),
                         "b_dt": (step + jnp.log(-jnp.expm1(-step))).astype(dt),
                         "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, N + 1, dtype=jnp.float32)), (E, N)).astype(dt),
                         "D": jnp.ones((E,), dt), "dt_norm": jnp.ones((R,), dt), "b_norm": jnp.ones((N,), dt),
                         "c_norm": jnp.ones((N,), dt), "w_out": w(k[6], E, d)}
            params["layers"].append({
                "mixer": mixer, "norm_1": jnp.ones((d,), dt), "norm_2": jnp.ones((d,), dt),
                "mlp": {"w_g": w(k[7], d, r.d_ff), "w_u": w(k[8], d, r.d_ff), "w_d": w(k[9], r.d_ff, d)},
            })
        return params, {}

    # -- the parts both programs share -----------------------------------
    def _dot(self, h, w):
        dt = self.recipe.compute_dtype
        return jnp.dot(h.astype(dt), w.astype(dt), preferred_element_type=jnp.float32)

    def _mlp(self, p, h):
        dt = self.recipe.compute_dtype
        mid = jax.nn.silu(self._dot(h, p["w_g"])) * self._dot(h, p["w_u"])
        return self._dot(mid.astype(dt), p["w_d"])

    def _logits(self, params, x):
        r = self.recipe
        dt = r.compute_dtype
        h = _norm(x, params["norm_f"], r.rms_eps).astype(dt)
        return jax.lax.dot_general(h, params["tok_emb"].astype(dt), (((h.ndim - 1,), (1,)), ((), ())),
                                   preferred_element_type=jnp.float32)

    def _qkv(self, p, h):
        """``h [..., d]`` -> ``q [..., H, D]``, ``k, v [..., G, D]`` in the
        compute dtype."""
        r = self.recipe
        split = lambda y, n: y.astype(r.compute_dtype).reshape(*y.shape[:-1], n, r.head_dim)  # noqa: E731
        return (split(self._dot(h, p["w_q"]), r.n_heads), split(self._dot(h, p["w_k"]), r.n_kv_heads),
                split(self._dot(h, p["w_v"]), r.n_kv_heads))

    def _selective(self, p, x):
        """``x [..., E]`` after the convolution (compute dtype) -> ``dt [...,
        E]`` fp32 (after the softplus), ``B, C [..., N]`` fp32, each of the
        three RMS-normed with its gain first."""
        r = self.recipe
        R, N = r.dt_rank, r.d_state
        dbc = self._dot(x, p["w_x"])
        dt_r = _norm(dbc[..., :R], p["dt_norm"], r.rms_eps)
        B = _norm(dbc[..., R:R + N], p["b_norm"], r.rms_eps)
        C = _norm(dbc[..., R + N:], p["c_norm"], r.rms_eps)
        dt = jax.nn.softplus(self._dot(dt_r, p["w_dt"]) + p["b_dt"].astype(jnp.float32))
        return dt, B, C

    def _decay(self, p):
        """``A [N, E]`` fp32: ``-exp(A_log)``, states on the first axis."""
        return -jnp.exp(p["A_log"].astype(jnp.float32)).T

    def _conv(self, p, rows):
        """``rows [..., K, E]``: the window ending at the position, oldest
        first -> ``silu(sum_k w_k rows_k + b)`` fp32."""
        with jax.named_scope("mamba_conv"):
            s = jnp.einsum("...ke,ke->...e", rows.astype(jnp.float32), p["conv_w"].astype(jnp.float32))
            return jax.nn.silu(s + p["conv_b"].astype(jnp.float32))

    # -- the prompt's forms ------------------------------------------------
    def _attn_prompt(self, p, h):
        """One prompt's attention layer: ``h [T, d]`` -> (output ``[T, d]``,
        ``k``, ``v`` ``[T, G D]``)."""
        r = self.recipe
        T = h.shape[0]
        q, k, v = self._qkv(p, h)
        o = flash_attention(q[None], k[None], v[None], causal=True, scale=r.head_dim ** -0.5)[0]
        return self._dot(o.reshape(T, -1), p["w_o"]), k.reshape(T, -1), v.reshape(T, -1)

    def _mamba_prompt(self, p, h, n_real, scan=mamba_scan):
        """One prompt's Mamba layer: ``h [T, d]`` -> (output ``[T, d]``, the
        last ``K - 1`` REAL pre-convolution rows ``[K - 1, E]`` (noughts
        before position 0), the state ``[N, E]`` after row ``n_real - 1``)."""
        r = self.recipe
        T, E, K = h.shape[0], self.d_inner, r.d_conv
        xz = self._dot(h, p["w_in"]).astype(r.compute_dtype)
        x_pre, z = xz[:, :E], xz[:, E:]
        padded = jnp.concatenate([jnp.zeros((K - 1, E), x_pre.dtype), x_pre])
        with jax.named_scope("mamba_conv"):
            s = sum(padded[j:j + T].astype(jnp.float32) * p["conv_w"][j].astype(jnp.float32) for j in range(K))
            x = jax.nn.silu(s + p["conv_b"].astype(jnp.float32)).astype(r.compute_dtype)
        dt, B, C = self._selective(p, x)
        with jax.named_scope("mamba_scan"):
            y, state = scan(x, dt, B, C, z, self._decay(p), p["D"], n_real,
                            chunk=min(r.scan_chunk, T), block=r.scan_block)
        window = jax.lax.dynamic_slice_in_dim(padded, n_real, K - 1)  # rows n_real - K + 1 .. n_real - 1
        return self._dot(y, p["w_out"]), window, state

    def _prompt(self, params, tokens, n_real, scan=mamba_scan):
        """``tokens [T]`` -> (``x [T, d]`` after the last layer, the attention
        layers' ``(k, v)``, the Mamba layers' ``(window, state)``)."""
        r = self.recipe
        x = params["tok_emb"][tokens.astype(jnp.int32)].astype(jnp.float32)
        paged, held = [], []
        for kind, p in zip(self.kinds, params["layers"]):
            h = _norm(x, p["norm_1"], r.rms_eps)
            if kind == ATTENTION:
                a, *kv = self._attn_prompt(p["mixer"], h)
                paged.append(kv)
            else:
                a, *state = self._mamba_prompt(p["mixer"], h, n_real, scan)
                held.append(state)
            x = x + a
            x = x + self._mlp(p["mlp"], _norm(x, p["norm_2"], r.rms_eps))
        return x, paged, held

    # -- contract surface ---------------------------------------------------
    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T]`` (``T`` whole chunks of the scan) -> logits
        ``[B, T, V]`` fp32: the prompt's forms, a row of the batch at a time."""
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
        """Three kinds of state. PAGES over the attention layers alone:
        ``[page_size, G D]`` K or V rows. A SLOT (``slots``: each ``[layers,
        max_seqs, ...]``, under its name in the second pool's pytree beside
        ``"v"``) a Mamba layer: ``conv``, the last ``K - 1`` pre-convolution
        rows ``[K - 1, E]`` in the compute dtype, and ``ssm``, the state ``[N,
        E]`` fp32. Donated: both programs update every pool in place."""
        r = self.recipe
        page = (page_size, r.n_kv_heads * r.head_dim)
        n_mamba = len(self.mamba_layers)
        return {
            "kind": "kv", "k_page": page, "v_page": page, "dtype": r.compute_dtype, "donate": True,
            "paged_layers": len(self.attn_layers),
            "slots": {
                "conv": {"layers": n_mamba, "row": (r.d_conv - 1, self.d_inner), "dtype": r.compute_dtype},
                "ssm": {"layers": n_mamba, "row": (r.d_state, self.d_inner), "dtype": jnp.float32},
            },
        }

    def cache_rows(self, seq_lens) -> dict:
        """Host side: what the query at each of ``seq_lens`` (numpy) reads:
        ``mqa_rows``, the K/V rows of every attention layer (its context and
        itself), and ``ssm_state_bytes``, the recurrent state a decode step
        reads and writes over the Mamba layers (fp32, whatever the length)."""
        r = self.recipe
        t = np.asarray(seq_lens, np.int64)
        state = 2 * len(self.mamba_layers) * r.d_state * self.d_inner * 4
        return {"mqa_rows": len(self.attn_layers) * (t + 1), "ssm_state_bytes": np.full(t.shape, state, np.int64)}

    def decode_prefill(self, params, tokens, pages, k_pool, pools, slot, n_real, *,
                       page_size: int):
        """Cache one padded prompt (``tokens [T_b]``, ``pages [T_b /
        page_size]``, the scratch index for the padding tail) for ``slot``,
        ``n_real`` of its positions real: the prompt's forms minus the head;
        K and V of every position to the slot's pages (those past ``n_real``
        are never read: a later step overwrites them before its position
        makes them visible), each Mamba layer's last ``K - 1`` real
        pre-convolution rows and its state at ``n_real`` to the slot. Every
        pool written once."""
        _, paged, held = self._prompt(params, tokens, n_real)
        n = tokens.shape[0] // page_size
        by_pages = lambda rows: jnp.stack(rows).reshape(len(rows), n, page_size, -1)  # noqa: E731
        k = by_pages([c[0] for c in paged])
        v = by_pages([c[1] for c in paged])
        at = (0, slot, 0, 0)
        return k_pool.at[:, pages].set(k.astype(k_pool.dtype)), {
            "v": pools["v"].at[:, pages].set(v.astype(k_pool.dtype)),
            "conv": jax.lax.dynamic_update_slice(
                pools["conv"], jnp.stack([w for w, _ in held])[:, None].astype(pools["conv"].dtype), at),
            "ssm": jax.lax.dynamic_update_slice(pools["ssm"], jnp.stack([s for _, s in held])[:, None], at),
        }

    def decode_step(self, params, k_pool, pools, page_tables, seq_lens, last_tokens, active,
                    temperature, key, *, page_size: int):
        """One continuous-batching iteration over ALL slots: slot ``s`` embeds
        ``last_tokens[s]`` at position ``t = seq_lens[s]``. An attention layer
        attends over every cached position of the slot's pages and its own row
        (``sparse_decode`` with all pages chosen); its new K and V rows go to
        (page ``page_tables[s, t // page_size]``, offset ``t % page_size``) in
        ONE write after the last layer, inactive slots' to the scratch page. A
        Mamba layer convolves the slot's window and its own row and steps the
        slot's state (``mamba_step``), both from nought where ``t == 0``.
        Greedy where ``temperature[s] == 0``, else categorical under ``key``.
        -> ``(next_tokens [S], logits [S, V] fp32, k_pool, pools)``."""
        r = self.recipe
        S, M = page_tables.shape
        G, D, dt = r.n_kv_heads, r.head_dim, r.compute_dtype
        E = self.d_inner
        fresh = seq_lens == 0
        v_pool, conv, ssm = pools["v"], pools["conv"], pools["ssm"]
        # every page of the slot, in order; the kernel reads those below the length
        tables = jnp.broadcast_to(page_tables[:, None, :], (S, G, M))
        blocks = jnp.broadcast_to(jnp.arange(M, dtype=jnp.int32), (S, G, M))
        counts = jnp.broadcast_to(jnp.minimum(-(-seq_lens // page_size), M)[:, None], (S, G))
        x = params["tok_emb"][last_tokens.astype(jnp.int32)].astype(jnp.float32)
        ks, vs, windows, states = [], [], [], []
        for kind, p in zip(self.kinds, params["layers"]):
            h = _norm(x, p["norm_1"], r.rms_eps)
            mx = p["mixer"]
            if kind == ATTENTION:
                q, k, v = self._qkv(mx, h)
                with jax.named_scope("mqa_decode"):
                    o = sparse_decode(q.reshape(S, G, r.n_heads // G, D), k, v, k_pool, v_pool, tables, blocks,
                                      counts, seq_lens, layer=len(ks), scale=D ** -0.5)
                a = self._dot(o.reshape(S, -1), mx["w_o"])
                ks.append(k.reshape(S, G * D))
                vs.append(v.reshape(S, G * D))
            else:
                mi = len(windows)
                xz = self._dot(h, mx["w_in"]).astype(dt)
                x_new, z = xz[:, :E], xz[:, E:]
                window = jnp.where(fresh[:, None, None], 0, conv[mi])  # [S, K - 1, E]
                rows = jnp.concatenate([window, x_new[:, None].astype(window.dtype)], 1)
                xc = self._conv(mx, rows).astype(dt)
                d_t, B, C = self._selective(mx, xc)
                with jax.named_scope("mamba_step"):
                    y, s = mamba_step(xc, d_t, B, C, z, self._decay(mx), mx["D"], ssm[mi], fresh)
                a = self._dot(y, mx["w_out"])
                windows.append(rows[:, 1:])
                states.append(s)
            x = x + a
            x = x + self._mlp(p["mlp"], _norm(x, p["norm_2"], r.rms_eps))
        write_page = jnp.where(
            active, page_tables[jnp.arange(S), jnp.clip(seq_lens // page_size, 0, M - 1)], k_pool.shape[1] - 1)
        k_pool, v_pool = sparse_cache_write(k_pool, v_pool, jnp.stack(ks), jnp.stack(vs), write_page, seq_lens)
        logits = self._logits(params, x)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)[:, None]).astype(jnp.int32)
        return (jnp.where(temperature > 0, sampled, greedy), logits, k_pool,
                {"v": v_pool, "conv": jnp.stack(windows), "ssm": jnp.stack(states)})


class Jamba2_3B_Stage2(Jamba):
    """AI21 Jamba2-3B (``model_type`` jamba;
    huggingface.co/ai21labs/AI21-Jamba2-3B config.json) at its published
    widths, cut to ONE pipeline stage of this deployment: two stages of 14
    layers, each layer whole on its chip. Held here: the first stage, the
    published layers 0-13 (one whole period of 14: 13 Mamba-1 layers and the
    attention layer at index 7, the published order and ratio), with the
    embedding and the tied head so that the stage serves alone; the whole
    vocabulary of 65,536, every width: hidden 2,560, 20 query heads over one
    K/V head of 128, SwiGLU 8,192, ``d_inner`` 5,120, 16 states, a
    convolution of 4 taps, ``dt`` of rank 160. 1,598,556,096 parameters,
    3.20 GB in bfloat16. The Mamba initialisation is stated under
    ``assumed`` in ``benchmark/configs/jamba2-3b-decode.json``. Served, not
    trained, here: 16 bytes a parameter of training state are 25.6 GB at one
    period."""

    name = "jamba2_3b_stage2"

    @classmethod
    def default_recipe(cls) -> JambaRecipe:
        return JambaRecipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(262144,),
            num_classes=65536, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
            d_model=2560, d_ff=8192, n_heads=20, n_kv_heads=1, head_dim=128, n_layers=14,
            attn_period=14, attn_offset=7, d_state=16, d_conv=4, expand=2, dt_rank=160, rms_eps=1e-6,
            scan_chunk=256, scan_block=512,
        )
