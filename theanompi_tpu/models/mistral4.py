"""Latent attention beside a routed mixture of experts: the ``mistral4`` block
(Mistral-Small-4), under the zoo ``Model`` contract and with the incremental
decode surface of ``tmpi serve --decode``.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). Every layer is

    x += attn(norm_1(x));  x += moe(norm_2(x))

with RMS norms (a gain, no bias), after the last layer ``norm_f`` and an
untied head; the embedding is not scaled.

- ``attn``: latent attention (MLA). Queries through a rank ``q_lora``
  bottleneck with its norm; keys and values through ONE normed latent row
  ``c_kv`` (``kv_lora``) and ONE rotated key row ``k_pe`` (``qk_rope``) a
  position, shared by all heads. Heads are ``qk_nope + qk_rope`` wide for
  scores and ``v_head`` wide for values. Rotary positions are yarn-scaled,
  pairs ``(2j, 2j + 1)`` (:func:`yarn_frequencies`); the softmax scale is
  ``(qk_nope + qk_rope)^-0.5 m^2`` (:func:`softmax_scale`); the query at
  position ``p`` is also multiplied by ``1 + beta ln(1 + floor(p /
  original context))``.
- ``moe``: a shared SwiGLU expert beside top-k routing without dropped
  tokens (softmax over ALL router logits, the chosen scores divided by their
  sum) over the share of the experts held here
  (:func:`theanompi_tpu.ops.moe.routed_experts`).

The same layer runs in two FORMS (one set of weights, one mathematics):

- **expanded** (``apply``, ``decode_prefill``): per-head K and V rebuilt from
  the latent, causal attention through the flash kernel; prefill also writes
  every position's latent and rotated key to its pages;
- **absorbed** (``decode_step``): the query carried into the latent space,
  ``q_lat = q_nope W_uk``, attention over the cached latent rows themselves
  (:func:`theanompi_tpu.ops.pallas_mla.mla_decode`), ``o = o_lat W_uv``.

The cache is of kind ``latent`` (:meth:`Mistral4LM.cache_spec`): a position
costs ``2 (kv_lora + qk_rope)`` bytes a layer whatever the number of heads.
Both decode programs write the pools once, after their last read (prefill
whole pages, the decode step the new rows through ``mla_cache_write``), and
the engine donates the pools to them: no pool is copied.

Leaves are bfloat16 as the checkpoint is published and matmuls take them as
they are, on bfloat16 activations with fp32 accumulation; the residual stream,
the norms, the router (its input, matmul and softmax) and the attention
softmaxes are fp32. The router's input is NOT rounded to bfloat16: with random
weights a token whose fourth and fifth scores lie closer than that rounding
picks another expert than the float32 reference, and an expert's output here
is as large as the residual it joins (PERF.md section 2 has the readings).
:class:`MistralSmall4_EP8` is the model cut to one chip of a stated
deployment (its docstring).
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.transformer import _rms, next_token_loss, softmax_nll
from theanompi_tpu.ops.moe import route_topk, routed_experts
from theanompi_tpu.ops.pallas_attention import flash_attention
from theanompi_tpu.ops.pallas_mla import mla_cache_write, mla_decode


@dataclasses.dataclass
class Mistral4Recipe(Recipe):
    """``input_shape`` is ``(longest context served,)`` and ``num_classes``
    the vocabulary rows held (as the other LM recipes)."""

    d_model: int = 64
    n_layers: int = 2
    n_heads: int = 4
    q_lora: int = 32
    kv_lora: int = 32
    qk_nope: int = 16
    qk_rope: int = 16
    v_head: int = 32
    d_expert: int = 32  # width of one expert, routed or shared
    n_experts: int = 16  # the router's width: ALL experts
    experts_per_token: int = 4
    experts_held: int = 16  # experts first_expert .. first_expert + held live here
    first_expert: int = 0
    route_scale: float = 1.0
    rope_theta: float = 10000.0
    rope_factor: float = 128.0
    rope_original_context: int = 24  # yarn's original_max_position_embeddings
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    query_scale_beta: float = 0.1  # llama_4_scaling_beta
    rms_eps: float = 1e-6
    param_dtype: object = jnp.bfloat16
    moe_tile: int = 16  # rows of a grouped-product tile in prefill and apply
    moe_tile_decode: int = 16  # and in the decode step (a row or two an expert)


class Arch(NamedTuple):
    """What ``DecodeEngine`` and the benchmark's driver read of a decode model."""

    n_layers: int
    n_heads: int
    d_model: int
    max_len: int
    dtype: object


def yarn_frequencies(r: Mistral4Recipe):
    """-> (``[qk_rope / 2]`` rotary frequencies in fp32, ``low``, ``high``).
    ``t_j = theta^(-2j/D)``; dimensions below ``low`` keep ``t_j``, above
    ``high`` take ``t_j / factor``, a linear ramp between."""
    D, two_pi = r.qk_rope, 2 * math.pi

    def dim_of(rotations):
        return D * math.log(r.rope_original_context / (rotations * two_pi)) / (2 * math.log(r.rope_theta))

    low = max(math.floor(dim_of(r.rope_beta_fast)), 0)
    high = min(math.ceil(dim_of(r.rope_beta_slow)), D - 1)
    j = jnp.arange(D // 2, dtype=jnp.float32)
    t = r.rope_theta ** (-2.0 * j / D)
    ramp = jnp.clip((j - low) / max(high - low, 1e-3), 0.0, 1.0)
    return t * (1.0 - ramp) + t / r.rope_factor * ramp, low, high


def _mscale(factor: float, m: float) -> float:
    return 0.1 * m * math.log(factor) + 1.0 if factor > 1 else 1.0


def softmax_scale(r: Mistral4Recipe) -> float:
    """``(qk_nope + qk_rope)^-0.5 m^2`` with ``m = 0.1 mscale_all_dim
    ln(factor) + 1``."""
    return (r.qk_nope + r.qk_rope) ** -0.5 * _mscale(r.rope_factor, r.rope_mscale_all_dim) ** 2


def _rotate(x, positions, r: Mistral4Recipe):
    """``[..., T, (H,) D]`` rotated by ``positions [..., T]``, pairs ``(2j,
    2j + 1)``; angles in fp32, positions as they are (nothing is clipped)."""
    freq, _, _ = yarn_frequencies(r)
    ang = positions.astype(jnp.float32)[..., None] * freq
    amp = _mscale(r.rope_factor, r.rope_mscale) / _mscale(r.rope_factor, r.rope_mscale_all_dim)
    cos, sin = jnp.cos(ang) * amp, jnp.sin(ang) * amp
    if x.ndim == ang.ndim + 1:  # a heads axis between positions and D
        cos, sin = cos[..., None, :], sin[..., None, :]
    xf = x.astype(jnp.float32).reshape(*x.shape[:-1], -1, 2)
    a, b = xf[..., 0], xf[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape).astype(x.dtype)


class Mistral4LM(Model):
    """The ``mistral4`` block; its default recipe is a tiny preset for the
    CPU tests."""

    name = "mistral4_lm"
    is_lm = True
    supports_decode = True

    def __init__(self, recipe: Mistral4Recipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        if not 0 <= r.first_expert <= r.n_experts - r.experts_held:
            raise ValueError(
                f"experts {r.first_expert}..{r.first_expert + r.experts_held} "
                f"are not a share of {r.n_experts}")
        if r.v_head != r.qk_nope + r.qk_rope:
            raise ValueError(
                f"v_head {r.v_head} != qk_nope + qk_rope {r.qk_nope + r.qk_rope}: the "
                "flash kernel of the expanded form has one head width")
        self.arch = Arch(r.n_layers, r.n_heads, r.d_model, int(r.input_shape[0]), r.compute_dtype)

    @classmethod
    def default_recipe(cls) -> Mistral4Recipe:
        return Mistral4Recipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(64,),
            num_classes=64, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
        )

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, 0.02) matrices cast to the parameter dtype, gains ones.
        Keys: ``split(key, 2 + L)`` = embedding, head, then one per layer,
        itself split in twelve: w_dq w_uq w_dkv w_ukv w_o, router, the shared
        expert's w1 w3 w2, the held experts' w1 w3 w2."""
        r = self.recipe
        d, H, V, G, fe, dt = r.d_model, r.n_heads, r.num_classes, r.experts_held, r.d_expert, r.param_dtype

        def w(k, *shape):
            return (0.02 * jax.random.normal(k, shape)).astype(dt)

        def swiglu(k1, k3, k2, *lead):
            return {"w1": w(k1, *lead, d, fe), "w3": w(k3, *lead, d, fe), "w2": w(k2, *lead, fe, d)}

        ks = jax.random.split(key, 2 + r.n_layers)
        params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
                  "norm_f": jnp.ones((d,), dt), "layers": []}
        for kl in ks[2:]:
            k = jax.random.split(kl, 12)
            params["layers"].append({
                "attn": {"w_dq": w(k[0], d, r.q_lora), "q_norm": jnp.ones((r.q_lora,), dt),
                         "w_uq": w(k[1], r.q_lora, H, r.qk_nope + r.qk_rope),
                         "w_dkv": w(k[2], d, r.kv_lora + r.qk_rope),
                         "kv_norm": jnp.ones((r.kv_lora,), dt),
                         "w_ukv": w(k[3], r.kv_lora, H, r.qk_nope + r.v_head),
                         "w_o": w(k[4], H, r.v_head, d)},
                "norm_1": jnp.ones((d,), dt), "norm_2": jnp.ones((d,), dt),
                "ffn": {"router": w(k[5], d, r.n_experts),
                        "shared": swiglu(k[6], k[7], k[8]),
                        "experts": swiglu(k[9], k[10], k[11], G)},
            })
        return params, {}

    # -- the layer's parts ------------------------------------------------
    def _latents(self, p, h, positions):
        """``h [..., T, d]`` at ``positions [..., T]`` -> the scaled queries'
        two parts ``[..., T, H, nope]`` / ``[..., T, H, rope]`` (rotated), the
        normed latent ``[..., T, kv_lora]`` and the rotated shared key
        ``[..., T, rope]``: what both forms start from, the last two what
        the cache keeps."""
        r = self.recipe
        with jax.named_scope("mla_latents"):
            dt = r.compute_dtype
            h = h.astype(dt)  # the fp32 norm's rounding, for the matmuls
            c_q = _rms(h @ p["w_dq"].astype(dt), p["q_norm"], r.rms_eps)
            q = jnp.einsum("...r,rhk->...hk", c_q, p["w_uq"].astype(dt))
            kv = h @ p["w_dkv"].astype(dt)
            c_kv = _rms(kv[..., :r.kv_lora], p["kv_norm"], r.rms_eps)
            k_pe = _rotate(kv[..., r.kv_lora:], positions, r)
            # the query's own scale by position, applied in fp32 (the softmax
            # scale with its m^2 goes onto the fp32 scores inside the kernels)
            by_pos = 1.0 + r.query_scale_beta * jnp.log1p(
                jnp.floor(positions.astype(jnp.float32) / r.rope_original_context))
            q = (q.astype(jnp.float32) * by_pos[..., None, None]).astype(dt)
            return q[..., :r.qk_nope], _rotate(q[..., r.qk_nope:], positions, r), c_kv, k_pe

    def _attn_expanded(self, p, h, positions):
        """-> (attention output ``[B, T, d]``, ``c_kv``, ``k_pe``): K and V of
        every head rebuilt from the latent, causal flash attention."""
        r = self.recipe
        q_nope, q_rope, c_kv, k_pe = self._latents(p, h, positions)
        dt = c_kv.dtype
        with jax.named_scope("mla_prefill"):
            kv = jnp.einsum("btr,rhk->bthk", c_kv, p["w_ukv"].astype(dt))
            k = jnp.concatenate(
                [kv[..., :r.qk_nope],
                 jnp.broadcast_to(k_pe[:, :, None, :], (*kv.shape[:3], r.qk_rope))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = flash_attention(q, k, kv[..., r.qk_nope:], causal=True, scale=softmax_scale(r))
            return jnp.einsum("bthv,hvd->btd", o, p["w_o"].astype(dt),
                              preferred_element_type=jnp.float32), c_kv, k_pe

    def _attn_absorbed(self, p, h, positions, c_pool, r_pool, tables, layer, attend=mla_decode):
        """One new position a slot (``h [S, d]``) over the slot's cached
        latent rows -> (attention output ``[S, d]``, ``c_kv``, ``k_pe``)."""
        r = self.recipe
        q_nope, q_rope, c_kv, k_pe = self._latents(p, h, positions)
        dt = c_kv.dtype
        w_ukv = p["w_ukv"].astype(dt)
        with jax.named_scope("mla_decode"):
            q_lat = jnp.einsum("shn,rhn->shr", q_nope, w_ukv[..., :r.qk_nope])
            o_lat = attend(q_lat, q_rope, c_kv, k_pe, c_pool, r_pool, tables, positions,
                           layer=layer, scale=softmax_scale(r))
            o = jnp.einsum("shr,rhv->shv", o_lat, w_ukv[..., r.qk_nope:])
            return jnp.einsum("shv,hvd->sd", o, p["w_o"].astype(dt),
                              preferred_element_type=jnp.float32), c_kv, k_pe

    def _moe(self, p, h, tile):
        """``h [..., d]`` in fp32 -> the shared expert plus this chip's share
        of the routed ones, fp32. The router reads ``h`` as it is; the experts
        its rounding to the compute dtype."""
        r = self.recipe
        with jax.named_scope("moe_routed"):
            dt = r.compute_dtype
            flat32 = h.reshape(-1, h.shape[-1])
            idx, wts = route_topk(flat32, p["router"], None, r.experts_per_token, r.route_scale,
                                  scoring="softmax")
            flat, ex, sh = flat32.astype(dt), p["experts"], p["shared"]
            y, _ = routed_experts(flat, idx, wts, ex["w1"].astype(dt), ex["w3"].astype(dt),
                                  ex["w2"].astype(dt), r.first_expert, r.n_experts, tm=tile)
            mid = jax.nn.silu(flat @ sh["w1"].astype(dt)) * (flat @ sh["w3"].astype(dt))
            shared = jnp.dot(mid, sh["w2"].astype(dt), preferred_element_type=jnp.float32)
            return (shared + y).reshape(h.shape)

    def _embed(self, params, tokens):
        return params["tok_emb"][tokens.astype(jnp.int32)].astype(jnp.float32)  # the residual stream

    def _logits(self, params, x):
        dt = self.recipe.compute_dtype
        return _rms(x, params["norm_f"], self.recipe.rms_eps).astype(dt) @ params["head"].astype(dt)

    def _expanded(self, params, tokens):
        """``tokens [B, T]`` -> (``x [B, T, d]`` after the last layer, every
        layer's ``c_kv``, every layer's ``k_pe``). The residual ``x`` and the
        norms are fp32."""
        r = self.recipe
        x = self._embed(params, tokens)
        positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]), tokens.shape)
        cs, ks = [], []
        for p in params["layers"]:
            a, c_kv, k_pe = self._attn_expanded(p["attn"], _rms(x, p["norm_1"], r.rms_eps), positions)
            x = x + a
            x = x + self._moe(p["ffn"], _rms(x, p["norm_2"], r.rms_eps), r.moe_tile)
            cs.append(c_kv)
            ks.append(k_pe)
        return x, cs, ks

    # -- contract surface ---------------------------------------------------
    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        del train, rng  # no dropout
        return self._logits(params, self._expanded(params, tokens)[0]), state

    def loss(self, logits, labels):
        # labels ARE the token window [B, T]; shifted targets in-model
        return next_token_loss(labels.astype(jnp.int32), None, softmax_nll(logits))

    def metrics(self, logits, labels) -> dict:
        return {}

    # -- incremental decode surface (serve/decode DecodeEngine) -------------
    def cache_spec(self, page_size: int) -> dict:
        """The two pools of a latent cache: a page of ``k_pool`` holds
        ``[page, kv_lora]`` normed latent rows, a page of ``v_pool`` the
        rotated shared key rows TRANSPOSED, ``[qk_rope, page]`` (positions
        minor: ``ops/pallas_mla.py`` says why). Donated: both programs
        update the pools in place."""
        r = self.recipe
        return {"kind": "latent", "k_page": (page_size, r.kv_lora), "v_page": (r.qk_rope, page_size),
                "dtype": r.compute_dtype, "donate": True}

    def decode_prefill(self, params, tokens, pages, k_pool, v_pool, *, page_size: int):
        """Cache one padded prompt (``tokens [T_b]``, ``pages [T_b /
        page_size]``, the scratch index for the padding tail): the expanded
        forward minus the head, every position's latent and rotated key to
        its pages, both pools written once."""
        _, cs, ks = self._expanded(params, tokens[None])
        L, n = len(cs), tokens.shape[0] // page_size
        c = jnp.stack(cs).reshape(L, n, page_size, -1)  # [L, 1, T, kv_lora] by pages
        kr = jnp.swapaxes(jnp.stack(ks).reshape(L, n, page_size, -1), 2, 3)
        return (k_pool.at[:, pages].set(c.astype(k_pool.dtype)),
                v_pool.at[:, pages].set(kr.astype(v_pool.dtype)))

    def decode_step(self, params, k_pool, v_pool, page_tables, seq_lens, last_tokens,
                    active, temperature, key, *, page_size: int, attend=mla_decode):
        """One continuous-batching iteration over ALL slots, absorbed: slot
        ``s`` embeds ``last_tokens[s]`` at position ``seq_lens[s]`` and
        attends over its ``seq_lens[s]`` cached rows and its own; the new
        rows of all layers go to (page ``page_tables[s, pos // page_size]``,
        offset ``pos % page_size``) in ONE write after the last layer
        (``mla_cache_write``), inactive slots' to the scratch page. Greedy where
        ``temperature[s] == 0``, else categorical under ``key``. ->
        ``(next_tokens [S], logits [S, V] fp32, k_pool, v_pool)``."""
        r = self.recipe
        S, M = page_tables.shape
        scratch = k_pool.shape[1] - 1
        x = self._embed(params, last_tokens)
        cs, ks = [], []
        for li, p in enumerate(params["layers"]):
            a, c_kv, k_pe = self._attn_absorbed(
                p["attn"], _rms(x, p["norm_1"], r.rms_eps), seq_lens, k_pool, v_pool,
                page_tables, li, attend)
            x = x + a
            x = x + self._moe(p["ffn"], _rms(x, p["norm_2"], r.rms_eps), r.moe_tile_decode)
            cs.append(c_kv)
            ks.append(k_pe)
        write_page = jnp.where(
            active, page_tables[jnp.arange(S), jnp.clip(seq_lens // page_size, 0, M - 1)], scratch)
        k_pool, v_pool = mla_cache_write(k_pool, v_pool, jnp.stack(cs), jnp.stack(ks), write_page, seq_lens)
        logits = self._logits(params, x).astype(jnp.float32)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)[:, None]).astype(jnp.int32)
        return jnp.where(temperature > 0, sampled, greedy), logits, k_pool, v_pool


class MistralSmall4_EP8(Mistral4LM):
    """Mistral-Small-4 (119B-A6.5B, ``model_type`` mistral4;
    huggingface.co/mistralai/Mistral-Small-4-119B-2603 config.json), the text
    decoder at its published widths, cut to ONE chip of this deployment: 8
    chips share each layer (experts spread over them, attention whole on
    every chip because a latent cache cannot be split by heads, the
    vocabulary in eight slices), further layers on further chips. Held here:
    16 of the 128 routed experts of every layer, 16,384 of the 131,072
    vocabulary rows, the shared expert and attention whole; depth 6 of 36.
    2,872,634,880 parameters, 5.75 GB in bfloat16. The router scores all
    128 experts and takes 4 a token; what the 112 absent experts would add
    is left out (``benchmark/configs/mistral-small-4-decode.json`` states
    the cut). Served, not trained, here: 16 bytes a parameter of training
    state do not fit one chip at the guide's floors."""

    name = "mistral_small_4_ep8"

    @classmethod
    def default_recipe(cls) -> Mistral4Recipe:
        return Mistral4Recipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(1048576,),
            num_classes=16384, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
            d_model=4096, n_layers=6, n_heads=32, q_lora=1024, kv_lora=256,
            qk_nope=64, qk_rope=64, v_head=128, d_expert=2048, n_experts=128,
            experts_per_token=4, experts_held=16, first_expert=0, route_scale=1.0,
            rope_theta=10000.0, rope_factor=128.0, rope_original_context=8192,
            rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0,
            rope_mscale_all_dim=1.0, query_scale_beta=0.1, rms_eps=1e-6,
            moe_tile=256, moe_tile_decode=32,
        )
