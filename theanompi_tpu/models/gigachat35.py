"""Gated delta-rule linear attention beside gated latent attention and routed
experts: the ``gigachat3_5`` stack (GigaChat3.5), under the zoo ``Model``
contract and with the incremental decode surface of ``tmpi serve --decode``.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). Every layer is ``pre_post``
normed, four norms a layer with a zero-centred gain ``2 sigma(w)``:

    x += norm_1p(mixer(norm_1(x)));  x += norm_2p(mlp(norm_2(x)))
    logits = norm_f(x) head                              (untied)

Published layer ``i`` (:attr:`GigaChat35Recipe.layer_ids` says which are
held) is FULL attention where ``i`` is in ``full_attention_layers`` and a
gated DeltaNet otherwise; its MLP is a dense SwiGLU where ``i <
first_k_dense`` and routed experts otherwise.

- gated DeltaNet (``Hk`` key heads, ``H`` value heads of ``D``; value head
  ``h`` reads key head ``h // (H / Hk)``):

      q, k, v = silu(causal_depthwise_conv_K(h [W_q | W_k | W_v]))
      q, k = l2norm(q) D^-1/2, l2norm(k)                     (a key head)
      beta = sigma(h W_b);  g = -exp(A_log) softplus(h W_a + dt_bias)
      o = delta rule (``ops/pallas_gdn.py``);  y = rms(o) (1 + w_o) 2 sigma(h W_z)
      out = y W_out

  A prompt runs ``gdn_chunk`` (one Pallas kernel a layer), a decode step
  ``gdn_step``.
- gated latent attention: the ``mistral4`` block's MLA (``q_lora`` and
  ``kv_lora`` ranks, heads of ``nope + rope`` for scores and ``v_head`` for
  values, one shared rotated key, yarn rotary on pairs ``(2j, 2j + 1)``,
  softmax scale ``(nope + rope)^-0.5 m^2``), without the query's scale by
  position, its output times ``sigma(h W_g)`` a head before ``W_o``. A prompt
  runs the EXPANDED form (per-head K and V rebuilt, ``flash_attention`` with
  V of its own width), a decode step the ABSORBED form over the cached latent
  rows (``mla_decode``).
- routed MLP: sigmoid scores over all ``n_experts``, the ``k`` best by score
  plus a selection bias, their scores normalised and times ``route_scale``,
  over this chip's share of the experts (``ops/moe.py routed_experts``),
  beside one shared expert with no gate. Every SwiGLU clamps its gate's
  pre-activation at ``swiglu_limit`` and its up projection to ``+-
  swiglu_limit``.

The cache is of three kinds in one manager (:meth:`GigaChat35.cache_spec`):
LATENT pages (a normed latent row and a rotated key row a position) over the
full-attention layers alone, and a SLOT each delta-rule layer: ``conv``, the
last ``K - 1`` pre-convolution rows, and ``state``, ``[H, D, D]`` fp32. Both
programs write every pool after their last read of it and take the pools
donated.

Leaves are bfloat16 (the selection bias fp32) and matmuls take them as they
are, on bfloat16 activations with fp32 accumulation; the residual stream, the
norms, the gates, the router, the convolution's sum, the delta rule's state
and the attention softmax are fp32. :class:`GigaChat35_EP32` is the model cut
to one chip of a stated deployment (its docstring). Served, not trained:
nothing here has a backward pass.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.mistral4 import Arch, _rotate, softmax_scale
from theanompi_tpu.models.transformer import next_token_loss, softmax_nll
from theanompi_tpu.ops.moe import route_topk, routed_experts
from theanompi_tpu.ops.pallas_attention import flash_attention
from theanompi_tpu.ops.pallas_gdn import gdn_chunk, gdn_step
from theanompi_tpu.ops.pallas_mla import mla_cache_write, mla_decode

FULL, LINEAR = "full", "linear"
INIT_STD = 0.02  # every matrix, the convolution's taps and the selection bias


@dataclasses.dataclass
class GigaChat35Recipe(Recipe):
    """``input_shape`` is ``(longest context served,)`` and ``num_classes``
    the vocabulary rows held (as the other LM recipes). The default sizes are
    a tiny preset for the CPU tests."""

    d_model: int = 64
    layer_ids: tuple = (0, 3, 4, 5, 6)  # the published layers held, in order
    full_attention_layers: tuple = (3, 7, 11, 15, 19, 23, 27, 31, 35, 39)
    first_k_dense: int = 3
    d_dense: int = 96
    # gated DeltaNet
    gdn_key_heads: int = 2
    gdn_value_heads: int = 4
    gdn_head_dim: int = 16
    gdn_conv: int = 4
    gdn_chunk: int = 16  # rows a chunk of gdn_chunk
    # gated latent attention
    n_heads: int = 4
    q_lora: int = 32
    kv_lora: int = 32
    qk_nope: int = 16
    qk_rope: int = 16
    v_head: int = 16
    rope_theta: float = 100000.0
    rope_factor: float = 8.0
    rope_original_context: int = 24
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 1.0
    # routed experts
    d_expert: int = 32
    n_experts: int = 32
    experts_per_token: int = 4
    experts_held: int = 8
    first_expert: int = 0
    route_scale: float = 2.5
    swiglu_limit: float = 10.0
    rms_eps: float = 1e-6
    param_dtype: object = jnp.bfloat16
    moe_tile: int = 16  # rows of a grouped-product tile in prefill and apply
    moe_tile_decode: int = 16  # and in the decode step


def _norm(x, w, eps):
    """RMS norm in fp32 with the zero-centred gain ``2 sigma(w)``; fp32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (2.0 * jax.nn.sigmoid(w.astype(jnp.float32)))


def _rms(x, gain, eps):
    """RMS norm in fp32 with the plain gain ``gain``; fp32 out."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain.astype(jnp.float32)


def _l2(x, eps=1e-6):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)


class GigaChat35(Model):
    """The ``gigachat3_5`` stack."""

    name = "gigachat35_lm"
    is_lm = True
    supports_decode = True

    def __init__(self, recipe: GigaChat35Recipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        if not 0 <= r.first_expert <= r.n_experts - r.experts_held:
            raise ValueError(f"experts {r.first_expert}..{r.first_expert + r.experts_held} "
                             f"are not a share of {r.n_experts}")
        self.kinds = tuple(FULL if i in r.full_attention_layers else LINEAR for i in r.layer_ids)
        self.dense = tuple(i < r.first_k_dense for i in r.layer_ids)
        self.full_layers = tuple(j for j, k in enumerate(self.kinds) if k == FULL)
        self.linear_layers = tuple(j for j, k in enumerate(self.kinds) if k == LINEAR)
        self.arch = Arch(len(r.layer_ids), r.n_heads, r.d_model, int(r.input_shape[0]), r.compute_dtype)

    @classmethod
    def default_recipe(cls) -> GigaChat35Recipe:
        return GigaChat35Recipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(4096,),
            num_classes=256, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
        )

    @property
    def conv_width(self) -> int:
        """Channels of the delta rule's convolution: q, k and v."""
        r = self.recipe
        return (2 * r.gdn_key_heads + r.gdn_value_heads) * r.gdn_head_dim

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, INIT_STD) matrices and convolution taps cast to the
        parameter dtype, the selection bias normal(0, INIT_STD) in fp32; the
        zero-centred gains (the layer norms', the delta rule's output norm's)
        noughts, the latent attention's two inner gains ones; ``A_log =
        log((h + 1) / 4)`` for value head ``h``, ``dt_bias`` ones. Keys: ``split(key,
        2 + layers)`` = embedding, head, then one a layer, itself split in
        sixteen: a delta-rule layer w_q w_k w_v w_z w_b w_a conv_w w_o (the
        first eight), a latent one w_dq w_uq w_dkv w_ukv w_o w_g (the first
        six); a dense MLP w1 w3 w2 (keys 8-10), a routed one router, bias,
        the shared expert's w1 w3 w2, the held experts' w1 w3 w2 (keys 8-15)."""
        r = self.recipe
        d, V, dt = r.d_model, r.num_classes, r.param_dtype
        Hk, H, D = r.gdn_key_heads, r.gdn_value_heads, r.gdn_head_dim

        def w(k, *shape):
            return (INIT_STD * jax.random.normal(k, shape)).astype(dt)

        def swiglu(k1, k3, k2, width, *lead):
            return {"w1": w(k1, *lead, d, width), "w3": w(k3, *lead, d, width), "w2": w(k2, *lead, width, d)}

        ks = jax.random.split(key, 2 + len(r.layer_ids))
        params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V), "norm_f": jnp.zeros((d,), dt), "layers": []}
        for kind, dense, kl in zip(self.kinds, self.dense, ks[2:]):
            k = jax.random.split(kl, 16)
            if kind == LINEAR:
                mixer = {"w_q": w(k[0], d, Hk * D), "w_k": w(k[1], d, Hk * D), "w_v": w(k[2], d, H * D),
                         "w_z": w(k[3], d, H * D), "w_b": w(k[4], d, H), "w_a": w(k[5], d, H),
                         "conv_w": w(k[6], r.gdn_conv, self.conv_width),
                         "A_log": jnp.log(jnp.arange(1, H + 1, dtype=jnp.float32) / 4.0).astype(dt),
                         "dt_bias": jnp.ones((H,), dt), "o_norm": jnp.zeros((D,), dt), "w_o": w(k[7], H * D, d)}
            else:
                mixer = {"w_dq": w(k[0], d, r.q_lora), "q_norm": jnp.ones((r.q_lora,), dt),
                         "w_uq": w(k[1], r.q_lora, r.n_heads, r.qk_nope + r.qk_rope),
                         "w_dkv": w(k[2], d, r.kv_lora + r.qk_rope), "kv_norm": jnp.ones((r.kv_lora,), dt),
                         "w_ukv": w(k[3], r.kv_lora, r.n_heads, r.qk_nope + r.v_head),
                         "w_o": w(k[4], r.n_heads, r.v_head, d), "w_g": w(k[5], d, r.n_heads * r.v_head)}
            if dense:
                mlp = swiglu(k[8], k[9], k[10], r.d_dense)
            else:
                mlp = {"router": w(k[8], d, r.n_experts),
                       "bias": INIT_STD * jax.random.normal(k[9], (r.n_experts,), jnp.float32),
                       "shared": swiglu(k[10], k[11], k[12], r.d_expert),
                       "experts": swiglu(k[13], k[14], k[15], r.d_expert, r.experts_held)}
            params["layers"].append({"mixer": mixer, "mlp": mlp, **{
                n: jnp.zeros((d,), dt) for n in ("norm_1", "norm_1p", "norm_2", "norm_2p")}})
        return params, {}

    # -- the parts both programs share -----------------------------------
    def _dot(self, h, w):
        dt = self.recipe.compute_dtype
        return jnp.dot(h.astype(dt), w.astype(dt), preferred_element_type=jnp.float32)

    def _swiglu(self, p, h):
        r = self.recipe
        gate = jnp.minimum(self._dot(h, p["w1"]), r.swiglu_limit)
        up = jnp.clip(self._dot(h, p["w3"]), -r.swiglu_limit, r.swiglu_limit)
        return self._dot(jax.nn.silu(gate) * up, p["w2"])

    def _moe(self, p, h, tile):
        """``h [..., d]`` fp32 -> the shared expert plus this chip's share of
        the routed ones, fp32. The router reads ``h`` as it is; the experts
        its rounding to the compute dtype."""
        r = self.recipe
        with jax.named_scope("moe_routed"):
            flat32 = h.reshape(-1, h.shape[-1])
            idx, wts = route_topk(flat32, p["router"], p["bias"], r.experts_per_token, r.route_scale,
                                  scoring="sigmoid")
            dt, ex = r.compute_dtype, p["experts"]
            y, _ = routed_experts(flat32.astype(dt), idx, wts, ex["w1"].astype(dt), ex["w3"].astype(dt),
                                  ex["w2"].astype(dt), r.first_expert, r.n_experts, tm=tile,
                                  limit=r.swiglu_limit)
            return (self._swiglu(p["shared"], flat32) + y).reshape(h.shape)

    def _mlp(self, p, h, dense, tile):
        return self._swiglu(p, h) if dense else self._moe(p, h, tile)

    def _embed(self, params, tokens):
        return params["tok_emb"][tokens.astype(jnp.int32)].astype(jnp.float32)  # the residual stream

    def _logits(self, params, x):
        return self._dot(_norm(x, params["norm_f"], self.recipe.rms_eps), params["head"])

    # -- gated latent attention ---------------------------------------------
    def _latents(self, p, h, positions):
        """``h [..., T, d]`` -> the queries' two parts ``[..., T, H, nope]`` /
        ``[..., T, H, rope]`` (rotated), the normed latent ``[..., T,
        kv_lora]``, the rotated shared key ``[..., T, rope]``, the output gate
        ``[..., T, H, v_head]`` fp32."""
        r = self.recipe
        with jax.named_scope("mla_latents"):
            dt = r.compute_dtype
            c_q = _rms(self._dot(h, p["w_dq"]), p["q_norm"], r.rms_eps).astype(dt)
            q = jnp.einsum("...r,rhk->...hk", c_q, p["w_uq"].astype(dt))
            kv = self._dot(h, p["w_dkv"])
            c_kv = _rms(kv[..., :r.kv_lora], p["kv_norm"], r.rms_eps).astype(dt)
            k_pe = _rotate(kv[..., r.kv_lora:].astype(dt), positions, r)
            gate = jax.nn.sigmoid(self._dot(h, p["w_g"])).reshape(*h.shape[:-1], r.n_heads, r.v_head)
            return q[..., :r.qk_nope], _rotate(q[..., r.qk_nope:], positions, r), c_kv, k_pe, gate

    def _out(self, p, o, gate):
        """The gated heads ``o [..., H, v]`` through ``W_o`` -> ``[..., d]`` fp32."""
        dt = self.recipe.compute_dtype
        return jnp.einsum("...hv,hvd->...d", (o.astype(jnp.float32) * gate).astype(dt), p["w_o"].astype(dt),
                          preferred_element_type=jnp.float32)

    def _mla_prompt(self, p, h):
        """One prompt's latent attention, expanded: ``h [T, d]`` -> (output
        ``[T, d]``, ``c_kv [T, kv_lora]``, ``k_pe [T, rope]``)."""
        r = self.recipe
        T = h.shape[0]
        q_nope, q_rope, c_kv, k_pe, gate = self._latents(p, h, jnp.arange(T))
        with jax.named_scope("mla_prefill"):
            kv = jnp.einsum("tr,rhk->thk", c_kv, p["w_ukv"].astype(c_kv.dtype))
            k = jnp.concatenate([kv[..., :r.qk_nope], jnp.broadcast_to(k_pe[:, None, :], (T, r.n_heads, r.qk_rope))], -1)
            q = jnp.concatenate([q_nope, q_rope], -1)
            o = flash_attention(q[None], k[None], kv[None, ..., r.qk_nope:], causal=True, scale=softmax_scale(r))[0]
            return self._out(p, o, gate), c_kv, k_pe

    def _mla_step(self, p, h, seq_lens, c_pool, r_pool, tables, layer):
        """One new position a slot (``h [S, d]``), absorbed, over the slot's
        cached latent rows -> (output ``[S, d]``, ``c_kv``, ``k_pe``)."""
        r = self.recipe
        q_nope, q_rope, c_kv, k_pe, gate = self._latents(p, h, seq_lens)
        w_ukv = p["w_ukv"].astype(c_kv.dtype)
        with jax.named_scope("mla_decode"):
            q_lat = jnp.einsum("shn,rhn->shr", q_nope, w_ukv[..., :r.qk_nope])
            o_lat = mla_decode(q_lat, q_rope, c_kv, k_pe, c_pool, r_pool, tables, seq_lens,
                               layer=layer, scale=softmax_scale(r))
            o = jnp.einsum("shr,rhv->shv", o_lat, w_ukv[..., r.qk_nope:])
            return self._out(p, o, gate), c_kv, k_pe

    # -- gated DeltaNet -----------------------------------------------------
    def _conv_out(self, p, rows):
        """``rows [..., K, C]``, the window ending at the position, oldest
        first -> ``silu(sum_k w_k rows_k)`` fp32."""
        with jax.named_scope("gdn_conv"):
            return jax.nn.silu(jnp.einsum("...kc,kc->...c", rows.astype(jnp.float32),
                                          p["conv_w"].astype(jnp.float32)))

    def _pre_conv(self, p, h):
        """``h [..., d]`` -> the convolution's input rows ``[..., C]`` (q, k,
        v side by side) in the compute dtype."""
        return jnp.concatenate([self._dot(h, p[n]) for n in ("w_q", "w_k", "w_v")], -1).astype(self.recipe.compute_dtype)

    def _heads(self, p, h, x):
        """``h [..., d]`` (the layer's input) and ``x [..., C]`` (after the
        convolution) -> q, k ``[..., Hk, D]`` (normed, q scaled; compute
        dtype), v ``[..., H, D]``, beta and g ``[..., H]`` fp32."""
        r = self.recipe
        Hk, H, D, dt = r.gdn_key_heads, r.gdn_value_heads, r.gdn_head_dim, r.compute_dtype
        lead = x.shape[:-1]
        q = (_l2(x[..., :Hk * D].reshape(*lead, Hk, D)) * D ** -0.5).astype(dt)
        k = _l2(x[..., Hk * D:2 * Hk * D].reshape(*lead, Hk, D)).astype(dt)
        v = x[..., 2 * Hk * D:].reshape(*lead, H, D).astype(dt)
        beta = jax.nn.sigmoid(self._dot(h, p["w_b"]))
        g = -jnp.exp(p["A_log"].astype(jnp.float32)) * jax.nn.softplus(
            self._dot(h, p["w_a"]) + p["dt_bias"].astype(jnp.float32))
        return q, k, v, beta, g

    def _gdn_out(self, p, h, o):
        """``o [..., H, D]`` fp32 -> ``rms(o) (1 + w) 2 sigma(h W_z)`` through ``W_out``."""
        r = self.recipe
        z = self._dot(h, p["w_z"]).reshape(o.shape)
        # at the layers' eps: linear_attn_o_norm_eps and rms_norm_eps are both 1e-6 as published
        y = _rms(o, 1.0 + p["o_norm"].astype(jnp.float32), r.rms_eps) * (2.0 * jax.nn.sigmoid(z))
        return self._dot(y.reshape(*o.shape[:-2], -1), p["w_o"])

    def _gdn_prompt(self, p, h, n_real, chunk=gdn_chunk):
        """One prompt's delta-rule layer: ``h [T, d]`` -> (output ``[T, d]``,
        the last ``K - 1`` REAL pre-convolution rows ``[K - 1, C]`` (noughts
        before position 0), the state ``[H, D, D]`` after row ``n_real -
        1``)."""
        r = self.recipe
        T, K = h.shape[0], r.gdn_conv
        pre = self._pre_conv(p, h)
        padded = jnp.concatenate([jnp.zeros((K - 1, pre.shape[-1]), pre.dtype), pre])
        with jax.named_scope("gdn_conv"):
            s = sum(padded[j:j + T].astype(jnp.float32) * p["conv_w"][j].astype(jnp.float32) for j in range(K))
            x = jax.nn.silu(s)
        q, k, v, beta, g = self._heads(p, h, x)
        with jax.named_scope("gdn_chunk"):
            o, state = chunk(q, k, v, beta, g, n_real, chunk=min(r.gdn_chunk, T))
        window = jax.lax.dynamic_slice_in_dim(padded, n_real, K - 1)  # rows n_real - K + 1 .. n_real - 1
        return self._gdn_out(p, h, o), window, state

    def _prompt(self, params, tokens, n_real, chunk=gdn_chunk):
        """``tokens [T]`` -> (``x [T, d]`` after the last layer, the latent
        layers' ``(c_kv, k_pe)``, the delta-rule layers' ``(window, state)``)."""
        r = self.recipe
        x = self._embed(params, tokens)
        latent, held = [], []
        for kind, dense, p in zip(self.kinds, self.dense, params["layers"]):
            h = _norm(x, p["norm_1"], r.rms_eps)
            if kind == FULL:
                a, *kept = self._mla_prompt(p["mixer"], h)
                latent.append(kept)
            else:
                a, *kept = self._gdn_prompt(p["mixer"], h, n_real, chunk)
                held.append(kept)
            x = x + _norm(a, p["norm_1p"], r.rms_eps)
            f = self._mlp(p["mlp"], _norm(x, p["norm_2"], r.rms_eps), dense, r.moe_tile)
            x = x + _norm(f, p["norm_2p"], r.rms_eps)
        return x, latent, held

    # -- contract surface ---------------------------------------------------
    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        """``tokens [B, T]`` (``T`` whole chunks) -> logits ``[B, T, V]``
        fp32: the prompt's forms, a row of the batch at a time."""
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
        """Three kinds of state. LATENT pages over the full-attention layers
        alone: ``[page_size, kv_lora]`` normed latent rows and ``[qk_rope,
        page_size]`` rotated key rows (``ops/pallas_mla.py`` says why
        transposed). A SLOT (``slots``: each ``[layers, max_seqs, ...]``, under
        its name in the second pool's pytree beside ``"v"``) a delta-rule
        layer: ``conv``, the last ``K - 1`` pre-convolution rows ``[K - 1, C]``
        in the compute dtype, and ``state``, ``[H, D, D]`` fp32. Donated: both
        programs update every pool in place."""
        r = self.recipe
        n = len(self.linear_layers)
        D = r.gdn_head_dim
        return {
            "kind": "latent", "k_page": (page_size, r.kv_lora), "v_page": (r.qk_rope, page_size),
            "dtype": r.compute_dtype, "donate": True, "paged_layers": len(self.full_layers),
            "slots": {
                "conv": {"layers": n, "row": (r.gdn_conv - 1, self.conv_width), "dtype": r.compute_dtype},
                "state": {"layers": n, "row": (r.gdn_value_heads, D, D), "dtype": jnp.float32},
            },
        }

    def cache_rows(self, seq_lens) -> dict:
        """Host side: what the query at each of ``seq_lens`` (numpy) reads:
        ``latent_rows``, the cached latent rows of every full-attention layer
        (its context and itself), and ``gdn_state_bytes``, the delta rule's
        state a decode step reads and writes over its layers (fp32, whatever
        the length)."""
        r = self.recipe
        t = np.asarray(seq_lens, np.int64)
        state = 2 * len(self.linear_layers) * r.gdn_value_heads * r.gdn_head_dim ** 2 * 4
        return {"latent_rows": len(self.full_layers) * (t + 1), "gdn_state_bytes": np.full(t.shape, state, np.int64)}

    def decode_prefill(self, params, tokens, pages, k_pool, pools, slot, n_real, *, page_size: int):
        """Cache one padded prompt (``tokens [T_b]``, ``pages [T_b /
        page_size]``, the scratch index for the padding tail) for ``slot``,
        ``n_real`` of its positions real: the prompt's forms minus the head;
        every position's latent and rotated key to the slot's pages (those
        past ``n_real`` are never read: a later step overwrites them before
        its position makes them visible), each delta-rule layer's last ``K -
        1`` real pre-convolution rows and its state at ``n_real`` to the
        slot. Every pool written once."""
        _, latent, held = self._prompt(params, tokens, n_real)
        L, n = len(latent), tokens.shape[0] // page_size
        c = jnp.stack([c for c, _ in latent]).reshape(L, n, page_size, -1)
        kr = jnp.swapaxes(jnp.stack([k for _, k in latent]).reshape(L, n, page_size, -1), 2, 3)
        at = (0, slot) + (0,) * (pools["state"].ndim - 2)
        return k_pool.at[:, pages].set(c.astype(k_pool.dtype)), {
            "v": pools["v"].at[:, pages].set(kr.astype(pools["v"].dtype)),
            "conv": jax.lax.dynamic_update_slice(
                pools["conv"], jnp.stack([w for w, _ in held])[:, None].astype(pools["conv"].dtype), at[:4]),
            "state": jax.lax.dynamic_update_slice(pools["state"], jnp.stack([s for _, s in held])[:, None], at),
        }

    def decode_step(self, params, k_pool, pools, page_tables, seq_lens, last_tokens, active,
                    temperature, key, *, page_size: int):
        """One continuous-batching iteration over ALL slots: slot ``s`` embeds
        ``last_tokens[s]`` at position ``t = seq_lens[s]``. A full-attention
        layer attends, absorbed, over the slot's ``t`` cached latent rows and
        its own (``mla_decode``); its new rows go to (page ``page_tables[s, t
        // page_size]``, offset ``t % page_size``) in ONE write after the last
        layer (``mla_cache_write``), inactive slots' to the scratch page. A
        delta-rule layer convolves the slot's window and its own row and steps
        the slot's state (``gdn_step``), both from nought where ``t == 0``.
        Greedy where ``temperature[s] == 0``, else categorical under ``key``.
        -> ``(next_tokens [S], logits [S, V] fp32, k_pool, pools)``."""
        r = self.recipe
        S, M = page_tables.shape
        fresh = seq_lens == 0
        r_pool, conv, state = pools["v"], pools["conv"], pools["state"]
        x = self._embed(params, last_tokens)
        cs, ks, windows = [], [], []
        for kind, dense, p in zip(self.kinds, self.dense, params["layers"]):
            h = _norm(x, p["norm_1"], r.rms_eps)
            mx = p["mixer"]
            if kind == FULL:
                a, c_kv, k_pe = self._mla_step(mx, h, seq_lens, k_pool, r_pool, page_tables, len(cs))
                cs.append(c_kv)
                ks.append(k_pe)
            else:
                li = len(windows)
                window = jnp.where(fresh[:, None, None], 0, conv[li])  # [S, K - 1, C]
                rows = jnp.concatenate([window, self._pre_conv(mx, h)[:, None].astype(window.dtype)], 1)
                q, k, v, beta, g = self._heads(mx, h, self._conv_out(mx, rows))
                with jax.named_scope("gdn_step"):
                    o, state = gdn_step(q, k, v, beta, g, state, seq_lens, layer=li)
                a = self._gdn_out(mx, h, o)
                windows.append(rows[:, 1:])
            x = x + _norm(a, p["norm_1p"], r.rms_eps)
            f = self._mlp(p["mlp"], _norm(x, p["norm_2"], r.rms_eps), dense, r.moe_tile_decode)
            x = x + _norm(f, p["norm_2p"], r.rms_eps)
        write_page = jnp.where(
            active, page_tables[jnp.arange(S), jnp.clip(seq_lens // page_size, 0, M - 1)], k_pool.shape[1] - 1)
        k_pool, r_pool = mla_cache_write(k_pool, r_pool, jnp.stack(cs), jnp.stack(ks), write_page, seq_lens)
        logits = self._logits(params, x)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        sampled = jax.random.categorical(
            key, logits / jnp.maximum(temperature, 1e-6)[:, None]).astype(jnp.int32)
        return (jnp.where(temperature > 0, sampled, greedy), logits, k_pool,
                {"v": r_pool, "conv": jnp.stack(windows), "state": state})


class GigaChat35_EP32(GigaChat35):
    """GigaChat3.5-432B-A28B (``model_type`` gigachat3_5;
    huggingface.co/ai-sage/GigaChat3.5-432B-A28B config.json), the text
    decoder at its published widths, cut to ONE chip of this deployment: 32
    chips share each layer (the 256 routed experts spread over them, 8 a
    chip; attention whole on every chip, as data-parallel attention, since
    neither a latent cache nor a delta-rule state is split by heads here;
    the vocabulary in eighths), further layers on further chips as pipeline
    stages. Held here: the published layers 0 and 3-6 (the first dense
    layer, a delta-rule mixer with SwiGLU 18,432, and one whole period of
    the routed layers in published order: latent attention at 3, the delta
    rule at 4-6), 8 of the 256 routed experts of each routed layer, 16,032
    of the 128,256 vocabulary rows. Every width as published: hidden 7,168;
    the delta rule's 32 key and 64 value heads of 128, a convolution of 4;
    latent attention 64 x (128 + 64) over 512 + 64 with the output gate;
    experts of 2,048, top 8 of 256 scaled by 2.5, one shared; dense 18,432.
    3,322,436,608 parameters, 6.64 GB in bfloat16. The two multi-token
    prediction modules are left out (a server that does not speculate never
    runs them). ``benchmark/configs/gigachat35-decode.json`` states the cut and
    every reading under ``assumed``. Served, not trained, here: 16 bytes a
    parameter of training state do not fit one chip at the guide's floors."""

    name = "gigachat35_ep32"

    @classmethod
    def default_recipe(cls) -> GigaChat35Recipe:
        return GigaChat35Recipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(262144,),
            num_classes=16032, dataset="lm_synthetic", compute_dtype=jnp.bfloat16,
            d_model=7168, layer_ids=(0, 3, 4, 5, 6), full_attention_layers=tuple(range(3, 40, 4)),
            first_k_dense=3, d_dense=18432, gdn_key_heads=32, gdn_value_heads=64, gdn_head_dim=128,
            gdn_conv=4, gdn_chunk=64, n_heads=64, q_lora=1536, kv_lora=512, qk_nope=128,
            qk_rope=64, v_head=128, rope_theta=100000.0, rope_factor=8.0, rope_original_context=32768,
            rope_beta_fast=32.0, rope_beta_slow=1.0, rope_mscale=1.0, rope_mscale_all_dim=1.0,
            d_expert=2048, n_experts=256, experts_per_token=8, experts_held=8, first_expert=0,
            route_scale=2.5, swiglu_limit=10.0, rms_eps=1e-6, moe_tile=32, moe_tile_decode=16,
        )
