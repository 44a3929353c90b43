"""Decoder LMs built from layer KINDS, under the zoo ``Model`` contract.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7). Where ``models/transformer.py``
is one block written out, a model here is a LIST of layers, each described
by three kinds (ROADMAP D5):

- attention: causal over every earlier key, or over a sliding ``window``;
  always fewer K/V heads than query heads allowed, QK RMS norm, a sigmoid
  output gate;
- positions: ``"rotary"`` (whole head dimension, half-split pairing) or
  ``"none"``;
- FFN: ``"dense"`` (gated, SwiGLU) or ``"routed"`` (a shared SwiGLU expert
  beside top-k sigmoid routing without dropped tokens over the share of
  the experts held here, :func:`theanompi_tpu.ops.moe.routed_experts`).

Every layer has four RMS norms (sandwich): ``x += norm(attn(norm(x)))``,
``x += norm(ffn(norm(x)))``. The leading dense layer, the period of window
and full layers, the experts held and the vocabulary rows are data of the
recipe. This is the ``afmoe`` family (Arcee Trinity); :class:`TrinityMini_EP8`
is Trinity-Mini cut to one chip of a stated deployment (its docstring).

Precision is said where a tensor is used, not by a leaf's name: matmul
weights are cast to the compute dtype at the matmul; norm gains, the
router's matmul and sigmoid, softmax statistics and the loss stay fp32.

The routers' selection bias is not a parameter: it lives in ``model_state``
(as BatchNorm's statistics do) and moves once a step, outside the gradient,
by the aux-loss-free rule ``b += coeff * sign(mean(n) - n)`` (the delta's
mean taken out) from the step's token counts ``n`` over ALL experts. On one
chip ``n`` counts this chip's tokens; chips that share a layer would sum it
(under plain data parallelism ``BSPEngine`` averages the replicas' state).
``apply`` also leaves the step's routing counters in ``model_state``;
:meth:`AfmoeLM.state_metrics` hands them to the recorder's row.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.transformer import _rms, next_token_loss, softmax_nll
from theanompi_tpu.ops.moe import route_topk, routed_experts
from theanompi_tpu.ops.pallas_attention import flash_attention

COUNTERS = ("moe_pairs_here", "moe_pairs_absent", "moe_pad_rows",
            "moe_load_max_over_mean")


class LayerKind(NamedTuple):
    window: Optional[int]  # attention: keys a query sees; None = all earlier
    positions: str  # "rotary" | "none"
    ffn: str  # "dense" | "routed"


def period(n_dense: int, n_routed: int, window: int, every: int = 4):
    """``n_dense`` leading dense layers (windowed and rotary, as the
    first layers of a period are), then ``n_routed`` routed ones in the
    period's order: every ``every``-th attends to all earlier keys
    without positions, the others to a rotary window."""
    kinds = [(window, "rotary", "dense")] * n_dense
    for j in range(n_routed):
        full = (j + 1) % every == 0
        kinds.append((None if full else window, "none" if full else "rotary", "routed"))
    return tuple(kinds)


@dataclasses.dataclass
class AfmoeRecipe(Recipe):
    """``input_shape`` is ``(seq_len,)`` and ``num_classes`` the vocabulary
    rows held (as the other LM recipes). ``layers``: one ``(window,
    positions, ffn)`` per layer (:class:`LayerKind`)."""

    d_model: int = 64
    n_heads: int = 4
    n_kv_heads: int = 2
    head_dim: int = 16
    d_ff: int = 96  # dense FFN width
    d_expert: int = 32  # width of one expert, routed or shared
    n_experts: int = 16  # the router's width: ALL experts
    experts_per_token: int = 4
    experts_held: int = 16  # experts first_expert .. first_expert + held live here
    first_expert: int = 0
    route_scale: float = 2.826
    bias_update: float = 0.001
    rope_theta: float = 10000.0
    rms_eps: float = 1e-5
    layers: tuple = period(1, 4, window=8)
    moe_tile: int = 8  # rows of a grouped-product tile


def _mm(x, w):
    """A matmul in the activations' dtype: the fp32 weight is cast here."""
    return x @ w.astype(x.dtype)


def _swiglu(p, h):
    return _mm(jax.nn.silu(_mm(h, p["w1"])) * _mm(h, p["w3"]), p["w2"])


def _rotary(x, theta):
    """``[B, T, H, D]`` rotated by position over the whole head dimension,
    element ``i`` paired with ``i + D/2``; angles in fp32."""
    T, half = x.shape[1], x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1).astype(x.dtype)


class AfmoeLM(Model):
    """The generic kinds-built LM; its default recipe is the tiny preset
    of the same kinds for the CPU tests."""

    name = "afmoe_lm"
    is_lm = True

    def __init__(self, recipe: AfmoeRecipe | None = None):
        self.recipe = r = recipe or self.default_recipe()
        self.kinds = tuple(LayerKind(w if w is None else int(w), p, f)
                           for w, p, f in r.layers)
        for kd in self.kinds:
            if kd.positions not in ("rotary", "none") or kd.ffn not in ("dense", "routed"):
                raise ValueError(f"unknown layer kind {kd}")
        if not 0 <= r.first_expert <= r.n_experts - r.experts_held:
            raise ValueError(
                f"experts {r.first_expert}..{r.first_expert + r.experts_held} "
                f"are not a share of {r.n_experts}")
        self.n_routed = sum(kd.ffn == "routed" for kd in self.kinds)

    @classmethod
    def default_recipe(cls) -> AfmoeRecipe:
        return AfmoeRecipe(
            batch_size=2, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(32,),
            num_classes=64, dataset="lm_synthetic",
        )

    # -- parameters ------------------------------------------------------
    def init(self, key):
        """normal(0, 0.02) matrices, gains ones. Keys: ``split(key, 2 + L)``
        = embedding, head, then one per layer, itself split in twelve:
        wq wk wv wg wo, then w1 w3 w2 (dense) or router, the shared
        expert's w1 w3 w2, the held experts' w1 w3 w2 (routed)."""
        r = self.recipe
        d, H, Hk, hd, V = r.d_model, r.n_heads, r.n_kv_heads, r.head_dim, r.num_classes
        G, fe = r.experts_held, r.d_expert

        def w(k, *shape):
            return 0.02 * jax.random.normal(k, shape)

        def swiglu(k1, k3, k2, *lead, f):
            return {"w1": w(k1, *lead, d, f), "w3": w(k3, *lead, d, f), "w2": w(k2, *lead, f, d)}

        ks = jax.random.split(key, 2 + len(self.kinds))
        params = {"tok_emb": w(ks[0], V, d), "head": w(ks[1], d, V),
                  "final_norm": jnp.ones((d,)), "layers": []}
        for kd, kl in zip(self.kinds, ks[2:]):
            k = jax.random.split(kl, 12)
            layer = {
                "attn": {"wq": w(k[0], d, H, hd), "wk": w(k[1], d, Hk, hd),
                         "wv": w(k[2], d, Hk, hd), "wg": w(k[3], d, H, hd),
                         "wo": w(k[4], H, hd, d),
                         "q_norm": jnp.ones((hd,)), "k_norm": jnp.ones((hd,))},
                "norm_in": jnp.ones((d,)), "norm_post_attn": jnp.ones((d,)),
                "norm_pre_mlp": jnp.ones((d,)), "norm_post_mlp": jnp.ones((d,)),
            }
            if kd.ffn == "dense":
                layer["ffn"] = swiglu(k[5], k[6], k[7], f=r.d_ff)
            else:
                layer["ffn"] = {"router": w(k[5], d, r.n_experts),
                                "shared": swiglu(k[6], k[7], k[8], f=fe),
                                "experts": swiglu(k[9], k[10], k[11], G, f=fe)}
            params["layers"].append(layer)
        state = {"router_bias": jnp.zeros((self.n_routed, r.n_experts), jnp.float32),
                 "counters": {c: jnp.zeros((), jnp.float32) for c in COUNTERS}}
        return params, state

    # -- forward ----------------------------------------------------------
    def _attention(self, kd: LayerKind, p, h):
        r = self.recipe
        q = jnp.einsum("btd,dhk->bthk", h, p["wq"].astype(h.dtype))
        k = jnp.einsum("btd,dhk->bthk", h, p["wk"].astype(h.dtype))
        v = jnp.einsum("btd,dhk->bthk", h, p["wv"].astype(h.dtype))
        gate = jnp.einsum("btd,dhk->bthk", h, p["wg"].astype(h.dtype))
        q, k = _rms(q, p["q_norm"], r.rms_eps), _rms(k, p["k_norm"], r.rms_eps)
        if kd.positions == "rotary":
            q, k = _rotary(q, r.rope_theta), _rotary(k, r.rope_theta)
        att = flash_attention(q, k, v, causal=True, window=kd.window)
        return jnp.einsum("bthk,hkd->btd", att * jax.nn.sigmoid(gate),
                          p["wo"].astype(h.dtype))

    def _routed(self, p, h, bias):
        r = self.recipe
        B, T, d = h.shape
        flat = h.reshape(B * T, d)
        idx, wts = route_topk(flat, p["router"], bias, r.experts_per_token, r.route_scale)
        ex = p["experts"]
        y, stats = routed_experts(
            flat, idx, wts, ex["w1"].astype(h.dtype), ex["w3"].astype(h.dtype),
            ex["w2"].astype(h.dtype), r.first_expert, r.n_experts, tm=r.moe_tile)
        return _swiglu(p["shared"], h) + y.reshape(B, T, d), stats

    def _layer(self, kd: LayerKind, x, p, bias):
        """One layer -> (x, routing stats or None)."""
        eps = self.recipe.rms_eps
        a = self._attention(kd, p["attn"], _rms(x, p["norm_in"], eps))
        x = x + _rms(a, p["norm_post_attn"], eps)
        h, stats = _rms(x, p["norm_pre_mlp"], eps), None
        if kd.ffn == "dense":
            f = _swiglu(p["ffn"], h)
        else:
            f, stats = self._routed(p["ffn"], h, bias)
        return x + _rms(f, p["norm_post_mlp"], eps), stats

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        del rng  # no dropout
        r = self.recipe
        x = (params["tok_emb"][tokens.astype(jnp.int32)] * math.sqrt(r.d_model)
             ).astype(r.compute_dtype)
        all_stats, routed = [], 0
        for kd, p in zip(self.kinds, params["layers"]):
            bias = state["router_bias"][routed] if kd.ffn == "routed" else None
            layer = lambda x, p, bias, kd=kd: self._layer(kd, x, p, bias)  # noqa: E731
            x, stats = jax.checkpoint(layer)(x, p, bias)  # recompute per layer
            if stats is not None:
                all_stats.append(stats)
                routed += 1
        logits = _mm(_rms(x, params["final_norm"], r.rms_eps), params["head"])
        if not all_stats:
            return logits, state
        counters = {
            "moe_pairs_here": sum(s.pairs_here for s in all_stats),
            "moe_pairs_absent": sum(s.pairs_absent for s in all_stats),
            "moe_pad_rows": sum(s.pad_rows for s in all_stats),
            "moe_load_max_over_mean": jnp.max(jnp.stack(
                [s.load_max_over_mean for s in all_stats])),  # the worst layer
        }
        new_state = {"router_bias": state["router_bias"],
                     "counters": {k: jnp.asarray(v, jnp.float32) for k, v in counters.items()}}
        if train:
            n = jnp.stack([s.counts for s in all_stats]).astype(jnp.float32)
            delta = jnp.sign(jnp.mean(n, -1, keepdims=True) - n)
            new_state["router_bias"] = state["router_bias"] + r.bias_update * (
                delta - jnp.mean(delta, -1, keepdims=True))
        return logits, jax.lax.stop_gradient(new_state)

    def loss(self, logits, labels):
        # labels ARE the token window [B, T]; shifted targets in-model
        return next_token_loss(labels.astype(jnp.int32), None, softmax_nll(logits))

    def metrics(self, logits, labels) -> dict:
        # the loss only: an argmax over the logits is a pass over the
        # vocabulary that no row of this model's needs
        return {}

    def state_metrics(self, model_state) -> dict:
        """The step's routing counters, for the recorder's row
        (``train.py fwd_bwd``); each is one scalar the drain fetches."""
        return dict(model_state["counters"])


class TrinityMini_EP8(AfmoeLM):
    """Arcee Trinity-Mini (26B-A3B, ``model_type`` afmoe;
    huggingface.co/arcee-ai/Trinity-Mini config.json) at its published
    widths, cut to ONE chip of this deployment: 8 chips share each layer
    (expert parallel, attention data parallel), further layers on further
    chips. Held here: 16 of the 128 routed experts of each expert layer,
    25,024 of the 200,192 vocabulary rows, the shared expert and attention
    whole; depth 5 of 32: one leading dense layer and one whole period
    (window, window, window, full). 705.5 M parameters, 11.3 GB of fp32
    parameters, gradients and Adam moments. The router scores all 128
    experts and takes 8 a token; what the 112 absent experts would add is
    left out (``benchmark/configs/trinity-mini.json`` states the cut)."""

    name = "trinity_mini_ep8"

    @classmethod
    def default_recipe(cls) -> AfmoeRecipe:
        return AfmoeRecipe(
            batch_size=1, n_epochs=1, optimizer="adam", schedule="constant",
            sched_kwargs={"lr": 3e-4}, lr_unit="step", input_shape=(8192,),
            num_classes=25024, dataset="lm_synthetic",
            compute_dtype=jnp.bfloat16,
            d_model=2048, n_heads=32, n_kv_heads=4, head_dim=128,
            d_ff=6144, d_expert=1024, n_experts=128, experts_per_token=8,
            experts_held=16, first_expert=0, route_scale=2.826,
            bias_update=0.001, rope_theta=10000.0, rms_eps=1e-5,
            layers=period(1, 4, window=2048), moe_tile=256,
        )
