"""Zoo-registered transformer LM models — the launchable face of the
N-D parallelism stack.

BEYOND-PARITY EXTENSION (SURVEY.md §5.7: the reference has no attention
anywhere). :class:`TransformerLMModel` wraps
:class:`theanompi_tpu.models.transformer.TransformerLM` in the standard
``Model`` contract, so the SAME drivers that run the CNN zoo run an LM:

- ``tmpi BSP 8 theanompi_tpu.models.lm TransformerLMModel`` — plain
  data-parallel LM training through BSPEngine (and EASGD/GoSGD work the
  same way: the sync rules never look inside the model).
- ``tmpi BSP 8 ... --tp 2 --sp 2`` — the CLI's mesh flags route to
  :class:`theanompi_tpu.parallel.nd.NDEngine`, which trains with
  Megatron tensor sharding, ring/Ulysses sequence parallelism, GPipe
  pipelining (``--pp``), or Switch-MoE expert parallelism (``--expert``,
  with :class:`MoELMModel`).

Token batches come from the ``lm_synthetic`` / ``lm_text`` datasets
(data/lm.py): "images" are token windows ``[B, T] int32`` and labels are
the same windows (next-token targets are computed in-model, shifted —
the target of position t is the token at t+1; the final position is
masked).
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp
import numpy as np

from theanompi_tpu.models.contract import Model, Recipe
from theanompi_tpu.models.transformer import (
    TransformerLM,
    next_token_loss,
    softmax_nll,
)


@dataclasses.dataclass
class LMRecipe(Recipe):
    """Recipe with the LM architecture knobs. ``input_shape`` is
    ``(seq_len,)`` and ``num_classes`` the vocabulary size (mirroring
    the image recipes so the driver's shape checks apply unchanged)."""

    d_model: int = 128
    n_heads: int = 4
    n_layers: int = 2
    d_ff: int = 256
    # "ring" = exact full attention locally, ring K/V rotation under SP;
    # "flash"/"ring_flash"/"ulysses"/"ulysses_flash" per TransformerLM
    attn: str = "ring"
    remat: bool = False
    # chunked loss (transformer.py::chunked_nll): CE per sequence chunk,
    # full [B, T, V] logits never materialize — the long-context memory
    # knob alongside remat. None = whole-sequence logits.
    loss_chunk: int | None = None
    # MoE knobs (MoELMModel only)
    n_experts: int = 8
    capacity_factor: float = 1.25
    aux_weight: float = 0.01


class TransformerLMModel(Model):
    """Dense decoder-only LM under the zoo contract. ``self.arch`` is
    the functional :class:`TransformerLM`; the parallel engines
    (``NDEngine``) reach through to it for tp/sp/pp sharding, while the
    plain contract surface below serves the DP/EASGD/GoSGD paths."""

    name = "transformer_lm"
    is_lm = True
    is_moe = False
    # serve/decode contract: the incremental prefill/decode surface
    # below exists (DecodeEngine checks this flag at construction)
    supports_decode = True

    def __init__(self, recipe: LMRecipe | None = None):
        self.recipe = recipe or self.default_recipe()
        r = self.recipe
        self.arch = TransformerLM(
            vocab=r.num_classes,
            d_model=r.d_model,
            n_heads=r.n_heads,
            n_layers=r.n_layers,
            d_ff=r.d_ff,
            max_len=r.input_shape[0],
            attn=r.attn,
            remat=r.remat,
            dtype=r.compute_dtype,
            loss_chunk=r.loss_chunk,
        )

    @classmethod
    def default_recipe(cls) -> LMRecipe:
        return LMRecipe(
            batch_size=32,
            n_epochs=5,
            optimizer="adam",
            schedule="constant",
            sched_kwargs={"lr": 1e-3},
            lr_unit="step",
            input_shape=(128,),
            num_classes=64,
            dataset="lm_synthetic",
        )

    # -- contract surface (DP / async-rule path) ------------------------
    def init(self, key):
        return self.arch.init(key), {}

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        del train, rng  # no dropout in this LM
        if self.recipe.loss_chunk:
            raise ValueError(
                "loss_chunk runs on the ND-engine path (arch.loss — "
                "tmpi --sp/--tp or the make_*_train_step builders); the "
                "classifier-contract path materializes the full logits "
                "this knob exists to avoid — unset loss_chunk here"
            )
        return self.arch.forward(params, tokens.astype(jnp.int32)), state

    def loss(self, logits, labels):
        # labels ARE the token window [B, T]; shifted targets in-model
        return next_token_loss(labels.astype(jnp.int32), None, softmax_nll(logits))

    def metrics(self, logits, labels) -> dict:
        labels = labels.astype(jnp.int32)
        preds = jnp.argmax(logits[:, :-1].astype(jnp.float32), axis=-1)
        err = jnp.mean((preds != labels[:, 1:]).astype(jnp.float32))
        return {"error": err}

    # -- incremental decode surface (serve/decode DecodeEngine) ---------
    # Decode-mode apply, split at the prefill/decode program boundary the
    # paged KV-cache needs (one compiled program per prompt bucket, ONE
    # single-token program for every decode iteration). Both delegate to
    # the functional arch so the MoE subclass inherits them unchanged
    # (its arch binds the dense top-1 Switch FFN).

    def cache_spec(self, page_size: int) -> dict:
        """What ``DecodeEngine`` builds the paged pools from: a page of
        either pool holds ``[page_size, n_heads * head_dim]`` fp32 K or V
        rows, so the pools are ``[L, n_pages + 1, page_size, H * hd]``. A
        position's heads lie side by side in one row (768 lanes at the
        136M widths) because a TPU array lives in ``(8, 128)`` tiles over
        its two minor dimensions: ``[n_heads, head_dim] = [12, 64]`` fills
        none and made the compiler re-lay whole pools
        (``transformer.paged_decode_step``). The programs do not take the
        pools donated. The page size is kept for :meth:`cache_rows`."""
        a = self.arch
        self._page_size = page_size
        page = (page_size, a.d_model)  # n_heads * head_dim
        return {"kind": "kv", "k_page": page, "v_page": page, "dtype": jnp.float32,
                "donate": False}

    def cache_rows(self, seq_lens) -> dict:
        """Host side: ``kv_positions``, the cached positions the decode step's
        kernel copies, in every layer, for the query at each of ``seq_lens``
        (numpy): its live pages whole (``ops/pallas_paged.py kv_decode``),
        pages of the size :meth:`cache_spec` was given."""
        page = self._page_size
        t = np.asarray(seq_lens, np.int64)
        return {"kv_positions": -(-t // page) * page}

    def decode_prefill(self, params, tokens, pages, k_pool, v_pool, *,
                       page_size: int):
        """Cache one padded prompt's K/V pages; see
        ``transformer.paged_prefill``."""
        return self.arch.prefill_cache(
            params, tokens, pages, k_pool, v_pool, page_size=page_size
        )

    def decode_step(self, params, k_pool, v_pool, page_tables, seq_lens,
                    last_tokens, active, temperature, key, *,
                    page_size: int):
        """One continuous-batching decode iteration; see
        ``transformer.paged_decode_step``."""
        return self.arch.decode_step(
            params, k_pool, v_pool, page_tables, seq_lens, last_tokens,
            active, temperature, key, page_size=page_size
        )


class MoELMModel(TransformerLMModel):
    """Switch-MoE LM. Trains via ``--expert N`` (expert-parallel
    NDEngine path, which uses ``arch.loss`` including the load-balance
    auxiliary); the plain contract path is blocked because the aux loss
    cannot flow through ``loss(logits, labels)``."""

    name = "moe_lm"
    is_moe = True

    def __init__(self, recipe: LMRecipe | None = None):
        from theanompi_tpu.models.moe import MoETransformerLM

        self.recipe = recipe or self.default_recipe()
        r = self.recipe
        if r.loss_chunk:
            raise ValueError(
                "loss_chunk is not implemented for the MoE stack "
                "(dense TransformerLMModel only)"
            )
        self.arch = MoETransformerLM(
            vocab=r.num_classes,
            d_model=r.d_model,
            n_heads=r.n_heads,
            n_layers=r.n_layers,
            d_ff=r.d_ff,
            max_len=r.input_shape[0],
            n_experts=r.n_experts,
            capacity_factor=r.capacity_factor,
            aux_weight=r.aux_weight,
            attn=r.attn,
            dtype=r.compute_dtype,
        )

    def apply(self, params, state, tokens, *, train: bool = False, rng=None):
        raise ValueError(
            "MoELMModel trains expert-parallel only (tmpi BSP ... --expert N); "
            "for plain data parallelism use TransformerLMModel — the Switch "
            "load-balance auxiliary loss cannot flow through the classifier "
            "contract's loss(logits, labels)"
        )


class TransformerLM_136M(TransformerLMModel):
    """GPT-2-small-scale config (~136M params): the benchmark's
    ``lm136m`` configuration (``benchmark/configs/lm136m.json``, cell
    ``lm136m-bsp1-train``). 12 layers x d=768,
    T=1024, 32k vocab, fused Pallas flash attention; bf16 compute
    (params stored fp32, matmuls/activations bf16 with fp32 softmax
    statistics — transformer.py::cast_block_params), so an
    MFU is read against the bf16 peak the math actually runs at.
    Sized so TWO full f32 states (params + adam m/v) fit one v5e
    alongside the un-sharded 32k-vocab logits: a caller that cannot
    donate its input state still fits."""

    name = "transformer_lm_136m"

    @classmethod
    def default_recipe(cls) -> LMRecipe:
        return LMRecipe(
            batch_size=8,
            n_epochs=1,
            optimizer="adam",
            schedule="constant",
            sched_kwargs={"lr": 3e-4},
            lr_unit="step",
            input_shape=(1024,),
            num_classes=32768,
            dataset="lm_synthetic",
            compute_dtype=jnp.bfloat16,
            d_model=768,
            n_heads=12,
            n_layers=12,
            d_ff=3072,
            attn="flash",
        )


class TransformerLM_350M(TransformerLMModel):
    """GPT-2-medium-scale benchable config (~360M params): 24 layers x
    d=1024, T=1024, 32k vocab, fused Pallas flash attention, bf16
    compute, per-block remat (activation memory, not weights, is what
    remains after donation). This size only fits one v5e when the
    caller DONATES the train state (``run_training`` does) —
    without donation two full f32 states (params + adam m/v ~ 4.3 GB)
    coexist and OOM."""

    name = "transformer_lm_350m"

    @classmethod
    def default_recipe(cls) -> LMRecipe:
        return LMRecipe(
            batch_size=8,
            n_epochs=1,
            optimizer="adam",
            schedule="constant",
            sched_kwargs={"lr": 3e-4},
            lr_unit="step",
            input_shape=(1024,),
            num_classes=32768,
            dataset="lm_synthetic",
            compute_dtype=jnp.bfloat16,
            d_model=1024,
            n_heads=16,
            n_layers=24,
            d_ff=4096,
            attn="flash",
            remat=True,
        )
