"""Train/eval step construction — the ``compile_iter_fns`` equivalent.

Reference (SURVEY.md §3.2): each model compiled a Theano ``train_fn``
(fwd+bwd, grads written to velocity shared vars), the exchanger ran MPI
between calls, then ``update_fn`` applied the averaged velocities. Here
the entire iteration — forward, backward, gradient sync collective,
optimizer update, LR schedule — is ONE jitted XLA program; the gradient
sync is a pluggable function applied to raw grads *inside* the step
(reference ordering: comm sees raw gradients, update runs post-exchange).

``make_train_step`` builds the single-device / replicated step; the
parallel layer (``theanompi_tpu.parallel``) wraps it in ``shard_map``
over a mesh and supplies the collective ``grad_sync``.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from theanompi_tpu.models.contract import Model
from theanompi_tpu.ops.optimizers import apply_updates

PyTree = Any
GradSync = Callable[[PyTree], PyTree]  # raw grads -> synced grads


class TrainState(NamedTuple):
    """The complete training state pytree — the analogue of the
    reference's Theano shared variables (params + vels) plus the step
    counter that drives the LR schedule.

    ``ef``: the wire codec's error-feedback residual accumulators
    (parallel/codec.py) — per-device quantization residuals of the
    gradient exchange, stacked ``[n_devices, ...]`` and sharded over
    the exchange axes. ``()`` (the default, zero leaves) whenever the
    codec carries no state, so codec-off runs pay nothing in state
    size, donation, or checkpoints; when present it is checkpointed
    with the rest of the state, making compressed-run resume exact."""

    params: PyTree
    model_state: PyTree  # BatchNorm running stats etc.
    opt_state: PyTree
    step: jax.Array  # int32 global step
    ef: PyTree = ()  # wire-codec error-feedback residuals (or ())


def init_train_state(model: Model, key: jax.Array) -> TrainState:
    params, model_state = model.init(key)
    opt_state = model.optimizer().init(params)
    return TrainState(params, model_state, opt_state, jnp.zeros((), jnp.int32))


def make_schedule_fn(model: Model, steps_per_epoch: int = 1):
    """``step -> lr`` honoring the recipe's schedule unit (the
    reference's ``adjust_hyperp(epoch)``, evaluated inside the compiled
    step)."""
    schedule = model.schedule()
    per_epoch = float(max(1, steps_per_epoch))
    by_epoch = model.recipe.lr_unit == "epoch"

    def schedule_lr(step):
        return schedule(step / per_epoch if by_epoch else step)

    return schedule_lr


def loss_and_grads(
    model: Model, params, model_state, images, labels, rng,
    loss_scale: float = 1.0, param_sync: Optional[Callable] = None,
):
    """The shared forward+backward core: ``-> (loss, logits,
    new_model_state, raw_grads)``. Used by make_train_step and the
    ZeRO-1 step (parallel/zero.py) so step semantics cannot drift.

    ``param_sync``: applied to the params INSIDE the differentiated
    function — the hook the bucketed overlap exchanger uses to plant
    per-bucket ``custom_vjp`` tags whose backward posts each bucket's
    collective at the point its grads are produced
    (parallel/strategies.py::BucketedOverlapSync.wrap_params). The
    returned grads are then already synced."""

    def loss_fn(params):
        if param_sync is not None:
            params = param_sync(params)
        logits, new_model_state = model.apply(
            params, model_state, images, train=True, rng=rng
        )
        loss = model.loss(logits, labels) * loss_scale
        return loss, (new_model_state, logits)

    (loss, (new_model_state, logits)), grads = jax.value_and_grad(
        loss_fn, has_aux=True
    )(params)
    if loss_scale != 1.0:
        grads = jax.tree_util.tree_map(lambda g: g / loss_scale, grads)
    return loss / loss_scale, logits, new_model_state, grads


def make_train_step(
    model: Model,
    steps_per_epoch: int = 1,
    grad_sync: Optional[GradSync] = None,
    loss_scale: float = 1.0,
    input_transform: Optional[Callable] = None,
    accum_steps: int = 1,
    numerics: bool = False,
    fused_update: bool = False,
):
    """Build the pure train step: ``(state, images, labels, rng) ->
    (state, metrics)``.

    ``accum_steps > 1``: gradient accumulation — the (per-device) batch
    is split into ``accum_steps`` microbatches folded through a
    ``lax.scan``; gradients average across microbatches BEFORE the
    exchanger sync and the single optimizer update, so the SGD
    trajectory is the large-batch one while activation memory is that
    of ``batch / accum_steps`` (beyond parity: the reference had no
    microbatching — its per-GPU batch WAS the memory limit; here config
    #5-scale global batches fit a handful of chips). BatchNorm batch
    stats update sequentially per microbatch (same running-stat stream
    as equally-sized small steps); metrics are microbatch means.

    ``steps_per_epoch`` converts the step counter to the schedule's unit
    when the recipe schedules by epoch (reference: ``adjust_hyperp(epoch)``
    ran between epochs; here the piecewise schedule is evaluated inside
    the compiled step so nothing happens on the host).

    ``grad_sync`` is the exchanger hook — under ``shard_map`` it holds the
    collective (psum mean / ring / compressed ring); None means single
    replica.

    ``fused_update``: replace the recipe's optimizer with its fused
    one-pass equivalent (ops/pallas_update.py — weight decay + clip +
    momentum + param write in one Pallas kernel per leaf, one HBM
    round-trip instead of ~4). SGD-family rules only; others refuse
    loudly. State layout matches the unfused rule, so checkpoints
    resume across the boundary.

    ``grad_sync`` objects exposing ``in_backward=True`` (the bucketed
    overlap exchanger, parallel/strategies.py) are applied to the
    PARAMS inside the differentiated loss instead of to the grads after
    it — their per-bucket collectives then overlap the tail of
    backward. Incompatible with ``accum_steps > 1`` (the sync must run
    once on the accumulated grads, not per microbatch).

    ``numerics``: compile the numerics sentinels into the step
    (obs/numerics.py) — global grad-norm (post-sync: the gradient the
    update actually sees), update-norm, new-param-norm, and a fused
    non-finite count over the grads, returned in the metrics dict under
    ``nm_``-prefixed keys. The loss/grad/update math is untouched; the
    sentinels are extra outputs of the same XLA program, so they drain
    through the dispatch pipeline with zero new host syncs.

    ``input_transform`` runs ON DEVICE at the top of the compiled step
    (e.g. uint8 -> ``(x - mean) * scale``): the host then ships compact
    uint8 batches and normalization fuses into the first conv — 4x less
    H2D traffic than shipping float32 (the reference normalized on the
    host loader, ``lib/proc_load_mpi.py``; on TPU the wire is the
    scarcer resource).

    NOTE: the local-grad → allreduce decomposition relies on classic
    pmap-style AD semantics (``shard_map(..., check_vma=False)``), under
    which the transpose of a forward psum is itself a psum (measured on
    jax 0.9 — cotangents flow across the collective), so each device's
    backward yields exactly ``d(sum over devices of local_loss)/d
    theta_local``. Summing those per-device grads over the mesh and
    dividing by n — the exchanger's psum-mean — is therefore the true
    gradient of the mean loss, and this stays EXACT even when the
    forward pass contains collectives (cross-replica BatchNorm), whose
    cross-device paths the transposed psums account for. Under
    ``check_vma=True`` the cotangent of replicated params arrives
    already globally summed ("unreduced"), so an explicit exchanger
    would double-count — verified empirically on jax 0.9; see
    tests/test_bsp.py. All shard_maps in this framework therefore use
    ``check_vma=False``. (models/transformer.py::make_nd_train_step
    generalizes this rule to multi-axis tp/sp meshes.)
    """
    if fused_update:
        from theanompi_tpu.ops.pallas_update import fuse_optimizer

        optimizer = fuse_optimizer(model.recipe.optimizer,
                                   **model.recipe.opt_kwargs)
    else:
        optimizer = model.optimizer()
    schedule_lr = make_schedule_fn(model, steps_per_epoch)
    accum_steps = max(1, int(accum_steps))
    in_backward = bool(getattr(grad_sync, "in_backward", False))
    if in_backward and accum_steps > 1:
        # in-backward buckets only: the :ef bucketed variant is
        # stateful/post-backward (in_backward=False) and composes with
        # accumulation — one bucketed sync on the accumulated grads
        raise ValueError(
            "--allreduce-buckets syncs inside backward, but "
            f"accum_steps={accum_steps} needs ONE sync on the "
            "accumulated grads — per-microbatch bucket collectives "
            "would multiply the wire volume; drop one of the two"
        )
    param_sync = grad_sync.wrap_params if in_backward else None

    def fwd_bwd(params, model_state, images, labels, rng):
        loss, logits, new_model_state, grads = loss_and_grads(
            model, params, model_state, images, labels, rng,
            loss_scale=loss_scale, param_sync=param_sync,
        )
        metrics = {"loss": loss, **model.metrics(logits, labels)}
        if hasattr(model, "state_metrics"):
            # counters a model's apply left in its state (models/afmoe.py:
            # the step's routing counts) ride the row; other models'
            # compiled steps are the programs they were
            metrics.update(model.state_metrics(new_model_state))
        return new_model_state, grads, metrics

    def train_step(state: TrainState, images, labels, rng):
        if input_transform is not None:
            images = input_transform(images)

        if accum_steps == 1:
            new_model_state, grads, metrics = fwd_bwd(
                state.params, state.model_state, images, labels, rng
            )
        else:
            B = images.shape[0]
            if B % accum_steps:
                raise ValueError(
                    f"per-device batch {B} must be divisible by "
                    f"accum_steps={accum_steps}"
                )
            xm = images.reshape(accum_steps, B // accum_steps, *images.shape[1:])
            ym = labels.reshape(accum_steps, B // accum_steps, *labels.shape[1:])

            def micro(carry, inp):
                model_state, gsum = carry
                x, y, idx = inp
                model_state, grads, metrics = fwd_bwd(
                    state.params, model_state, x, y, jax.random.fold_in(rng, idx)
                )
                # Accumulate in fp32 regardless of param dtype: repeated
                # bf16 additions across microbatches would drift from the
                # large-batch trajectory this mode promises.
                gsum = jax.tree_util.tree_map(
                    lambda a, g: a + g.astype(jnp.float32), gsum, grads
                )
                return (model_state, gsum), metrics

            gzero = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params
            )
            (new_model_state, gsum), ms = jax.lax.scan(
                micro, (state.model_state, gzero),
                (xm, ym, jnp.arange(accum_steps)),
            )
            grads = jax.tree_util.tree_map(
                lambda g, p: (g / accum_steps).astype(p.dtype),
                gsum, state.params,
            )
            metrics = jax.tree_util.tree_map(lambda m: jnp.mean(m, axis=0), ms)

        new_ef = state.ef
        if grad_sync is not None and not in_backward:
            if getattr(grad_sync, "stateful", False):
                # compressed exchange with error feedback: the strategy
                # threads the codec residuals through engine state
                # (parallel/strategies.py::codec_psum_mean, and the
                # bucketed :ef path)
                grads, new_ef = grad_sync(grads, state.ef)
            else:
                grads = grad_sync(grads)
        # (in_backward syncs already ran inside the bucket tags' vjps —
        # `grads` here is post-collective either way, so the numerics
        # sentinels below keep their post-sync meaning)

        lr = schedule_lr(state.step)
        if optimizer.apply is not None:
            # fused one-pass epilogue (ops/pallas_update.py): params and
            # velocity are rewritten in place, no update tree exists
            new_params, new_opt_state = optimizer.apply(
                grads, state.opt_state, state.params, lr
            )
            updates = None
        else:
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params, lr
            )
            new_params = apply_updates(state.params, updates)

        metrics = {**metrics, "lr": lr}
        if numerics:
            from theanompi_tpu.obs.numerics import sentinel_metrics

            if updates is None:
                # fused path: reconstruct the update tree for the gauges
                # only — the numerics variant is a SEPARATE compiled
                # program, so sentinel-off hot steps pay nothing
                from theanompi_tpu.ops.optimizers import update_delta

                updates = update_delta(new_params, state.params)
            metrics = {**metrics,
                       **sentinel_metrics(grads, updates, new_params)}
        new_state = TrainState(new_params, new_model_state, new_opt_state,
                               state.step + 1, new_ef)
        return new_state, metrics

    return train_step


def make_multi_step(step_fn, k: int, stacked: bool = False):
    """Fuse ``k`` successive train steps into one compiled program via
    ``lax.scan`` — one host dispatch per k steps.

    ``step_fn`` is any pure step ``(state, x, y, rng) -> (state, metrics)``
    (e.g. from :func:`make_train_step`). With ``stacked=False`` the one
    given batch is reused every sub-step (benchmarking); with
    ``stacked=True`` images/labels carry a leading dim of size ``k`` (a
    compiled epoch slice). The mode is explicit — inferring it from
    shapes would misfire whenever batch_size == k. Per-sub-step rngs are
    derived by folding the step index into ``rng``. Returns
    ``(state, metrics)`` with metrics stacked over ``k``.

    Scanning removes Python (and the per-step host dispatch) from the
    loop.
    """

    def run(state, images, labels, rng):
        if stacked and images.shape[0] != k:
            raise ValueError(
                f"stacked=True expects leading dim {k}, got {images.shape[0]}"
            )

        def body(st, idx):
            x = images[idx] if stacked else images
            y = labels[idx] if stacked else labels
            st, m = step_fn(st, x, y, jax.random.fold_in(rng, idx))
            return st, m

        return jax.lax.scan(body, state, jnp.arange(k))

    return run


def make_eval_step(
    model: Model,
    input_transform: Optional[Callable] = None,
    views: int = 1,
):
    """``(state, images, labels) -> metrics`` with loss, on eval stats.

    ``views > 1``: multi-view evaluation (the AlexNet-era 10-crop val
    protocol — 4 corners + center, each mirrored). ``images`` carries
    ``len(labels) * views`` rows, view-major per image; per-image logits
    are the mean over views before loss/metrics (reference: the
    published top-1 protocol the recipes were validated with).

    The forward itself is :func:`theanompi_tpu.models.zoo.infer_fn` —
    the same eval-mode closure the serving engine compiles, so train-
    time validation and serving can never diverge on inference
    semantics (train=False, no rng, fixed BN stats)."""
    from theanompi_tpu.models.zoo import infer_fn

    fwd = infer_fn(model)

    def eval_step(state: TrainState, images, labels):
        if input_transform is not None:
            images = input_transform(images)
        logits = fwd(state.params, state.model_state, images)
        if views > 1:
            logits = logits.reshape(-1, views, logits.shape[-1]).mean(axis=1)
        return {"loss": model.loss(logits, labels), **model.metrics(logits, labels)}

    return eval_step
