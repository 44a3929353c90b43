"""BSP data-parallel training step.

TPU-native rebuild of the reference's BSP rule (reference:
``lib/exchanger.py`` — ``BSP_Exchanger.exchange()`` called between
Theano functions each iteration; SURVEY.md §3.2). Here the whole BSP
iteration — forward, backward, gradient allreduce, update — is ONE
``jax.jit``-compiled SPMD program over a ``('data',)`` mesh:

- the per-device batch shard comes in sharded along ``data``;
- params / optimizer state are replicated; every device computes the
  identical update after the gradient mean (lockstep by construction —
  the XLA program IS the barrier, where the reference relied on
  blocking MPI allreduce);
- the exchanger strategy is compiled into the step (``psum`` by
  default, explicit/compressed ring variants for parity with
  ``asa32``/``asa16``).
"""

from __future__ import annotations

import os

import jax
from jax import lax
from jax.sharding import Mesh

from theanompi_tpu.models.contract import Model
from theanompi_tpu.parallel.mesh import DATA_AXIS
from theanompi_tpu.parallel.strategies import (
    bucketed,
    checked_mode_strategy,
    get_strategy,
)
from theanompi_tpu.train import TrainState, init_train_state, make_eval_step, make_train_step


def _checked_vma() -> bool:
    """Module switch executing the check_vma migration plan for the BSP
    engine (parallel/strategies.py "check_vma pin & migration plan"):
    ``TMPI_CHECKED_VMA=1`` builds every BSP shard_map with
    ``check_vma=True`` and swaps the exchanger for its checked-mode form
    (division by the axis size — AD already summed the cotangents).
    Measured outcome (round 5, jax 0.9.0, 8-device CPU mesh): the full
    BSP oracle suite passes identically both ways, single-step params
    agree to float epsilon, forward cross-replica collectives (BN pmean)
    included — see tests/test_bsp.py::TestCheckedVmaBSP. Default stays
    classic semantics: the OTHER engines (easgd/gosgd/nd/zero/fused
    strategies) still assume local-grad AD, and the plan requires the
    flip to land everywhere at once."""
    return os.environ.get("TMPI_CHECKED_VMA", "") == "1"


def _axes_tuple(axis_name) -> tuple:
    return (axis_name,) if isinstance(axis_name, str) else tuple(axis_name)


from theanompi_tpu.parallel.mesh import fold_linear_index as _fold_linear_index


def _bsp_recipe(mesh, axis_name, codec):
    """The BSP :class:`~theanompi_tpu.parallel.recipe.ShardingRecipe`:
    everything replicated, EXCEPT the codec's error-feedback residuals,
    which are per-device (stacked ``[n, ...]``) and must be declared
    sharded over the data axes — a blanket replicated spec would stamp
    device-varying residuals as replicated with no error under
    ``check_vma=False``. THE single spec source for this engine's
    shard_map specs, memory factors, and topology stamp."""
    from theanompi_tpu.parallel.recipe import ShardingRecipe

    return ShardingRecipe.bsp(
        mesh, axis_name,
        ef_sharded=codec is not None and codec.error_feedback,
    )


def _bsp_grad_sync(strategy, axis_name, n, codec, checked,
                   allreduce_buckets, axis_sizes=None):
    """The one place the BSP step builders resolve their exchanger:
    ``--allreduce-buckets`` swaps the single psum for the bucketed
    overlap scheduler (parallel/strategies.py::BucketedOverlapSync);
    checked-mode AD has no exchanger collective to bucket and refuses.
    ``axis_sizes``: the per-axis mesh extents (mesh-axis order) the
    'hier' strategy needs to stage its two-hop schedule."""
    if allreduce_buckets:
        if checked:
            raise ValueError(
                "--allreduce-buckets has nothing to bucket under "
                "TMPI_CHECKED_VMA=1: checked-mode AD already summed the "
                "cotangents, there is no exchanger collective"
            )
        return bucketed(strategy, axis_name, n, allreduce_buckets,
                        codec=codec, axis_sizes=axis_sizes)
    return (
        checked_mode_strategy(strategy, axis_name, n, codec=codec) if checked
        else get_strategy(strategy, axis_name, n, codec=codec,
                          axis_sizes=axis_sizes)
    )


def make_bsp_train_step(
    model: Model,
    mesh: Mesh,
    steps_per_epoch: int = 1,
    strategy: str = "psum",
    axis_name=DATA_AXIS,
    donate: bool = True,
    input_transform=None,
    accum_steps: int = 1,
    numerics: bool = False,
    wire_codec=None,
    fused_update: bool = False,
    allreduce_buckets: float = 0.0,
):
    """Build the jitted BSP step: ``(state, images, labels, rng) ->
    (state, metrics)`` over global arrays. ``accum_steps``: gradient
    accumulation inside the step (see train.make_train_step) — the
    per-DEVICE batch splits into that many microbatches.

    ``images``/``labels`` hold the GLOBAL batch (sharded or shardable
    along ``data``); ``state`` is replicated; ``rng`` is a single key —
    each device folds in its axis index so dropout masks differ per
    shard (the reference's workers each had their own RNG stream).

    ``axis_name`` may be a TUPLE of mesh axes for multi-slice meshes
    (``('dcn', 'data')``): the gradient mean then reduces over ICI
    within each slice and DCN across slices — XLA lowers the hierarchy
    from the mesh layout (SURVEY.md §5.8 "topology split").

    ``fused_update``: one-pass optimizer epilogue (train.make_train_step
    / ops/pallas_update.py). ``allreduce_buckets`` (MB, 0 = off): chunk
    the gradient allreduce into ~MB buckets whose psums launch inside
    backward (parallel/strategies.py::BucketedOverlapSync) — same
    numerics as the single psum, strategy 'psum' only.
    """
    from theanompi_tpu.parallel.codec import get_codec

    codec = get_codec(wire_codec)
    allreduce_buckets = float(allreduce_buckets or 0.0)
    axes = _axes_tuple(axis_name)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    axis_sizes = tuple(int(mesh.shape[a]) for a in axes)
    if n == 1:
        # validate early (bucketed also checks the strategy/codec pair);
        # a 1-device mesh has no collectives, so buckets are a no-op
        if allreduce_buckets:
            bucketed(strategy, axis_name, n, allreduce_buckets, codec=codec,
                     axis_sizes=axis_sizes)
        else:
            get_strategy(strategy, axis_name, n, codec=codec,
                         axis_sizes=axis_sizes)
        # Single-device path: no collectives exist, so skip the shard_map
        # machinery entirely — the plain jitted step is semantically
        # identical. The state is donated as on many devices: on a 16 GB
        # chip a second params+opt copy per in-flight step is what a
        # full-width one-chip run cannot afford.
        base = make_train_step(model, steps_per_epoch,
                               input_transform=input_transform,
                               accum_steps=accum_steps, numerics=numerics,
                               fused_update=fused_update)

        def single_step(state, images, labels, rng):
            return base(state, images, labels, jax.random.fold_in(rng, 0))

        return jax.jit(single_step, donate_argnums=(0,) if donate else ())

    checked = _checked_vma()
    grad_sync = _bsp_grad_sync(strategy, axis_name, n, codec, checked,
                               allreduce_buckets, axis_sizes=axis_sizes)
    base_step = make_train_step(
        model, steps_per_epoch, grad_sync=grad_sync,
        input_transform=input_transform, accum_steps=accum_steps,
        numerics=numerics, fused_update=fused_update,
    )

    def sharded_step(state: TrainState, images, labels, rng):
        rng = _fold_linear_index(rng, axes, mesh)
        new_state, metrics = base_step(state, images, labels, rng)
        # Per-replica BatchNorm stats diverge across shards; average them
        # so the output state is truly replicated (the reference kept
        # per-worker stats and checkpointed rank 0's — averaging is the
        # better-defined equivalent).
        new_state = new_state._replace(
            model_state=lax.pmean(new_state.model_state, axis_name)
        )
        metrics = lax.pmean(metrics, axis_name)
        return new_state, metrics

    # check_vma=False by default: the exchanger abstraction requires
    # classic pmap AD semantics (psum transpose = psum) — see
    # make_train_step's note. TMPI_CHECKED_VMA=1 flips this engine to
    # the migrated checked-mode semantics (_checked_vma docstring).
    recipe = _bsp_recipe(mesh, axis_name, codec)
    spec = recipe.batch_spec
    sspec = recipe.state_spec(TrainState)
    mapped = jax.shard_map(
        sharded_step,
        mesh=mesh,
        in_specs=(sspec, spec, spec, recipe.scalar),
        out_specs=(sspec, recipe.scalar),
        check_vma=checked,
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


class BSPEngine:
    """Rule-engine wrapper over the BSP step (uniform driver protocol
    shared with EASGDEngine/GOSGDEngine).

    Collective schedule pinned by the SPMD analyzer (ISSUE 7): the
    in-step grad psum + metrics pmean signature is golden-snapshotted
    (tools/analyze/golden/bsp_*.json) and ``traffic_model()`` is
    cross-checked against the traced wire bytes — changing the
    exchange or the analytic model alone fails ``tmpi lint``
    (SPMD003/SPMD101); regenerate with ``tmpi lint --update-golden``."""

    name = "bsp"
    exchange_every = 0  # the allreduce is inside every step
    # donation audit (ISSUE 2): with donate_argnums=(0,) every in-flight
    # step under the async dispatch pipeline reuses the params+opt
    # buffers instead of doubling HBM — on one device as on many
    donates_state = True

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        steps_per_epoch: int = 1,
        strategy: str = "psum",
        axis_name=None,
        input_transform=None,
        eval_views: int = 1,
        accum_steps: int = 1,
        wire_codec=None,
        fused_update: bool = False,
        allreduce_buckets: float = 0.0,
    ):
        from theanompi_tpu.parallel.codec import get_codec

        if axis_name is None:
            from theanompi_tpu.parallel.mesh import batch_axes

            axis_name = batch_axes(mesh)
        self.model = model
        self.mesh = mesh
        self.codec = get_codec(wire_codec)
        self._build = dict(
            steps_per_epoch=steps_per_epoch, strategy=strategy,
            axis_name=axis_name, input_transform=input_transform,
            accum_steps=accum_steps, wire_codec=self.codec,
            fused_update=bool(fused_update),
            allreduce_buckets=float(allreduce_buckets or 0.0),
        )
        # THE spec source for this engine (parallel/recipe.py): the
        # analyzer (SHARD001-004) verifies these declared specs against
        # the compiled executable, memory_model divides by their
        # extents, and the checkpoint topology stamp carries them
        self.sharding = _bsp_recipe(mesh, axis_name, self.codec)
        # per-flag variants, built lazily: {numerics_flag: jitted step}.
        # The numerics step is a SECOND compiled program (sentinels are
        # extra outputs) — only runs where --numerics-freq selects it.
        self._steps = {False: make_bsp_train_step(model, mesh, **self._build)}
        self._eval = make_bsp_eval_step(
            model, mesh, axis_name=axis_name, input_transform=input_transform,
            eval_views=eval_views,
        )

    def init_state(self, rng):
        state = init_train_state(self.model, rng)
        n = 1
        for a in _axes_tuple(self._build["axis_name"]):
            n *= self.mesh.shape[a]
        if n > 1 and self.codec.error_feedback:
            if self._build["strategy"] == "hier":
                # hier feeds quantization error back on the DCN shard,
                # not per grad leaf: one (n, seg) residual row-stack
                # (per bucket, when bucketed) — see hier_ef_template
                from theanompi_tpu.parallel.mesh import slice_topology
                from theanompi_tpu.parallel.strategies import (
                    hier_ef_template,
                )

                bb = None
                if self._build["allreduce_buckets"]:
                    bb = max(1, int(
                        self._build["allreduce_buckets"] * 2 ** 20))
                state = state._replace(ef=hier_ef_template(
                    state.params, slice_topology(self.mesh),
                    bucket_bytes=bb,
                ))
            else:
                # per-device quantization residuals, stacked [n, ...]
                # and sharded over the data axes by the step's state
                # spec — checkpointed with the rest of the state (exact
                # resume)
                state = state._replace(ef=self.codec.init_ef(state.params,
                                                             stack=n))
        return state

    def train_step(self, state, images, labels, rng, numerics: bool = False):
        numerics = bool(numerics)
        if numerics not in self._steps:
            self._steps[numerics] = make_bsp_train_step(
                self.model, self.mesh, numerics=numerics, **self._build
            )
        return self._steps[numerics](state, images, labels, rng)

    def exchange(self, state):
        return state

    def eval_step(self, state, images, labels):
        # strip the codec residuals: eval's state spec is a blanket P()
        # (replicated), and the sharded ef leaves are irrelevant to a
        # forward pass — passing them would force a gather per val batch
        return self._eval(state._replace(ef=()), images, labels)

    def get_step(self, state) -> int:
        from theanompi_tpu.parallel.mesh import first_local_value

        return int(first_local_value(state.step))

    def sharding_recipe(self):
        """The engine's :class:`~theanompi_tpu.parallel.recipe.
        ShardingRecipe` — the declared spec table the sharding analyzer
        (tools/analyze/sharding.py) verifies against GSPMD's compiled
        truth and the worker stamps into the ``__topology__`` manifest."""
        return self.sharding

    def elastic_spec(self) -> dict:
        """Per-leaf reshard policies stamped into every checkpoint's
        topology manifest (utils/checkpoint.load_resharded). BSP state
        is replicated — mesh-invariant global content, the default
        ``global`` policy — except the codec's per-device error-feedback
        residuals, which pair with each device's own quantization
        history and are meaningless on a different world: reset."""
        return {"policies": {".ef": {"policy": "reset"}}}

    def traffic_model(self, state):
        """Analytic per-step wire volume of this engine's gradient
        allreduce (obs/comm.py): the in-step psum/ring over the data
        axes, sized by the grad pytree (= params) and the strategy's /
        codec's wire compression — raw AND effective bytes. With
        ``--allreduce-buckets`` the TOTAL volume is unchanged (the same
        bytes, chunked) but the schedule geometry — bucket count and the
        overlap fraction the attribution model prices comm at — rides
        the detail block, keeping the gauges and the SPMD101/102
        cross-checks truthful about the bucketed wire."""
        import math as _math

        from theanompi_tpu.obs.comm import bsp_traffic, pytree_num_elements
        from theanompi_tpu.parallel.mesh import slice_topology

        axes = _axes_tuple(self._build["axis_name"])
        n = 1
        for a in axes:
            n *= self.mesh.shape[a]
        axis_sizes = tuple(int(self.mesh.shape[a]) for a in axes)
        n_slices, _per = slice_topology(self.mesh)
        n_buckets = None
        overlap = None
        segments = None
        if self._build["allreduce_buckets"] and n > 1:
            from theanompi_tpu.parallel.strategies import (
                bucket_overlap_frac,
            )

            sync = bucketed(
                self._build["strategy"], self._build["axis_name"], n,
                self._build["allreduce_buckets"], codec=self.codec,
                axis_sizes=axis_sizes,
            )
            # one bucket walk serves both figures (this runs on the
            # metrics-snapshot path)
            buckets = sync.buckets_for(state.params)
            n_buckets = len(buckets)
            overlap = (
                bucket_overlap_frac(n_buckets) if sync.in_backward
                else 0.0
            )
            if self._build["strategy"] == "hier":
                # each bucket pads and reduce-scatters its own flat
                # buffer — the two-hop model prices the exact schedule
                import jax as _jax

                leaves = _jax.tree_util.tree_leaves(state.params)
                segments = [
                    sum(int(_math.prod(
                        getattr(leaves[i], "shape", ()) or ()) or 1)
                        for i in idx)
                    for idx in buckets
                ]
        return bsp_traffic(
            pytree_num_elements(state.params), n,
            strategy=self._build["strategy"], codec=self.codec,
            n_buckets=n_buckets, overlap_frac=overlap,
            n_slices=n_slices, segments=segments,
        )

    def memory_model(self, state):
        """Analytic per-leaf HBM residency of this engine's state
        (utils/flops.py ``MemoryModel``; the memory-side peer of
        ``traffic_model()``, consumed by ``tmpi preflight`` /
        tools/analyze/memory.py). BSP state is replicated on every
        device — shard factor 1 everywhere — except the codec's
        error-feedback residuals, stacked ``[n, ...]`` and sharded over
        the data axes. Factors and specs both come from the engine's
        ShardingRecipe (parallel/recipe.py), so the 1/n claims here can
        never drift from the specs the step actually shards with
        (SHARD003 verifies the pair against the compiled program).
        ``state`` may be abstract (eval_shape structs)."""
        from theanompi_tpu.utils.flops import state_memory_model

        n = 1
        for a in _axes_tuple(self._build["axis_name"]):
            n *= self.mesh.shape[a]
        lf = self.sharding.leaf_factors(state)

        def factor(path, leaf):
            return lf.get(path, (1, None))[0]

        return state_memory_model(
            state, "bsp", n, factor,
            detail={"note": "replicated state; ef stacked per-device"},
            specs={p: s for p, (_f, s) in lf.items()},
        )

    def cost_model(self, state, global_batch: int):
        """XLA cost analysis of this engine's compiled numerics-off
        train step over an abstract global batch (utils/flops.py
        ``CostModel``) — the per-executable FLOPs + HBM bytes behind
        the live ``tmpi_mfu``/attribution gauges (obs/attribution.py).
        Lowering over ShapeDtypeStructs compiles but never executes."""
        import jax as _jax

        from theanompi_tpu.utils.flops import abstract_batch, compiled_cost

        x, y = abstract_batch(self.model, int(global_batch))
        return compiled_cost(self._steps[False], state, x, y,
                             _jax.random.PRNGKey(0))

    def numerics_model(self, state):
        """Numerics declaration (obs/numerics.py): the standard sentinel
        set; no divergence gauge — BSP params are replicated by
        construction (the in-step pmean IS the consistency proof)."""
        from theanompi_tpu.obs.numerics import NumericsModel

        del state  # sentinel set is state-independent for this rule
        return NumericsModel(
            rule="bsp",
            detail={"note": "params replicated in-step; no divergence "
                            "gauge needed"},
        )


def make_bsp_eval_step(
    model: Model, mesh: Mesh, axis_name=DATA_AXIS, input_transform=None,
    eval_views: int = 1,
):
    """Jitted eval step over the mesh: metrics averaged across shards."""
    base = make_eval_step(model, input_transform=input_transform, views=eval_views)
    axes = _axes_tuple(axis_name)
    if all(mesh.shape[a] == 1 for a in axes):
        return jax.jit(base)

    def sharded(state: TrainState, images, labels):
        return lax.pmean(base(state, images, labels), axis_name)

    # eval states carry no codec residuals (the engine strips ef), so
    # the recipe's whole-state spec is replicated
    recipe = _bsp_recipe(mesh, axis_name, None)
    spec = recipe.batch_spec
    mapped = jax.shard_map(
        sharded,
        mesh=mesh,
        in_specs=(recipe.scalar, spec, spec),
        out_specs=recipe.scalar,
        check_vma=_checked_vma(),
    )
    return jax.jit(mapped)
