"""ZeRO-1 data parallelism: optimizer state sharded over the data axis.

BEYOND-PARITY EXTENSION. The reference replicated optimizer state on
every GPU (Theano shared ``vels`` per rank — SURVEY.md §2.1 "two-phase
update"); at modern model sizes the accumulators dominate memory
(Adam on VGG16: ~1.1 GB fp32 of m/v per chip). ZeRO stage 1 (Rajbhandari
et al. 2020, PAPERS.md) shards them: each data-parallel rank owns ONE
``1/n`` segment of the flat parameter buffer and steps only that segment.

TPU-native realization — the whole exchange is two XLA collectives on
the packed buffer (same packing the exchanger strategies use,
``ravel_pytree``; reference: ``BSP_Exchanger``'s pre-concatenated comm
buffer):

    grads   --psum_scatter-->  my summed segment        (ICI, P/n wire)
    segment --optimizer.update (on the local 1/n flat slice)
    params  --all_gather-->    replicated new params    (ICI, P/n wire)

Per-step wire volume is the SAME as a plain allreduce (reduce-scatter +
all-gather IS the ring allreduce, just with the update between the two
halves), so ZeRO-1 costs nothing extra in communication — it only
removes ``(n-1)/n`` of the optimizer-state memory.

Composable with any registry optimizer; the train step mirrors
``theanompi_tpu.train.make_train_step`` semantics (loss/metrics, LR
schedule by epoch, BN state) and is oracle-tested for exact equivalence
with the replicated BSP step (tests/test_zero.py).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh

from theanompi_tpu.models.contract import Model
from theanompi_tpu.ops.optimizers import apply_updates, get_optimizer
from theanompi_tpu.parallel.mesh import DATA_AXIS
from theanompi_tpu.train import loss_and_grads, make_schedule_fn

PyTree = Any


class ZeroTrainState(NamedTuple):
    """Like train.TrainState, but ``opt_state`` holds accumulators over
    the flat 1/n parameter segment owned by each rank (global leaves are
    ``[n * seg]`` sharded over the data axis).

    ``ef``: wire-codec error-feedback residuals (parallel/codec.py) —
    ``{"g": [n, n*seg], "p": [n, seg]}`` sharded over the data axis:
    per-device residuals of the quantized grad reduce-scatter, and the
    per-owner master-correction residual of the quantized param
    all-gather (exact = gathered + ef_p, so the fp32 trajectory
    survives quantized replication). ``()`` when the codec carries no
    state."""

    params: PyTree  # replicated pytree
    model_state: PyTree
    opt_state: PyTree  # flat-segment accumulators, sharded
    step: jax.Array
    ef: PyTree = ()  # codec error-feedback residuals (or ())


def _resolve_optimizer(model, optimizer, fused_update: bool):
    """The one optimizer-resolution rule for ZeRO-1 (shared by the step
    builder and the engine's ShardingRecipe construction, so the spec
    table is derived from the SAME optimizer state the step runs)."""
    if fused_update:
        # fused one-pass epilogue over the flat 1/n segment: ZeRO-1
        # reuses the SAME kernel the replicated engines run, applied to
        # its flat-padded slice (ops/pallas_update.py; state layout
        # matches the unfused rule, so resume crosses the boundary)
        from theanompi_tpu.ops.pallas_update import fuse_optimizer

        if optimizer is not None and not isinstance(optimizer, str):
            raise ValueError(
                "fused_update composes with a named optimizer (the "
                "fused kernel is built from the recipe), not an "
                "Optimizer instance"
            )
        # mirror the classic path's kwarg scoping exactly: an explicit
        # name gets builder DEFAULTS (get_optimizer(optimizer) passes no
        # kwargs), only the recipe's own rule carries its opt_kwargs —
        # a momentum recipe's kwargs must not leak into an explicit
        # "sgd" override
        name = optimizer if isinstance(optimizer, str) else (
            model.recipe.optimizer
        )
        opt_kwargs = (
            {} if isinstance(optimizer, str) else model.recipe.opt_kwargs
        )
        if opt_kwargs.get("clip_norm") is not None:
            # the fused clip is a GLOBAL grad norm; inside this step the
            # optimizer only sees the rank's 1/n flat segment, so each
            # rank would clip by a different partial-norm coefficient —
            # silently wrong numerics, refused instead
            raise ValueError(
                "--fused-update clip_norm is not supported under ZeRO-1:"
                " the fused global-norm clip would be computed over each"
                " rank's local segment, not the global gradient (drop "
                "clip_norm or run the replicated engines)"
            )
        return fuse_optimizer(name, **opt_kwargs)
    return (
        get_optimizer(optimizer)
        if isinstance(optimizer, str)
        else (optimizer or model.optimizer())
    )


def _flat_geometry(model, n: int) -> tuple:
    """``(flat_size, seg)`` of the packed parameter buffer: total
    elements and the padded per-rank segment — from an abstract init
    (nothing materialized)."""
    import math

    params_shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0))[0]
    )
    flat_size = sum(
        math.prod(l.shape)
        for l in jax.tree_util.tree_leaves(params_shapes)
    )
    return flat_size, -(-flat_size // n)  # padded segment per rank


class _Zero1Setup(NamedTuple):
    """The ONE derivation of a ZeRO-1 configuration's codec, optimizer,
    flat geometry, and ShardingRecipe — shared by the step builder and
    the engine so the declared spec table can only describe the program
    that compiled (no second copy to drift)."""

    codec: Any
    use_ef: bool
    opt: Any
    flat_size: int
    seg: int
    opt_shapes: Any
    recipe: Any  # parallel/recipe.ShardingRecipe


def _zero1_setup(model, mesh, axis_name, optimizer, fused_update,
                 wire_codec) -> _Zero1Setup:
    from theanompi_tpu.parallel.codec import get_codec
    from theanompi_tpu.parallel.recipe import ShardingRecipe

    n = dict(zip(mesh.axis_names, mesh.devices.shape))[axis_name]
    codec = get_codec(wire_codec)
    if n == 1:
        codec = get_codec(None)  # no peers, no wire to compress
    use_ef = codec.active and codec.error_feedback
    opt = _resolve_optimizer(model, optimizer, fused_update)
    flat_size, seg = _flat_geometry(model, n)
    opt_shapes = jax.eval_shape(
        lambda: opt.init(jnp.zeros((seg,), jnp.float32))
    )
    return _Zero1Setup(
        codec=codec, use_ef=use_ef, opt=opt, flat_size=flat_size,
        seg=seg, opt_shapes=opt_shapes,
        recipe=ShardingRecipe.zero1(mesh, axis_name, opt_shapes, use_ef),
    )


def make_zero1_train_step(
    model: Model,
    mesh: Mesh,
    *,
    axis_name: str = DATA_AXIS,
    optimizer=None,
    steps_per_epoch: int = 1,
    input_transform: Optional[Callable] = None,
    donate: bool = True,
    numerics: bool = False,
    wire_codec=None,
    fused_update: bool = False,
    _setup: "Optional[_Zero1Setup]" = None,
):
    """Build ``(init_state, train_step)`` for ZeRO-1 BSP training over
    ``mesh``'s ``axis_name``.

    ``_setup``: a pre-derived :class:`_Zero1Setup` for this EXACT
    configuration (the engine passes its own so builder and engine
    share one derivation — never pass one built from different args).

    ``init_state(key) -> ZeroTrainState`` (host-callable; jitted and
    sharded). ``train_step(state, x, y, rng) -> (state, metrics)`` with
    ``x``/``y`` sharded over the axis (the global batch, exactly like
    parallel/bsp.py). ``optimizer`` defaults to the model recipe's.
    """
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axis_name not in sizes:
        raise ValueError(f"axis {axis_name!r} not in mesh axes {mesh.axis_names}")
    if len(mesh.axis_names) > 1:
        # collectives here run over axis_name ONLY; on a multi-axis mesh
        # the P() out-specs would stamp dcn-divergent params as
        # replicated with no error
        raise ValueError(
            f"ZeRO-1 runs on a 1-D data mesh; got axes {mesh.axis_names} "
            "(for multi-slice, flatten to one data axis — XLA still "
            "routes the collectives hierarchically over ICI/DCN)"
        )
    n = sizes[axis_name]
    # THE one config derivation (codec, optimizer, geometry, recipe) —
    # the engine hands its own _Zero1Setup down, so the declared spec
    # table and the compiled program share one derivation
    setup = _setup if _setup is not None else _zero1_setup(
        model, mesh, axis_name, optimizer, fused_update, wire_codec)
    codec, use_ef, opt = setup.codec, setup.use_ef, setup.opt
    flat_size, seg = setup.flat_size, setup.seg
    schedule_lr = make_schedule_fn(model, steps_per_epoch)
    recipe = setup.recipe

    def _seg_slice(flat, rank):
        padded = jnp.pad(flat, (0, n * seg - flat_size))
        return lax.dynamic_slice(padded, (rank * seg,), (seg,))

    def sharded_init(key):
        params, model_state = model.init(key)
        opt_state = opt.init(jnp.zeros((seg,), jnp.float32))
        ef = (
            {"g": jnp.zeros((1, n * seg), jnp.float32),
             "p": jnp.zeros((1, seg), jnp.float32)}
            if use_ef else ()
        )
        return ZeroTrainState(
            params, model_state, opt_state, jnp.zeros((), jnp.int32), ef
        )

    state_specs = recipe.state_spec(ZeroTrainState)
    init_state = jax.jit(
        jax.shard_map(
            sharded_init,
            mesh=mesh,
            in_specs=(recipe.scalar,),
            out_specs=state_specs,
            check_vma=False,
        )
    )

    def sharded_step(state, images, labels, rng):
        if input_transform is not None:
            images = input_transform(images)

        loss, logits, new_model_state, grads = loss_and_grads(
            model, state.params, state.model_state, images, labels, rng
        )
        # BN running stats etc. are per-shard batch statistics — average
        # them across the data axis exactly like parallel/bsp.py (the
        # P() out-spec under check_vma=False would otherwise silently
        # emit device-divergent state as if replicated)
        new_model_state = jax.tree_util.tree_map(
            lambda s: lax.pmean(s, axis_name), new_model_state
        )

        rank = lax.axis_index(axis_name)
        flat_g, _ = ravel_pytree(grads)
        flat_g = jnp.pad(flat_g.astype(jnp.float32), (0, n * seg - flat_size))
        new_ef = state.ef
        if codec.active:
            # compressed reduce-scatter: quantize this rank's LOCAL
            # contribution (error-feedback residual re-injected first),
            # accumulate in fp32 — the 1611.04255 recipe on the scatter
            # half of the exchange
            if use_ef:
                flat_g = flat_g + state.ef["g"][0]
            g_wire = codec.qdq(flat_g)
            if use_ef:
                new_ef = dict(new_ef, g=(flat_g - g_wire)[None])
            flat_g = g_wire
        # reduce-scatter: each rank receives the SUM of its segment
        g_seg = lax.psum_scatter(flat_g, axis_name, scatter_dimension=0,
                                 tiled=True) / n

        flat_p, unravel = ravel_pytree(state.params)
        p_seg = _seg_slice(flat_p.astype(jnp.float32), rank)
        if use_ef:
            # master correction: the replicated params are the QUANTIZED
            # gather of last step; exact segment = quantized + residual,
            # so the optimizer walks the fp32 trajectory while replicas
            # carry the compressed copy
            p_seg = p_seg + state.ef["p"][0]

        lr = schedule_lr(state.step)
        if opt.apply is not None:
            # fused one-pass segment update (ops/pallas_update.py); the
            # gauges' update segment is reconstructed in the numerics
            # block below
            new_p_seg, new_opt = opt.apply(g_seg, state.opt_state, p_seg, lr)
            updates = None
        else:
            updates, new_opt = opt.update(g_seg, state.opt_state, p_seg, lr)
            new_p_seg = apply_updates(p_seg, updates)

        gather_seg = new_p_seg
        if codec.active:
            # compressed all-gather: every rank (owner included) adopts
            # the dequantized segment, so params stay bit-replicated;
            # the owner's residual preserves the exact master above
            gather_seg = codec.qdq(new_p_seg)
            if use_ef:
                new_ef = dict(new_ef, p=(new_p_seg - gather_seg)[None])
        new_flat = lax.all_gather(gather_seg, axis_name, tiled=True)[:flat_size]
        new_params = unravel(new_flat.astype(flat_p.dtype))

        metrics = {
            "loss": lax.pmean(loss, axis_name),
            "lr": lr,
            **{k: lax.pmean(v, axis_name)
               for k, v in model.metrics(logits, labels).items()},
        }
        if numerics:
            # sentinels over the SHARDED flat segments (obs/numerics.py
            # semantics): each rank owns one 1/n slice of the summed
            # grads/updates, so the global norms are psums of local
            # squared sums — scalar collectives only. param_norm reads
            # the freshly all-gathered full buffer (replicated), and
            # the non-finite count covers the synced grads exactly like
            # the replicated engines'.
            if updates is None:
                from theanompi_tpu.ops.optimizers import update_delta

                updates = update_delta(new_p_seg, p_seg)
            gsq = lax.psum(jnp.sum(jnp.square(g_seg)), axis_name)
            usq = lax.psum(
                jnp.sum(jnp.square(updates.astype(jnp.float32))), axis_name
            )
            nonf = lax.psum(
                jnp.sum((~jnp.isfinite(g_seg)).astype(jnp.float32)), axis_name
            )
            metrics = {
                **metrics,
                "nm_grad_norm": jnp.sqrt(gsq),
                "nm_update_norm": jnp.sqrt(usq),
                "nm_param_norm": jnp.sqrt(
                    jnp.sum(jnp.square(new_flat.astype(jnp.float32)))
                ),
                "nm_nonfinite": nonf,
            }
        return (
            ZeroTrainState(new_params, new_model_state, new_opt,
                           state.step + 1, new_ef),
            metrics,
        )

    train_step = jax.jit(
        jax.shard_map(
            sharded_step,
            mesh=mesh,
            in_specs=(state_specs, recipe.batch_spec, recipe.batch_spec,
                      recipe.scalar),
            out_specs=(state_specs, recipe.scalar),
            check_vma=False,
        ),
        # donate like parallel/bsp.py: without it every dispatch holds a
        # second params+opt copy, undercutting the memory saving that is
        # this module's point (donate=False for oracle tests that reuse
        # the input state)
        donate_argnums=(0,) if donate else (),
    )
    return init_state, train_step


class ZeroEngine:
    """Driver-protocol wrapper over the ZeRO-1 step, so ``tmpi BSP ...
    --zero 1`` runs optimizer-state-sharded training through the same
    ``run_training`` loop (recorder, loader, checkpoint/resume) as plain
    BSP. Eval reuses the BSP eval step on a view of the state WITHOUT
    the sharded accumulators (params/BN state are replicated), so no
    gather is paid per validation batch."""

    name = "zero1"
    exchange_every = 0
    # donation audit (ISSUE 2): make_zero1_train_step donates by default
    # (the sharded opt state + replicated params reuse their buffers).
    # The claim is now VERIFIED statically: the SPMD analyzer (ISSUE 7)
    # reads the lowered step's donated_invars and fails `tmpi lint`
    # (SPMD201) if this flag and the program disagree; the
    # reduce_scatter+all_gather schedule itself is pinned by
    # tools/analyze/golden/zero1_*.json (SPMD003).
    donates_state = True

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        steps_per_epoch: int = 1,
        input_transform=None,
        eval_views: int = 1,
        wire_codec=None,
        fused_update: bool = False,
    ):
        from theanompi_tpu.parallel.bsp import make_bsp_eval_step
        from theanompi_tpu.parallel.codec import get_codec

        self.model = model
        self.mesh = mesh
        self.codec = get_codec(wire_codec)
        # ONE _zero1_setup derivation, handed to every step variant the
        # engine builds (one per numerics flag) via _build — the
        # declared spec table (sharding analyzer, memory_model, topology
        # stamp) and the compiled programs share it by construction
        setup = _zero1_setup(model, mesh, DATA_AXIS, None,
                             bool(fused_update), self.codec)
        self.sharding = setup.recipe
        self._build = dict(steps_per_epoch=steps_per_epoch,
                           input_transform=input_transform,
                           wire_codec=self.codec,
                           fused_update=bool(fused_update),
                           _setup=setup)
        self._init, step = make_zero1_train_step(model, mesh, **self._build)
        self._steps = {False: step}
        self._eval = make_bsp_eval_step(
            model, mesh, input_transform=input_transform, eval_views=eval_views,
        )

    def init_state(self, rng) -> ZeroTrainState:
        return self._init(rng)

    def train_step(self, state, images, labels, rng, numerics: bool = False):
        numerics = bool(numerics)
        if numerics not in self._steps:
            _, self._steps[numerics] = make_zero1_train_step(
                self.model, self.mesh, numerics=numerics, **self._build
            )
        return self._steps[numerics](state, images, labels, rng)

    def exchange(self, state):
        return state

    def eval_step(self, state, images, labels):
        from theanompi_tpu.train import TrainState

        view = TrainState(state.params, state.model_state, (), state.step)
        return self._eval(view, images, labels)

    def get_step(self, state) -> int:
        from theanompi_tpu.parallel.mesh import first_local_value

        return int(first_local_value(state.step))

    def sharding_recipe(self):
        """The engine's ShardingRecipe (parallel/recipe.py) — declared
        spec table for the sharding analyzer and the topology stamp."""
        return self.sharding

    def elastic_spec(self) -> dict:
        """Per-leaf reshard policies for the topology manifest
        (utils/checkpoint.load_resharded). ZeRO is THE shape-changing
        case: the flat optimizer accumulators are padded to ``n``
        equal segments, so their global length is mesh-dependent
        (``n * ceil(F/n)``) — the ``flat_padded`` policy moves the
        logical ``F``-element prefix and re-pads for the target world.
        Params/BN state are replicated (``global``); error-feedback
        residuals are per-device and reset."""
        flat_size, _ = _flat_geometry(self.model, self.mesh.devices.size)
        return {"policies": {
            ".opt_state": {"policy": "flat_padded",
                           "logical": int(flat_size)},
            ".ef": {"policy": "reset"},
        }}

    def traffic_model(self, state):
        """ZeRO-1 wire model (obs/comm.py): psum_scatter + all_gather
        over the flat fp32 buffer padded to n segments — same volume as
        the plain allreduce, which is the module's headline claim; the
        codec compresses both halves."""
        from theanompi_tpu.obs.comm import pytree_num_elements, zero1_traffic
        from theanompi_tpu.parallel.mesh import slice_topology

        return zero1_traffic(
            pytree_num_elements(state.params), self.mesh.devices.size,
            codec=self.codec, n_slices=slice_topology(self.mesh)[0],
        )

    def memory_model(self, state):
        """Analytic per-leaf HBM residency (utils/flops.py
        ``MemoryModel``; see BSPEngine.memory_model). ZeRO-1's point IS
        this table: params/BN state replicated (factor 1), the flat
        optimizer accumulators sharded ``1/n`` over the data axis, the
        codec's error-feedback residuals likewise per-device. Factors
        and specs come from the engine's ShardingRecipe — the 1/n claim
        and the step's actual sharding are one declaration (SHARD003
        checks it against the compiled program)."""
        from theanompi_tpu.utils.flops import state_memory_model

        n = self.mesh.devices.size
        lf = self.sharding.leaf_factors(state)

        def factor(path, leaf):
            return lf.get(path, (1, None))[0]

        return state_memory_model(
            state, "zero1", n, factor,
            detail={"note": "optimizer state flat-sharded 1/n "
                            "(the ZeRO-1 memory claim)"},
            specs={p: s for p, (_f, s) in lf.items()},
        )

    def cost_model(self, state, global_batch: int):
        """XLA cost analysis of the compiled ZeRO-1 step over an
        abstract global batch (utils/flops.py ``CostModel``; see
        BSPEngine.cost_model) — scatter/update/gather included, since
        they are inside the same executable."""
        import jax as _jax

        from theanompi_tpu.utils.flops import abstract_batch, compiled_cost

        x, y = abstract_batch(self.model, int(global_batch))
        return compiled_cost(self._steps[False], state, x, y,
                             _jax.random.PRNGKey(0))

    def numerics_model(self, state):
        """Numerics declaration (obs/numerics.py): standard sentinels
        computed over the sharded flat segments (scalar psums); no
        divergence gauge — the all_gather re-replicates params every
        step, so sharded-consistency holds by construction."""
        from theanompi_tpu.obs.numerics import NumericsModel

        del state
        return NumericsModel(
            rule="zero1",
            detail={"note": "segment-sharded norms via scalar psums; "
                            "params re-replicated by the in-step "
                            "all_gather"},
        )
