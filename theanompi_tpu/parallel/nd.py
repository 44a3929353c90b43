"""NDEngine — the launchable N-D parallelism rule engine.

BEYOND-PARITY EXTENSION. Round 3 left tensor/sequence/pipeline/expert
parallelism as a *library* (``make_nd_train_step`` etc.) reachable only
from tests; this engine gives them the same driver protocol the sync
rules use (``init_state`` / ``train_step`` / ``eval_step`` /
``place_batch``), so ``launch/worker.py::run_training`` — recorder,
prefetch loader, checkpointing, resume, CLI — drives an LM sharded over
any of:

- ``dp`` (data axis) x ``tp`` (Megatron tensor axis) x ``sp`` (ring /
  Ulysses sequence axis) for the dense :class:`TransformerLMModel`;
- ``pipe`` (GPipe pipeline axis, microbatched) x ``dp``;
- ``expert`` (Switch-MoE all-to-all axis, doubling as the batch axis)
  x ``dp`` (data parallelism over the expert groups — the batch dim
  shards over (dp, expert) jointly) x ``tp`` (Megatron sharding WITHIN
  each expert/attention block) x ``sp`` for :class:`MoELMModel`.

CLI: ``tmpi BSP 8 theanompi_tpu.models.lm TransformerLMModel --tp 2
--sp 2`` (see cli.py). The engine owns batch *placement* because its
token sharding — ``P(dp, sp)``, or microbatch-major ``[M, B, T]`` for
pipelines — differs from the image engines' leading-dim-only layout.

Gradient sync follows the universal spec rule
(models/transformer.py::sync_grads_by_spec) under ``check_vma=False``
(see train.make_train_step's AD-semantics note); the optimizer, LR
schedule, and step counter mirror ``train.make_train_step`` so recipes
and checkpoints behave identically across engines.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from theanompi_tpu.models.transformer import (
    nd_spec_setup,
    opt_state_specs,
    sync_grads_by_spec,
)
from theanompi_tpu.ops.optimizers import apply_updates
from theanompi_tpu.train import make_schedule_fn

PyTree = Any

# canonical axis names for the launchable ND meshes (the mesh builder in
# launch/worker.py uses these; tests may use their own)
DP_AXIS = "data"
TP_AXIS = "model"
SP_AXIS = "seq"


class NDTrainState(NamedTuple):
    """Params + optimizer state + step. ``params`` leaves are sharded
    per the engine's param specs (tp/pipe/expert sharding or
    replicated); ``opt_state`` accumulators shard exactly like their
    parameters (transformer.py::opt_state_specs).

    ``ef``: wire-codec error-feedback residuals (parallel/codec.py) of
    the sharded-axis grad psums. Each leaf carries a leading stack dim
    covering exactly the axes that leaf is PSUMMED over (the complement
    of its sharded axes), so every device owns its own residual block —
    spec ``P(psum_axes, *leaf_spec)``. ``()`` when the codec carries no
    state."""

    params: PyTree
    opt_state: PyTree
    step: jax.Array
    ef: PyTree = ()


class NDEngine:
    """Driver-protocol engine over the N-D parallel LM step builders.

    Exactly one of three branches is active:

    - dense ND: any of ``dp_axis``/``tp_axis``/``sp_axis``
    - pipeline: ``pipe_axis`` (+ optional ``dp_axis``); tokens are
      reshaped host-side to microbatch-major ``[M, B/M, T]``
    - expert:   ``ep_axis`` (+ optional ``dp_axis``/``sp_axis``/
      ``tp_axis``); the batch dim shards over (dp, expert) jointly
    """

    name = "nd"
    exchange_every = 0
    # overridden per-instance from the donate flag; the SPMD analyzer
    # (ISSUE 7) verifies whatever is declared against the lowered step's
    # donated_invars (SPMD201) and pins the per-leaf dp-axis psum
    # schedule in tools/analyze/golden/nd_*.json
    donates_state = True

    def __init__(
        self,
        model,
        mesh: Mesh,
        *,
        steps_per_epoch: int = 1,
        dp_axis: Optional[str] = None,
        tp_axis: Optional[str] = None,
        sp_axis: Optional[str] = None,
        ep_axis: Optional[str] = None,
        pipe_axis: Optional[str] = None,
        microbatches: Optional[int] = None,
        pp_interleave: int = 1,
        donate: bool = True,
        wire_codec=None,
        fused_update: bool = False,
    ):
        if not hasattr(model, "arch"):
            raise ValueError(
                f"NDEngine needs an LM model exposing .arch (models/lm.py); "
                f"got {type(model).__name__}"
            )
        arch = model.arch
        self.model = model
        self.mesh = mesh
        self.microbatches = None
        self.schedule = None  # pipeline branch: schedule_report dict
        self._dp_axis = dp_axis  # kept for the analytic traffic model
        if fused_update:
            # fused epilogue over the spec-sharded leaves: inside
            # shard_map each leaf is its LOCAL shard, so the one-pass
            # kernel runs unchanged (ops/pallas_update.py). Refuses the
            # LM recipes' adam loudly — no fused kernel for it.
            from theanompi_tpu.ops.pallas_update import fuse_optimizer

            if model.recipe.opt_kwargs.get("clip_norm") is not None:
                # the fused clip is a GLOBAL grad norm; this step's
                # leaves are spec-sharded local shards, so each device
                # would clip by its own partial-norm coefficient
                raise ValueError(
                    "--fused-update clip_norm is not supported on the "
                    "ND engine: the fused global-norm clip would be "
                    "computed over each device's local param shards, "
                    "not the global gradient (drop clip_norm)"
                )
            opt = fuse_optimizer(model.recipe.optimizer,
                                 **model.recipe.opt_kwargs)
        else:
            opt = model.optimizer()
        schedule_lr = make_schedule_fn(model, steps_per_epoch)
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

        if pipe_axis is not None:
            if ep_axis:
                raise ValueError(
                    "the pipeline branch composes with dp, tp and sp "
                    "(pipe x expert is not implemented)"
                )
            from theanompi_tpu.parallel.pipeline import (
                make_pipeline_loss,
                pipeline_param_specs,
                pipeline_schedule_report,
                stack_pipeline_params,
                validate_pp_mesh,
            )

            axes, n_total = validate_pp_mesh(
                arch, mesh, pipe_axis, dp_axis, pp_interleave, tp_axis,
                sp_axis,
            )
            param_specs = pipeline_param_specs(pipe_axis, tp_axis)
            loss_fn = make_pipeline_loss(
                arch, pipe_axis, pp_interleave, tp_axis, sp_axis
            )
            n_pipe = sizes[pipe_axis]
            init_params = lambda key: stack_pipeline_params(  # noqa: E731
                arch.init(key), n_stages=n_pipe, interleave=pp_interleave
            )
            self.microbatches = int(microbatches or n_pipe)
            if pp_interleave > 1 and self.microbatches % n_pipe:
                raise ValueError(
                    f"--pp-interleave needs --microbatches "
                    f"({self.microbatches}) in groups of --pp ({n_pipe})"
                )
            self.schedule = pipeline_schedule_report(
                n_pipe, self.microbatches, pp_interleave
            )
            if jax.process_index() == 0:  # once per pod, not per host
                print(
                    f"[nd] pipeline schedule: {self.schedule['ticks']} ticks, "
                    f"bubble {self.schedule['bubble_fraction']:.1%} "
                    f"(interleave={pp_interleave}; suggest >= "
                    f"{self.schedule['suggested_microbatches']} microbatches "
                    f"for <10%)"
                )
            # [M, B, T]: M replicated, B on dp, T on sp
            tok_entry = dp_axis
            microbatched = True
            batch_axes = (dp_axis,) if dp_axis else ()
        elif ep_axis is not None:
            from theanompi_tpu.models.moe import ep_spec_setup

            axes, n_total, param_specs = ep_spec_setup(
                arch, mesh, ep_axis, sp_axis, dp_axis, tp_axis
            )
            loss_fn = lambda p, t: arch.loss(  # noqa: E731
                p, t, sp_axis, ep_axis=ep_axis, dp_axis=dp_axis,
                tp_axis=tp_axis,
            )
            init_params = arch.init
            # batch dim over (dp, ep) jointly, dp-major: host slices
            # stay contiguous under multi-controller feeds
            tok_entry = (dp_axis, ep_axis) if dp_axis else ep_axis
            microbatched = False
            batch_axes = ((dp_axis,) if dp_axis else ()) + (ep_axis,)
        else:
            axes, n_total, param_specs = nd_spec_setup(
                arch, mesh, dp_axis, tp_axis, sp_axis
            )
            loss_fn = lambda p, t: arch.loss(p, t, sp_axis, tp_axis=tp_axis)  # noqa: E731
            init_params = arch.init
            tok_entry = dp_axis
            microbatched = False
            batch_axes = (dp_axis,) if dp_axis else ()

        from theanompi_tpu.parallel.codec import get_codec

        codec = get_codec(wire_codec)
        if n_total == 1:
            codec = get_codec(None)  # no sync collectives, no wire
        self.codec = codec
        use_ef = codec.active and codec.error_feedback

        from theanompi_tpu.parallel.recipe import (
            ShardingRecipe,
            psum_axes as _recipe_psum_axes,
        )

        _is_spec = lambda x: isinstance(x, P)  # noqa: E731
        self._spec_leaves = jax.tree_util.tree_leaves(
            param_specs, is_leaf=_is_spec
        )
        # which leaves actually cross a wire (psummed over >= 1 axis) —
        # the complement rule lives in parallel/recipe.py::psum_axes
        # (same rule transformer.sync_grads_by_spec applies)
        self._wire_axes = [_recipe_psum_axes(s, tuple(axes))
                           for s in self._spec_leaves]
        self._ef_stack = [
            int(np.prod([sizes[a] for a in ax_t])) if ax_t else 1
            for ax_t in self._wire_axes
        ]

        opt_template = jax.eval_shape(
            lambda: opt.init(jax.eval_shape(init_params, jax.random.PRNGKey(0)))
        )
        # THE spec source (parallel/recipe.py): the per-leaf param
        # specs (model spec setup), their like-sharded optimizer
        # accumulators, the ef residual stacks (leading dim over each
        # leaf's psummed axes), and the token sharding — one recipe the
        # step, analyzer, memory model, and topology stamp all consume
        self.sharding = ShardingRecipe.nd(
            mesh, tuple(axes), param_specs, opt_template, use_ef,
            tok_entry, sp_axis, microbatched=microbatched,
        )
        state_specs = self.sharding.state_spec(NDTrainState)
        tok_spec = self.sharding.batch_spec
        self._state_specs = state_specs
        self._init_params = init_params
        self._opt = opt
        self._tok_spec = tok_spec
        self._tok_sharding = NamedSharding(mesh, tok_spec)
        self.donates_state = bool(donate)
        # multi-controller feed fraction (lo, hi, B): set by
        # host_batch_part when hosts load only their slice of the global
        # batch; None = every host feeds the full batch (replicated
        # tokens, or the pipeline's interleaved microbatch-major layout)
        self._part = None

        wire_flags = [bool(ax_t) for ax_t in self._wire_axes]

        def compress_grads(grads, ef):
            """Wire codec over the leaves that actually cross an axis
            (per-leaf block quantize + error feedback); fully-sharded
            leaves (no psum) pass through untouched."""
            g_leaves, treedef = jax.tree_util.tree_flatten(grads)
            if use_ef:
                ef_leaves = jax.tree_util.tree_leaves(ef)
                out_g, out_ef = [], []
                for g, r, w in zip(g_leaves, ef_leaves, wire_flags):
                    if not w:
                        out_g.append(g)
                        out_ef.append(r)
                        continue
                    q, nr = codec.compress_leaf(g, r[0])
                    out_g.append(q)
                    out_ef.append(nr[None])
                return (
                    jax.tree_util.tree_unflatten(treedef, out_g),
                    jax.tree_util.tree_unflatten(treedef, out_ef),
                )
            out_g = [
                codec.compress_leaf(g, None)[0] if w else g
                for g, w in zip(g_leaves, wire_flags)
            ]
            return jax.tree_util.tree_unflatten(treedef, out_g), ef

        def make_sharded_step(numerics: bool):
            def sharded_step(state: NDTrainState, tokens, rng):
                del rng  # no dropout in the LM stack; kept for protocol parity
                loss, grads = jax.value_and_grad(loss_fn)(state.params, tokens)
                new_ef = state.ef
                if codec.active:
                    # quantize each device's LOCAL contribution BEFORE
                    # the sharded-axis psums (EQuARX recipe; fp32
                    # accumulation inside the collective)
                    grads, new_ef = compress_grads(grads, state.ef)
                grads = sync_grads_by_spec(grads, param_specs, axes, n_total)
                for a in batch_axes:
                    loss = lax.pmean(loss, a)  # report the global batch mean
                lr = schedule_lr(state.step)
                if opt.apply is not None:
                    # fused one-pass update (ops/pallas_update.py); the
                    # gauges' update tree is reconstructed below, only
                    # in the numerics variant
                    new_params, new_opt = opt.apply(
                        grads, state.opt_state, state.params, lr
                    )
                    updates = None
                else:
                    updates, new_opt = opt.update(
                        grads, state.opt_state, state.params, lr
                    )
                    new_params = apply_updates(state.params, updates)
                metrics = {"loss": loss, "lr": lr}
                if numerics:
                    if updates is None:
                        from theanompi_tpu.ops.optimizers import (
                            update_delta,
                        )

                        updates = update_delta(new_params, state.params)
                    # sentinels over SPEC-SHARDED trees: per-leaf local
                    # squared sums psummed over exactly the axes that
                    # leaf shards over (obs/numerics.py) — scalar
                    # collectives, no gather of the sharded params
                    from theanompi_tpu.obs.numerics import sharded_sentinels

                    metrics = {
                        **metrics,
                        **sharded_sentinels(grads, updates, new_params,
                                            param_specs),
                    }
                return (
                    NDTrainState(new_params, new_opt, state.step + 1,
                                 new_ef),
                    metrics,
                )

            return sharded_step

        def jit_step(numerics: bool):
            return jax.jit(
                jax.shard_map(
                    make_sharded_step(numerics),
                    mesh=mesh,
                    in_specs=(state_specs, tok_spec, self.sharding.scalar),
                    out_specs=(state_specs, self.sharding.scalar),
                    check_vma=False,
                ),
                donate_argnums=(0,) if donate else (),
            )

        self._jit_step = jit_step
        self._steps = {False: jit_step(False)}

        def sharded_eval(state: NDTrainState, tokens):
            loss = loss_fn(state.params, tokens)
            for a in batch_axes:
                loss = lax.pmean(loss, a)
            return {"loss": loss}

        self._eval = jax.jit(
            jax.shard_map(
                sharded_eval,
                mesh=mesh,
                in_specs=(state_specs, tok_spec),
                out_specs=self.sharding.scalar,
                check_vma=False,
            )
        )

    # -- driver protocol ------------------------------------------------
    @property
    def state_shardings(self) -> NDTrainState:
        """Per-leaf NamedShardings of the train state — used by the
        driver to re-place a restored (host-numpy) checkpoint under
        multi-controller launch, where a plain ``jnp.asarray`` would
        produce process-local arrays the SPMD step cannot consume."""
        return jax.tree_util.tree_map(
            lambda spec: NamedSharding(self.mesh, spec),
            self._state_specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    def init_state(self, rng) -> NDTrainState:
        # jit with out_shardings: each process computes only its shards
        # (multi-controller correct, and the replicated-then-reshard
        # device_put round-trip is gone — init never materializes the
        # full parameter set per device)
        def build(rng):
            params = self._init_params(rng)
            ef: Any = ()
            if self.codec.active and self.codec.error_feedback:
                leaves, treedef = jax.tree_util.tree_flatten(params)
                ef = jax.tree_util.tree_unflatten(
                    treedef,
                    [jnp.zeros((stk, *p.shape), jnp.float32)
                     for p, stk in zip(leaves, self._ef_stack)],
                )
            return NDTrainState(
                params, self._opt.init(params), jnp.zeros((), jnp.int32), ef
            )

        return jax.jit(build, out_shardings=self.state_shardings)(rng)

    def host_batch_part(self, global_batch: int):
        """The slice of the global ``[B, T]`` token batch THIS controller
        process must produce (None = the full batch) — the ND analogue of
        ``mesh.host_local_batch_slice`` (reference: per-rank loader feed,
        ``lib/proc_load_mpi.py``), derived from the token sharding itself:

        - batch dim sharded over a process-spanning axis (dp / expert):
          the contiguous row range covered by this process's addressable
          devices;
        - batch dim replicated across processes (pure tp/sp) or the
          pipeline's microbatch-major layout (whose host rows interleave
          dp shards non-contiguously): every host feeds the full batch —
          tokens are int32 and host-cheap, and placement still moves only
          the addressable shards onto devices (zero cross-host copies).
        """
        if jax.process_count() == 1:
            return None
        if self.microbatches is not None:
            return None
        spec0 = self._tok_spec[0]
        if spec0 is None:
            return None
        idx_map = NamedSharding(
            self.mesh, self.sharding.leading_batch_spec
        ).addressable_devices_indices_map((global_batch,))
        rows: set[int] = set()
        for idx in idx_map.values():
            s = idx[0]
            rows.update(range(s.start or 0, s.stop if s.stop is not None
                              else global_batch))
        lo, hi = min(rows), max(rows) + 1
        if len(rows) != hi - lo:
            return None  # non-contiguous coverage: feed the full batch
        part = (lo, hi, global_batch)
        if self._part is not None and (
            self._part[0] * global_batch != lo * self._part[2]
            or self._part[1] * global_batch != hi * self._part[2]
        ):
            raise ValueError(
                f"inconsistent host batch fractions {self._part} vs {part} "
                "(train/val batches must shard proportionally)"
            )
        self._part = part
        return None if (lo, hi) == (0, global_batch) else slice(lo, hi)

    def _put_global(self, x: np.ndarray, sharding: NamedSharding, batch_dim: int):
        """Place host rows as a (possibly multi-process) global array.

        Single-controller: plain sharded device_put. Multi-controller:
        assemble the global array from this process's rows — the callback
        maps each addressable device's global index window into the host
        buffer, shifted by the host's feed offset."""
        if jax.process_count() == 1:
            return jax.device_put(x, sharding)
        x = np.ascontiguousarray(x)
        if self._part is not None and self._part[1] - self._part[0] != self._part[2]:
            lo, hi, B = self._part
            g = x.shape[batch_dim] * B // (hi - lo)
            off = g * lo // B
        else:
            g, off = x.shape[batch_dim], 0
        gshape = list(x.shape)
        gshape[batch_dim] = g

        def cb(index):
            idx = list(index)
            s = idx[batch_dim]
            idx[batch_dim] = slice(
                (s.start or 0) - off,
                (s.stop if s.stop is not None else g) - off,
            )
            return x[tuple(idx)]

        return jax.make_array_from_callback(tuple(gshape), sharding, cb)

    def _split_microbatches(self, x):
        """Reshape the batch dim to microbatch-major ``[M, B/M]`` (no-op
        for non-pipeline engines) — the ONE place the pipeline host
        layout is defined."""
        if self.microbatches is None:
            return x
        M = self.microbatches
        if x.shape[0] % M:
            raise ValueError(
                f"global batch {x.shape[0]} must be divisible by "
                f"microbatches={M}"
            )
        return x.reshape(M, x.shape[0] // M, *x.shape[1:])

    def place_batch(self, x, y):
        """Host tokens ``[B, T]`` -> device, sharded per the engine's
        token spec (microbatch-major for pipelines). Returns the SAME
        device array for x and y (labels are the tokens; zero extra
        transfer)."""
        del y  # labels ARE the tokens
        x = self._split_microbatches(np.asarray(x))
        t = self._put_global(
            x, self._tok_sharding,
            batch_dim=1 if self.microbatches is not None else 0,
        )
        return t, t

    def train_step(self, state, tokens, labels, rng, numerics: bool = False):
        del labels
        numerics = bool(numerics)
        if numerics not in self._steps:
            self._steps[numerics] = self._jit_step(numerics)
        return self._steps[numerics](state, tokens, rng)

    def exchange(self, state):
        return state

    def eval_step(self, state, tokens, labels):
        del labels
        return self._eval(state, tokens)

    def get_step(self, state) -> int:
        from theanompi_tpu.parallel.mesh import first_local_value

        return int(first_local_value(state.step))

    def sharding_recipe(self):
        """The engine's ShardingRecipe (parallel/recipe.py) — declared
        spec table for the sharding analyzer and the topology stamp."""
        return self.sharding

    def elastic_spec(self) -> dict:
        """Per-leaf reshard policies for the topology manifest
        (utils/checkpoint.load_resharded). ND params and their
        like-sharded optimizer accumulators keep mesh-invariant GLOBAL
        shapes (the sharding divides them, it never pads them), so the
        default ``global`` bounds-based move is exact for any axis
        regrouping; only the per-device error-feedback residual stacks
        are topology-bound and reset."""
        return {"policies": {".ef": {"policy": "reset"}}}

    def traffic_model(self, state):
        """Approximate ND wire model (obs/comm.py): the dp-axis grad
        allreduce over each device's local (1/shard_ways) param slice.
        Activation collectives (tp psum, sp ring/all-to-all, pipeline
        ppermute, MoE all-to-all) are NOT modeled — the returned model
        is flagged ``approx`` in its detail."""
        from theanompi_tpu.obs.comm import nd_traffic, pytree_num_elements
        from theanompi_tpu.parallel.mesh import slice_topology

        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        dp = sizes.get(self._dp_axis, 1) if self._dp_axis else 1
        shard_ways = max(1, self.mesh.devices.size // dp)
        return nd_traffic(
            pytree_num_elements(state.params), dp, shard_ways=shard_ways,
            codec=self.codec, n_slices=slice_topology(self.mesh)[0],
        )

    def memory_model(self, state):
        """Analytic per-leaf HBM residency (utils/flops.py
        ``MemoryModel``; see BSPEngine.memory_model). The ND engine is
        the spec-driven case: each leaf's shard factor is the mesh
        extent over the axes its own PartitionSpec names
        (``self._state_specs`` — the same per-leaf specs the
        checkpoint topology manifest stamps), so tp/pipe/expert-sharded
        params and their like-sharded accumulators divide by their
        sharding ways while replicated leaves count in full. Factors
        and specs are resolved per STATE leaf by the recipe, so prefix
        specs broadcast correctly (SHARD003 verifies the table against
        the compiled program)."""
        from theanompi_tpu.utils.flops import state_memory_model

        lf = self.sharding.leaf_factors(state)

        def factor(path, leaf):
            return lf.get(path, (1, None))[0]

        return state_memory_model(
            state, "nd", self.mesh.devices.size, factor,
            detail={"note": "per-leaf PartitionSpec extents "
                            "(tp/pipe/expert sharding)"},
            specs={p: s for p, (_f, s) in lf.items()},
        )

    def cost_model(self, state, global_batch: int):
        """XLA cost analysis of the compiled numerics-off ND step over
        an abstract global token batch (utils/flops.py ``CostModel``;
        see BSPEngine.cost_model) — tp/sp/pp/expert collectives are
        inside the executable, so its FLOPs/bytes include them even
        though ``traffic_model()`` models the dp grad sync only."""
        import jax as _jax

        from theanompi_tpu.utils.flops import abstract_batch, compiled_cost

        tok, _ = abstract_batch(self.model, int(global_batch))
        return compiled_cost(self._steps[False], state, tok,
                             _jax.random.PRNGKey(0))

    def numerics_model(self, state):
        """Numerics declaration (obs/numerics.py): sentinels computed
        spec-aware over the sharded param/grad trees (per-leaf scalar
        psums over each leaf's sharded axes); no divergence gauge — the
        sharding IS the single source of truth, there are no replicas
        to drift."""
        from theanompi_tpu.obs.numerics import NumericsModel

        del state
        return NumericsModel(
            rule="nd",
            detail={"note": "spec-aware sharded norms (scalar psums per "
                            "leaf); tp/sp/pp/expert layouts have no "
                            "replica divergence by construction"},
        )
