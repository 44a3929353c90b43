"""EASGD: elastic-averaging SGD over a worker mesh.

Rebuild of the reference's EASGD rule (reference: ``lib/exchanger.py`` —
``EASGD_Exchanger`` / ``Exch_swap``: each worker trains locally and every
``avg_freq`` iterations does a pairwise Sendrecv with a central parameter
server, both sides applying the elastic update ``±alpha*(w - w~)``;
SURVEY.md §3.3). The reference's FCFS asynchrony cannot exist under
gang-scheduled SPMD; this is the **synchronous EASGD** variant from the
original paper (Zhang, Choromanska & LeCun 2015, Alg. 1 with all workers
communicating on the same round):

- every device holds its OWN worker replica (params + optimizer state),
  stacked on a leading worker axis and sharded over the mesh;
- the center w~ is replicated;
- local steps run with NO collectives at all (the EASGD selling point:
  comm every avg_freq steps only);
- at an exchange round:  ``w_i -= alpha*(w_i - w~)`` and
  ``w~ += alpha * sum_i (w_i - w~)`` — one psum of the elastic
  differences, the TPU equivalent of the reference's n pairwise swaps.

Timing-model divergence from the reference (documented per SURVEY.md §7
item 6): exchanges are gang-scheduled rather than FCFS-async, so every
worker exchanges on the same step. The per-worker algebra is identical.

Batch semantics (reference meaning, SURVEY.md §3.3): each worker trains
on its OWN full ``recipe.batch_size`` stream — the incoming global batch
must be ``n_workers x batch_size``, sharded so each device's shard IS
one worker's batch (the driver feeds this; config #4 "ResNet-50 EASGD,
16 workers, batch 256" means 256 examples per worker per local step).

**Worker groups** (``group_size > 1``): each EASGD worker is itself a
data-parallel GROUP of chips — the engine reshapes the mesh to 2-D
``(worker, data)``, runs BSP (in-step psum over the group's ``data``
axis) inside every group, and the elastic exchange couples the
group-replicated worker params with the center over the ``worker`` axis.
This is how a 256-chip pod runs "16 workers": 16 groups x 16 chips,
each group seeing the worker's full batch (SURVEY.md §7.6's
recommended subgroup-mesh shape). A group of g chips is numerically a
single bigger worker: per-worker trajectories match group_size=1 runs
with the same per-worker batch (tests/test_easgd_groups.py).
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from theanompi_tpu.models.contract import Model
from theanompi_tpu.parallel.mesh import DATA_AXIS, stack_replicas
from theanompi_tpu.train import TrainState, init_train_state, make_eval_step, make_train_step

PyTree = Any


class EASGDState(NamedTuple):
    workers: TrainState  # leaves stacked (n_workers, ...), sharded over the mesh
    center_params: PyTree  # replicated
    center_model_state: PyTree  # replicated (refreshed at exchange rounds)
    # wire-codec error-feedback residuals of the elastic-difference psum
    # (parallel/codec.py): per-worker, stacked (n_workers, ...) and
    # sharded like the workers; () when the codec carries no state
    ef: PyTree = ()


class EASGDEngine:
    """Rule engine: local train step + periodic elastic exchange.

    ``alpha``: elastic rate per exchange. The EASGD paper uses
    ``alpha = beta/n`` with beta=0.9 as the stable default; that is the
    default here (reference configs exposed ``alpha`` directly).
    ``avg_freq``: steps between exchanges (reference: ``avg_freq``).
    """

    name = "easgd"
    # donation audit (ISSUE 2): local steps and the elastic exchange
    # both donate the stacked worker state, so async in-flight steps
    # reuse buffers instead of doubling HBM. Verified statically by the
    # SPMD analyzer (ISSUE 7, rule SPMD201); the silent-local-step +
    # every-avg_freq elastic-psum schedule is pinned by
    # tools/analyze/golden/easgd_*.json — both the step AND exchange
    # traces, amortized, must match traffic_model() (SPMD101).
    donates_state = True

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        steps_per_epoch: int = 1,
        avg_freq: int = 8,
        alpha: Optional[float] = None,
        axis_name: str = DATA_AXIS,
        input_transform=None,
        eval_views: int = 1,
        group_size: int = 1,
        accum_steps: int = 1,
        n_slices: Optional[int] = None,
        wire_codec=None,
        fused_update: bool = False,
    ):
        from theanompi_tpu.parallel.codec import get_codec
        from theanompi_tpu.parallel.mesh import make_worker_group_mesh

        self.codec = get_codec(wire_codec)
        self.model = model
        self.group_size = g = max(1, int(group_size))
        # n_slices: validate the pod topology split — groups (per-step
        # psum) inside a slice, the worker axis (every-avg_freq elastic
        # exchange) across slices; see make_worker_group_mesh
        mesh, gspec, grad_sync = make_worker_group_mesh(mesh, g, n_slices=n_slices)
        ax = mesh.axis_names[0] if g > 1 else axis_name
        self.mesh = mesh
        self.axis_name = ax
        self.n = mesh.shape[ax]  # number of WORKERS
        if self.n == 1:
            self.codec = get_codec(None)  # no peers, no wire to compress
        self.avg_freq = max(1, avg_freq)
        self.alpha = alpha if alpha is not None else 0.9 / self.n
        base_eval = make_eval_step(
            model, input_transform=input_transform, views=eval_views
        )
        a = self.alpha
        all_axes = tuple(mesh.axis_names)

        from theanompi_tpu.parallel.mesh import fold_linear_index

        def fold_all(rng):
            # distinct stream per DEVICE (worker identity + group slot)
            return fold_linear_index(rng, all_axes, mesh)

        # ---- local step: each worker trains its own replica; groups
        # ---- psum gradients over their internal data axis, no comm
        # ---- crosses workers. A factory per numerics flag: the
        # ---- sentinel variant adds the in-graph gauges (obs/numerics)
        # ---- including the EASGD-specific center<->worker L2 distance
        # ---- (one scalar psum — local steps stay otherwise silent) ----
        def make_sharded_step(numerics: bool):
            from theanompi_tpu.obs.numerics import sentinels_across_workers

            bstep = make_train_step(
                model, steps_per_epoch, grad_sync=grad_sync,
                input_transform=input_transform, accum_steps=accum_steps,
                numerics=numerics, fused_update=fused_update,
            )

            def sharded_step(state: EASGDState, images, labels, rng):
                local = jax.tree_util.tree_map(lambda v: v[0], state.workers)
                new_local, metrics = bstep(local, images, labels, fold_all(rng))
                if g > 1:
                    # group-replicated state: average BN stats within the
                    # group (grads were already psummed; BN stats are not)
                    new_local = new_local._replace(
                        model_state=lax.pmean(new_local.model_state, DATA_AXIS)
                    )
                if numerics:
                    # divergence gauge: RMS over workers of the L2
                    # distance to the center — what the elastic force
                    # acts on; unbounded growth = replicas escaping the
                    # center's basin (raise alpha / lower avg_freq)
                    d2 = sum(
                        jnp.sum(jnp.square(w.astype(jnp.float32)
                                           - c.astype(jnp.float32)))
                        for w, c in zip(
                            jax.tree_util.tree_leaves(new_local.params),
                            jax.tree_util.tree_leaves(state.center_params),
                        )
                    )
                    metrics["nm_divergence"] = jnp.sqrt(lax.pmean(d2, ax))
                    # per-worker rule: aggregate the base-step sentinels
                    # across the worker axis with their own semantics —
                    # the non-finite COUNT psums (a fractional count
                    # would misstate magnitude), the norms combine as
                    # RMS over workers (comparable to a single worker's
                    # reading); the blanket pmean below is then identity
                    metrics = sentinels_across_workers(metrics, ax)
                workers = jax.tree_util.tree_map(lambda v: v[None], new_local)
                metrics = lax.pmean(metrics, all_axes)
                return state._replace(workers=workers), metrics

            return sharded_step

        # THE spec source (parallel/recipe.py): worker stack + ef
        # residuals sharded over the worker axis, center replicated —
        # the worker-axis prefix broadcasts over an empty () ef subtree
        # when the codec is off
        from theanompi_tpu.parallel.recipe import ShardingRecipe

        self.sharding = ShardingRecipe.easgd(
            mesh, ax, group_batch_spec=gspec if g > 1 else None)
        sspec = self.sharding.state_spec(EASGDState)
        scalar = self.sharding.scalar
        bspec = self.sharding.batch_spec

        def jit_step(numerics: bool):
            return jax.jit(
                jax.shard_map(
                    make_sharded_step(numerics),
                    mesh=mesh,
                    in_specs=(sspec, bspec, bspec, scalar),
                    out_specs=(sspec, scalar),
                    check_vma=False,
                ),
                donate_argnums=(0,),
            )

        self._jit_step = jit_step
        self._steps = {False: jit_step(False)}

        # ---- elastic exchange: one psum of the elastic differences ----
        codec = self.codec

        def sharded_exchange(state: EASGDState):
            local = jax.tree_util.tree_map(lambda v: v[0], state.workers)
            diff = jax.tree_util.tree_map(
                lambda w, c: a * (w - c), local.params, state.center_params
            )
            # wire codec (parallel/codec.py): only the psum'd elastic
            # differences cross the worker axis — quantize them (error-
            # feedback residual per worker); the worker applies its OWN
            # exact difference locally, no wire involved
            wire_diff, new_ef = codec.compress_stacked(diff, state.ef)
            new_params = jax.tree_util.tree_map(lambda w, d: w - d, local.params, diff)
            center = jax.tree_util.tree_map(
                lambda c, d: c + lax.psum(d, ax), state.center_params, wire_diff
            )
            # center BN/eval state: average of worker states at exchange time
            center_ms = lax.pmean(local.model_state, ax)
            workers = jax.tree_util.tree_map(
                lambda v: v[None], local._replace(params=new_params)
            )
            return EASGDState(workers, center, center_ms, new_ef)

        self._exchange = jax.jit(
            jax.shard_map(
                sharded_exchange,
                mesh=mesh,
                in_specs=(sspec,),
                out_specs=sspec,
                check_vma=False,
            ),
            donate_argnums=(0,),
        )

        # ---- eval on the CENTER params (reference: server validates center) ----
        def sharded_eval(state: EASGDState, images, labels):
            center = TrainState(
                state.center_params, state.center_model_state,
                opt_state=(), step=jnp.zeros((), jnp.int32),
            )
            return lax.pmean(base_eval(center, images, labels), all_axes)

        self._eval = jax.jit(
            jax.shard_map(
                sharded_eval,
                mesh=mesh,
                in_specs=(sspec, bspec, bspec),
                out_specs=scalar,
                check_vma=False,
            )
        )

    # -- engine protocol ----------------------------------------------------
    @property
    def exchange_every(self) -> int:
        return self.avg_freq

    def init_state(self, rng) -> EASGDState:
        ts = init_train_state(self.model, rng)
        return EASGDState(
            workers=stack_replicas(ts, self.n),
            center_params=ts.params,
            center_model_state=ts.model_state,
            ef=self.codec.init_ef(ts.params, stack=self.n),
        )

    def train_step(self, state, images, labels, rng, numerics: bool = False):
        numerics = bool(numerics)
        if numerics not in self._steps:
            self._steps[numerics] = self._jit_step(numerics)
        return self._steps[numerics](state, images, labels, rng)

    def exchange(self, state):
        return self._exchange(state)

    def eval_step(self, state, images, labels):
        return self._eval(state, images, labels)

    def get_step(self, state) -> int:
        from theanompi_tpu.parallel.mesh import first_local_value

        return int(first_local_value(state.workers.step))

    def sharding_recipe(self):
        """The engine's ShardingRecipe (parallel/recipe.py) — declared
        spec table for the sharding analyzer and the topology stamp."""
        return self.sharding

    def elastic_spec(self) -> dict:
        """Per-leaf reshard policies for the topology manifest
        (utils/checkpoint.load_resharded). The center is replicated
        (``global``, exact across any world); the per-worker replicas
        are stacked ``(n_workers, ...)`` so a world change resizes the
        stack — ``worker_consensus`` re-seeds every new worker from the
        mean of the saved ones (int leaves like the per-worker step
        counter take the first worker's value), a parity-preserving
        approximation of the elastic consensus, not an exact resume.
        Error-feedback residuals are per-worker and reset."""
        return {"policies": {
            ".workers": {"policy": "worker_consensus"},
            ".ef": {"policy": "reset"},
        }}

    def traffic_model(self, state):
        """EASGD wire model (obs/comm.py): silent local steps (plus the
        group-internal grad psum when workers are chip groups), one
        param-sized psum of elastic differences every ``avg_freq``
        steps over the worker axis."""
        from theanompi_tpu.obs.comm import easgd_traffic, pytree_num_elements
        from theanompi_tpu.parallel.mesh import slice_topology

        # workers leaves are stacked (n_workers, ...): per-worker size
        per_worker = pytree_num_elements(state.workers.params) // self.n
        return easgd_traffic(
            per_worker, self.n, self.avg_freq, group_size=self.group_size,
            codec=self.codec, n_slices=slice_topology(self.mesh)[0],
        )

    def memory_model(self, state):
        """Analytic per-leaf HBM residency (utils/flops.py
        ``MemoryModel``; see BSPEngine.memory_model). The per-worker
        replicas are stacked ``(n_workers, ...)`` and sharded over the
        worker axis — each device holds ONE worker's params+opt — while
        the elastic center (params + refreshed BN state) is replicated
        on every device; error-feedback residuals are per-worker.
        Factors/specs come from the engine's ShardingRecipe (SHARD003
        checks them against the compiled program)."""
        from theanompi_tpu.utils.flops import state_memory_model

        n = self.n
        lf = self.sharding.leaf_factors(state)

        def factor(path, leaf):
            return lf.get(path, (1, None))[0]

        return state_memory_model(
            state, "easgd", n, factor,
            detail={"note": "worker stack sharded 1/n; center "
                            "replicated on every device"},
            specs={p: s for p, (_f, s) in lf.items()},
        )

    def cost_model(self, state, global_batch: int):
        """XLA cost analysis of the compiled numerics-off LOCAL step
        over an abstract global batch (utils/flops.py ``CostModel``;
        see BSPEngine.cost_model). The periodic elastic exchange is a
        separate executable and is NOT included — its wire time is the
        traffic model's amortized share (obs/attribution.py books it
        under comm, not compute)."""
        import jax as _jax

        from theanompi_tpu.utils.flops import abstract_batch, compiled_cost

        x, y = abstract_batch(self.model, int(global_batch))
        return compiled_cost(self._steps[False], state, x, y,
                             _jax.random.PRNGKey(0))

    def numerics_model(self, state):
        """Numerics declaration (obs/numerics.py): standard sentinels
        plus the EASGD divergence gauge — RMS-over-workers L2 distance
        of worker params to the center. Costs one scalar psum per
        numerics step; local steps stay otherwise collective-free."""
        from theanompi_tpu.obs.numerics import NumericsModel

        del state  # the gauge's cost is state-size independent (scalar)
        return NumericsModel(
            rule="easgd",
            divergence="center_worker_l2",
            detail={"extra_wire": "one scalar psum per numerics step",
                    "avg_freq": self.avg_freq},
        )
