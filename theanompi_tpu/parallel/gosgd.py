"""GoSGD: randomized peer-to-peer gossip SGD.

Rebuild of the reference's GoSGD rule (reference: ``lib/exchanger.py`` —
``GOSGD_Exchanger``: after each local step, every worker draws
Bernoulli(p); on success it isends (params, share-weight/2) to one random
peer and halves its own share; the receiver merges by share-weighted
average ``w_j <- (a_i*w_i + a_j*w_j)/(a_i + a_j)`` and adds the received
share; SURVEY.md §3.5; algorithm: Blot et al. 2016, "Gossip training for
deep learning").

SPMD redesign: MPI isend/iprobe does not exist under gang scheduling.
A gossip round draws ONE shared uniform shift ``s in [1, n-1]`` (from
the round's shared rng); every worker that pushes this round sends to
the peer ``s`` hops forward. The round is realized as a SINGLE
``lax.ppermute`` of the packed (share*w, share) buffer, selected from
the n-1 static shift permutations by ``lax.switch`` (every device
computes the same ``s``, so all replicas take the same branch — safe
for a collective under SPMD). Round cost is O(|w|), independent of n —
the same wire cost as one reference point-to-point push.

Probability-law note (documented divergence, SURVEY.md §7 hard-part 1):
each sender's peer is still EXACTLY uniform over the other n-1 workers,
and the push decisions stay independent Bernoulli(p) per worker — the
per-(sender, receiver) marginal law matches the reference. What changes
is the joint law across senders within one round: peers are perfectly
correlated (everyone shifts by the same s), which makes the assignment
receiver-side conflict-free — at most one message per receiver per
round, where the reference could deliver several queued gossip messages
in one iteration. Merge algebra per delivered message is identical.

``gossip_every=k`` runs the gossip collective only every k-th step (two
compiled step variants; the host picks — no recompile), cutting gossip
bandwidth by k while applying the same per-round push law.

Batch semantics (reference meaning, SURVEY.md §3.5): each worker trains
on its OWN full ``recipe.batch_size`` stream — the incoming global
batch is ``n_workers x batch_size``, sharded so each device's shard IS
one worker's batch (the driver feeds this).

**Worker groups** (``group_size > 1``): as in EASGD, each gossip worker
is a data-parallel GROUP of chips on a 2-D ``(worker, data)`` mesh —
BSP inside the group, gossip ppermute over the worker axis (payloads
are group-replicated, the whole group pushes together). See
parallel/easgd.py's worker-group notes.

Share-weight invariant: sum_i alpha_i == 1 at all times (checked in
tests); consensus params = sum_i alpha_i * w_i. On a 1-device mesh
gossip is the identity (a push would otherwise leak share mass with no
possible recipient).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh

from theanompi_tpu.models.contract import Model
from theanompi_tpu.parallel.mesh import DATA_AXIS
from theanompi_tpu.train import TrainState, init_train_state, make_eval_step, make_train_step

PyTree = Any


class GOSGDState(NamedTuple):
    workers: TrainState  # stacked (n, ...), sharded over the mesh
    alpha: jax.Array  # (n,) share weights, sharded; sum == 1
    # wire-codec error-feedback residuals of the gossip payload values
    # (parallel/codec.py): (n, flat_params) sharded over the mesh; ()
    # when the codec carries no state. The share weight itself always
    # rides EXACT (gossip_encode) — quantizing it would leak the
    # sum(alpha) == 1 mass invariant.
    ef: PyTree = ()


class GOSGDEngine:
    """Rule engine: local step + in-step randomized gossip.

    ``p_push``: per-step push probability (reference drew Bernoulli(p)
    each iteration; its configs derived p from avg_freq ~ 1/p).
    """

    name = "gosgd"
    # donation audit (ISSUE 2): the gossip step donates its stacked
    # per-worker state — in-flight async dispatches reuse buffers.
    # Verified statically (ISSUE 7, SPMD201). The one-ppermute-per-round
    # gossip schedule is pinned by tools/analyze/golden/gosgd_*.json;
    # note the int8 gossip payload is PHYSICAL compression (the packed
    # int8 message is the ppermute operand), which the analyzer prices
    # by dtype, vs the value-space codec psums priced analytically.
    donates_state = True

    def __init__(
        self,
        model: Model,
        mesh: Mesh,
        steps_per_epoch: int = 1,
        p_push: float = 0.25,
        avg_freq: int | None = None,
        gossip_every: int = 1,
        axis_name: str = DATA_AXIS,
        input_transform=None,
        eval_views: int = 1,
        group_size: int = 1,
        accum_steps: int = 1,
        n_slices: "int | None" = None,
        wire_codec=None,
        fused_update: bool = False,
    ):
        from theanompi_tpu.parallel.codec import get_codec
        from theanompi_tpu.parallel.mesh import make_worker_group_mesh

        self.codec = get_codec(wire_codec)
        self.model = model
        self.group_size = g = max(1, int(group_size))
        # n_slices: pod topology validation (groups inside a slice, the
        # gossip ppermute across slices) — see make_worker_group_mesh
        mesh, gspec, grad_sync = make_worker_group_mesh(mesh, g, n_slices=n_slices)
        if g > 1:
            axis_name = mesh.axis_names[0]
        # THE spec source (parallel/recipe.py): replicas, gossip shares
        # and ef residuals all per-worker
        from theanompi_tpu.parallel.recipe import ShardingRecipe

        self.sharding = ShardingRecipe.gosgd(
            mesh, axis_name, group_batch_spec=gspec if g > 1 else None)
        bspec = self.sharding.batch_spec
        self.mesh = mesh
        self.axis_name = axis_name
        self.n = mesh.shape[axis_name]  # number of WORKERS
        if self.n == 1:
            self.codec = get_codec(None)  # gossip is the identity
        if avg_freq:  # reference-style configuration: p = 1/avg_freq
            p_push = 1.0 / avg_freq
        self.p_push = float(p_push)
        self.gossip_every = max(1, int(gossip_every))
        self._count: int | None = None

        def make_base_step(numerics: bool):
            return make_train_step(
                model, steps_per_epoch, grad_sync=grad_sync,
                input_transform=input_transform, accum_steps=accum_steps,
                numerics=numerics, fused_update=fused_update,
            )

        base_step = make_base_step(False)
        base_eval = make_eval_step(
            model, input_transform=input_transform, views=eval_views
        )
        ax, n, p = axis_name, self.n, float(p_push)
        codec = self.codec
        use_ef = codec.active and codec.error_feedback
        all_axes = tuple(mesh.axis_names)

        def gossip(params: PyTree, alpha: jax.Array, rng: jax.Array,
                   ef: PyTree):
            """One gossip round: ONE executed ppermute; returns merged
            (params, alpha, ef'). ``rng`` must be identical across
            devices — the shared shift comes straight from it,
            per-device push decisions from folding in the device index.
            Identity on a 1-device mesh (no recipient exists).

            With a wire codec the message IS the packed quantized
            layout (codec.gossip_encode — for int8 the int8 lanes ride
            the interconnect); the share weight travels exact. Error
            feedback applies only on rounds this worker PUSHES: a
            silent round ships exact zeros (a residual injected into a
            zero-share payload would hand the receiver mass-less junk
            values)."""
            if n == 1:
                return params, alpha, ef
            me = lax.axis_index(ax)
            hop_key, push_base = jax.random.split(rng)
            # shared across devices: every replica draws the same shift
            hop = jax.random.randint(hop_key, (), 1, n)
            push = jax.random.bernoulli(jax.random.fold_in(push_base, me), p)

            send_share = jnp.where(push, alpha * 0.5, 0.0)
            keep_share = alpha - send_share
            # big-buffer pack (reference: exchanger packed params into one
            # contiguous comm buffer): share rides in the last slot so the
            # whole round is a single collective
            from jax.flatten_util import ravel_pytree

            from theanompi_tpu.parallel.codec import (
                gossip_decode,
                gossip_encode,
            )

            flat, unravel = ravel_pytree(params)
            L = flat.shape[0]
            values = send_share * flat
            if use_ef:
                values = values + jnp.where(push, ef[0], 0.0)
            payload = gossip_encode(codec, values, send_share)
            # one ppermute, shift chosen at runtime: lax.switch over the
            # n-1 static shift permutations (ppermute's perm is static).
            # Uniform predicate across replicas => same branch everywhere.
            branches = [
                lambda x, _s=s: lax.ppermute(
                    x, ax, [(i, (i + _s) % n) for i in range(n)]
                )
                for s in range(1, n)
            ]
            received = lax.switch(hop - 1, branches, payload)
            recv_values, recv_share = gossip_decode(codec, received, L)
            new_ef = ef
            if use_ef:
                # residual = what MY quantizer discarded this round
                # (decode my own message — dequant is cheap; identical
                # to what my receiver reconstructs)
                sent_values, _ = gossip_decode(codec, payload, L)
                new_ef = jnp.where(push, values - sent_values, ef[0])[None]
            acc = keep_share * flat + recv_values
            acc_share = keep_share + recv_share
            return unravel(acc / acc_share), acc_share, new_ef

        def make_flag_fn(numerics: bool):
            """Factory per numerics flag: the sentinel variant adds the
            in-graph gauges (obs/numerics.py) including the GoSGD
            inter-replica disagreement — RMS distance of worker params
            to the unweighted replica mean, whose pmean costs one
            param-sized allreduce per numerics step (exactly what
            ``--numerics-freq > 1`` amortizes on this rule)."""
            from theanompi_tpu.obs.numerics import sentinels_across_workers

            bstep = make_base_step(numerics) if numerics else base_step

            def sharded_step_flag(state: GOSGDState, images, labels, rng,
                                  with_gossip):
                """``with_gossip``: a static Python bool, one jit variant
                each."""
                local = jax.tree_util.tree_map(lambda v: v[0], state.workers)
                a_local = state.alpha[0]
                step_rng, gossip_rng = jax.random.split(rng)
                from theanompi_tpu.parallel.mesh import fold_linear_index

                step_rng = fold_linear_index(step_rng, all_axes, mesh)
                new_local, metrics = bstep(local, images, labels, step_rng)
                if g > 1:
                    # group-replicated worker: average BN stats within
                    # the group (grads were already psummed)
                    new_local = new_local._replace(
                        model_state=lax.pmean(new_local.model_state, DATA_AXIS)
                    )
                # a Python branch, not lax.cond: the no-gossip program
                # stays genuinely collective-free — lax.cond stages BOTH
                # branches even for a concrete predicate (verified),
                # which would put a dead ppermute switch in the local
                # step and lean on XLA to simplify it out
                merged, a_new, ef_new = (
                    gossip(new_local.params, a_local, gossip_rng, state.ef)
                    if with_gossip
                    else (new_local.params, a_local, state.ef)
                )
                new_local = new_local._replace(params=merged)
                if numerics:
                    wbar = jax.tree_util.tree_map(
                        lambda w: lax.pmean(w.astype(jnp.float32), ax), merged
                    )
                    d2 = sum(
                        jnp.sum(jnp.square(w.astype(jnp.float32) - wb))
                        for w, wb in zip(
                            jax.tree_util.tree_leaves(merged),
                            jax.tree_util.tree_leaves(wbar),
                        )
                    )
                    metrics["nm_divergence"] = jnp.sqrt(lax.pmean(d2, ax))
                    # per-worker sentinel aggregation (obs/numerics.py):
                    # count psums, norms RMS over workers — the blanket
                    # pmean below is then identity on the nm_ keys
                    metrics = sentinels_across_workers(metrics, ax)
                metrics = lax.pmean(metrics, all_axes)
                return (
                    GOSGDState(
                        jax.tree_util.tree_map(lambda v: v[None], new_local),
                        a_new[None], ef_new,
                    ),
                    metrics,
                )

            return sharded_step_flag

        self._sharded_step_flag = make_flag_fn(False)
        self._state_spec = self.sharding.state_spec(GOSGDState)

        def make_sharded_step(with_gossip: bool, numerics: bool = False):
            flag_fn = (
                self._sharded_step_flag if not numerics else make_flag_fn(True)
            )

            def sharded_step(state, images, labels, rng):
                return flag_fn(state, images, labels, rng, with_gossip)

            return jax.jit(
                jax.shard_map(
                    sharded_step,
                    mesh=mesh,
                    in_specs=(self._state_spec, bspec, bspec,
                              self.sharding.scalar),
                    out_specs=(self._state_spec, self.sharding.scalar),
                    check_vma=False,
                ),
                donate_argnums=(0,),
            )

        self._make_jit_step = make_sharded_step
        self._steps = {(True, False): make_sharded_step(True)}
        self._steps[(False, False)] = (
            make_sharded_step(False) if self.gossip_every > 1
            else self._steps[(True, False)]
        )

        # ---- eval on the consensus params: sum_i alpha_i w_i -------------
        def sharded_eval(state: GOSGDState, images, labels):
            local = jax.tree_util.tree_map(lambda v: v[0], state.workers)
            a_local = state.alpha[0]
            consensus_params = jax.tree_util.tree_map(
                lambda w: lax.psum(a_local * w, ax), local.params
            )
            consensus_ms = lax.pmean(local.model_state, ax)
            consensus = TrainState(
                consensus_params, consensus_ms, opt_state=(), step=jnp.zeros((), jnp.int32)
            )
            return lax.pmean(base_eval(consensus, images, labels), all_axes)

        self._eval = jax.jit(
            jax.shard_map(
                sharded_eval,
                mesh=mesh,
                in_specs=(self._state_spec, bspec, bspec),
                out_specs=self.sharding.scalar,
                check_vma=False,
            )
        )

    # -- engine protocol ----------------------------------------------------
    exchange_every = 0  # gossip happens inside the step

    def init_state(self, rng) -> GOSGDState:
        from theanompi_tpu.parallel.mesh import stack_replicas

        ts = init_train_state(self.model, rng)
        # _count stays None: the first train_step derives it from the
        # state's step counter, which is also correct when the driver
        # swaps in a restored checkpoint after init_state (resume keeps
        # the gossip cadence aligned with the global step).
        self._count = None
        ef = ()
        if self.codec.active and self.codec.error_feedback:
            # one flat residual per worker, sized like the packed
            # gossip payload's values (ravel of the param pytree)
            from jax.flatten_util import ravel_pytree

            flat_size = jax.eval_shape(
                lambda p: ravel_pytree(p)[0], ts.params
            ).shape[0]
            ef = jnp.zeros((self.n, flat_size), jnp.float32)
        return GOSGDState(
            workers=stack_replicas(ts, self.n),
            # strongly typed, like the alpha every step returns: a
            # weak-typed initial share retraces the step on its 2nd call
            alpha=jnp.full((self.n,), 1.0 / self.n, jnp.float32),
            ef=ef,
        )

    def train_step(self, state, images, labels, rng, numerics: bool = False):
        if self._count is None:  # resumed state: derive from the step counter
            self._count = self.get_step(state)
        nxt = self._count + 1
        key = (nxt % self.gossip_every == 0, bool(numerics))
        if key not in self._steps:
            self._steps[key] = self._make_jit_step(*key)
        out = self._steps[key](state, images, labels, rng)
        # advance only after the dispatch succeeds: a raise (OOM on a new
        # shape) must not shift the gossip cadence off the applied steps
        self._count = nxt
        return out

    def exchange(self, state):
        return state

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
        (utils/checkpoint.load_resharded). Worker replicas resize by
        ``worker_consensus`` (mean over the saved stack — the unweighted
        stand-in for the alpha-weighted gossip consensus; parity, not
        exact); the share weights restart uniform at ``1/W`` so the
        ``sum(alpha) == 1`` mass invariant holds EXACTLY on the new
        world; error-feedback residuals are per-worker and reset."""
        return {"policies": {
            ".workers": {"policy": "worker_consensus"},
            ".alpha": {"policy": "worker_uniform"},
            ".ef": {"policy": "reset"},
        }}

    def traffic_model(self, state):
        """GoSGD wire model (obs/comm.py): one ppermute of the packed
        ``(share*w, share)`` buffer per gossip round (every
        ``gossip_every`` steps), plus the group-internal grad psum when
        workers are chip groups."""
        from theanompi_tpu.obs.comm import gosgd_traffic, pytree_num_elements
        from theanompi_tpu.parallel.mesh import slice_topology

        per_worker = pytree_num_elements(state.workers.params) // self.n
        return gosgd_traffic(
            per_worker, self.n, gossip_every=self.gossip_every,
            group_size=self.group_size, codec=self.codec,
            n_slices=slice_topology(self.mesh)[0],
        )

    def memory_model(self, state):
        """Analytic per-leaf HBM residency (utils/flops.py
        ``MemoryModel``; see BSPEngine.memory_model). Everything in
        GoSGD state is per-worker — the stacked replicas, the share
        weights, and the codec residuals all shard ``1/n`` over the
        worker axis; there is no replicated center. Factors/specs come
        from the engine's ShardingRecipe (SHARD003 checks them against
        the compiled program)."""
        from theanompi_tpu.utils.flops import state_memory_model

        n = self.n
        lf = self.sharding.leaf_factors(state)

        def factor(path, leaf):
            return lf.get(path, (1, None))[0]

        return state_memory_model(
            state, "gosgd", n, factor,
            detail={"note": "all state per-worker (stack + alpha + ef "
                            "sharded 1/n); no replicated center"},
            specs={p: s for p, (_f, s) in lf.items()},
        )

    def cost_model(self, state, global_batch: int):
        """XLA cost analysis of the compiled numerics-off WITH-GOSSIP
        step variant over an abstract global batch (utils/flops.py
        ``CostModel``; see BSPEngine.cost_model) — the gossip ppermute
        rides inside the step, so the representative executable is the
        gossip-round one (exact on ``gossip_every == 1``, a slight
        over-count of pack/unpack flops otherwise)."""
        import jax as _jax

        from theanompi_tpu.utils.flops import abstract_batch, compiled_cost

        x, y = abstract_batch(self.model, int(global_batch))
        return compiled_cost(self._steps[(True, False)], state, x, y,
                             _jax.random.PRNGKey(0))

    def numerics_model(self, state):
        """Numerics declaration (obs/numerics.py): standard sentinels
        plus the inter-replica disagreement gauge (RMS distance to the
        replica mean). The mean needs a param-sized pmean — one full
        allreduce of extra wire per numerics step, so size
        ``--numerics-freq`` accordingly on this rule."""
        from theanompi_tpu.obs.comm import allreduce_bytes, pytree_num_elements
        from theanompi_tpu.obs.numerics import NumericsModel

        per_worker = pytree_num_elements(state.workers.params) // self.n
        return NumericsModel(
            rule="gosgd",
            divergence="replica_disagreement",
            detail={"extra_wire": "param-sized pmean per numerics step",
                    "extra_bytes_per_numerics_step": allreduce_bytes(
                        per_worker, self.n)},
        )
