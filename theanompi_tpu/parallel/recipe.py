"""ShardingRecipe — the single source of PartitionSpecs.

ROADMAP item 5 names the refactor: every engine, the checkpoint
topology stamp, and serve used to hand-roll their own PartitionSpecs,
so nothing could verify that what one layer DECLARED (traffic_model /
memory_model / elastic_spec / the ``__topology__`` manifest) matched
what another layer BUILT — let alone what GSPMD actually compiled.
A :class:`ShardingRecipe` is one object holding the mesh axes plus the
per-leaf-role spec rules for a rule engine's state; everything that
needs a spec asks the recipe:

- the engines' ``shard_map`` in/out specs (``state_spec``,
  ``batch_spec``, ``stacked_batch_spec``, ``scalar``);
- the per-leaf declared spec table (``leaf_specs``) the sharding
  analyzer (tools/analyze/sharding.py, rules SHARD001-004) checks
  against the COMPILED truth read off the lowered executable;
- the per-leaf shard factors (``leaf_factors``) the engines'
  ``memory_model()`` divides HBM residency by — so the memory
  pre-flight's 1/n claims and the specs can no longer drift apart;
- the checkpoint topology stamp (``as_json`` rides the
  ``__topology__`` manifest next to the live-array specs);
- serve's template/load placement (``place_replicated`` /
  ``leaf_specs`` — the train->serve handoff SHARD004 verifies).

A *role* is a top-level state field (``params``, ``opt_state``,
``workers``, ``ef``, ...). Its rule is either one
:class:`~jax.sharding.PartitionSpec` (a pytree PREFIX — the whole
subtree shards that way) or a spec tree matching the field's structure
(ND's per-leaf param specs, ZeRO's flat-segment accumulators). The
shapes here follow the mesh+NamedSharding utility idiom of
SNIPPETS.md [1]/[3], generalized to role tables.

Engines must not construct PartitionSpecs directly: the sharding
analyzer's source guard flags any ``PartitionSpec(...)`` call in
``parallel/{bsp,zero,easgd,gosgd,nd}.py`` or ``serve/*`` — specs are
born here (or in parallel/mesh.py's topology helpers) and consumed
everywhere else.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Optional

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

PyTree = Any


def _is_spec(x) -> bool:
    return isinstance(x, PartitionSpec)


def spec_axes(spec) -> tuple:
    """Every mesh axis a PartitionSpec names, in order of appearance."""
    out = []
    for entry in tuple(spec):
        for ax in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if ax is not None:
                out.append(str(ax))
    return tuple(out)


def psum_axes(spec, axes: tuple) -> tuple:
    """The participating ``axes`` a leaf's gradient is psummed over —
    the complement of the axes its spec shards it on (the universal
    rule models/transformer.py::sync_grads_by_spec applies). Shared by
    the ND engine's wire bookkeeping and the ef-residual spec rule."""
    sharded_on = set(spec_axes(spec))
    return tuple(a for a in axes if a not in sharded_on)


@dataclass(frozen=True)
class ShardingRecipe:
    """Mesh axes + per-leaf-role spec rules for one rule engine.

    ``roles`` maps each top-level state field to its spec rule: a
    single PartitionSpec prefix, a spec tree matching the field's
    structure, or ``()`` for fields that are empty in this
    configuration (codec-off ``ef``)."""

    rule: str
    mesh: Mesh
    axes: tuple  # the data/worker axes batches shard over
    roles: dict
    batch_spec: PartitionSpec = field(default_factory=PartitionSpec)

    # -- spec construction (the ONE sanctioned PartitionSpec factory) --
    @property
    def scalar(self) -> PartitionSpec:
        """Replicated spec — rng keys, scalar metrics, whole-state
        prefixes for replicated rules."""
        return PartitionSpec()

    @property
    def stacked_batch_spec(self) -> PartitionSpec:
        """Fused-dispatch batch spec: leading group/step dim replicated,
        the batch dims per ``batch_spec``."""
        return PartitionSpec(None, *self.batch_spec)

    @property
    def leading_batch_spec(self) -> PartitionSpec:
        """Spec of the batch dim ALONE (1-D) — host feed-range
        computations that only care how rows divide over processes."""
        entries = tuple(self.batch_spec)
        return PartitionSpec(entries[0]) if entries else PartitionSpec()

    def state_spec(self, state_cls):
        """The ``shard_map`` in/out spec tree for the engine's state
        NamedTuple — one rule per field, in field order."""
        return state_cls(*(self.roles[f] for f in state_cls._fields))

    def role_spec(self, role: str):
        return self.roles[role]

    # -- per-leaf resolution (what the analyzer/stamp/preflight read) --
    def _resolve(self, path) -> PartitionSpec:
        """The spec covering one state leaf: descend the role tree
        along the leaf's key path until a PartitionSpec prefix (or the
        path ends)."""
        entries = list(path)
        if not entries:
            raise ValueError("empty leaf path")
        head, rest = entries[0], entries[1:]
        name = getattr(head, "name", None) or getattr(head, "key", None)
        if name is None or name not in self.roles:
            raise ValueError(
                f"leaf path {jax.tree_util.keystr(tuple(path))!r} does "
                f"not start at a recipe role (roles: {sorted(self.roles)})"
            )
        node = self.roles[name]
        for e in rest:
            if _is_spec(node):
                return node
            if isinstance(node, dict):
                node = node[e.key]
            elif hasattr(node, "_fields"):
                node = getattr(node, e.name)
            elif isinstance(node, (tuple, list)):
                node = node[e.idx]
            else:
                raise ValueError(
                    f"role {name!r} spec tree cannot follow path entry "
                    f"{e!r}"
                )
        if not _is_spec(node):
            raise ValueError(
                f"role {name!r} resolved to a non-spec {type(node).__name__}"
                f" at {jax.tree_util.keystr(tuple(path))!r}"
            )
        return node

    def leaf_specs(self, state_template) -> list:
        """``[(path_str, PartitionSpec)]`` for every leaf of a
        (possibly abstract) state pytree — the DECLARED spec table the
        sharding analyzer verifies against the compiled executable and
        the checkpoint manifest stamps next to the live-array specs."""
        out = []
        for path, _leaf in jax.tree_util.tree_flatten_with_path(
                state_template)[0]:
            out.append((jax.tree_util.keystr(path), self._resolve(path)))
        return out

    def shard_factor(self, spec) -> int:
        """Mesh extent a leaf with ``spec`` is divided over (1 =
        replicated) — the denominator the memory pre-flight's per-leaf
        residency uses, derived from the SAME spec the engine shards
        with."""
        sizes = dict(zip(self.mesh.axis_names, self.mesh.devices.shape))
        ways = 1
        for ax in spec_axes(spec):
            ways *= int(sizes.get(ax, 1))
        return ways

    def leaf_factors(self, state_template) -> dict:
        """``{path_str: (shard_factor, spec)}`` over the state — what
        engine ``memory_model()`` hooks feed utils/flops.py with."""
        return {p: (self.shard_factor(s), s)
                for p, s in self.leaf_specs(state_template)}

    def as_json(self) -> dict:
        """Serializable identity for the checkpoint ``__topology__``
        manifest: rule + mesh + axes + batch spec (the per-leaf table
        is stamped separately off the live arrays)."""
        from theanompi_tpu.parallel.mesh import mesh_topology, spec_to_json

        return {
            "rule": self.rule,
            "mesh": mesh_topology(self.mesh),
            "axes": [str(a) for a in self.axes],
            "batch_spec": spec_to_json(self.batch_spec),
        }

    # -- placement ------------------------------------------------------
    def place_replicated(self, tree):
        """Place a host pytree replicated over this recipe's mesh
        (committed: a one-device serving mesh pins its replica to THAT
        device, not to the process default)."""
        return jax.device_put(tree, NamedSharding(self.mesh, PartitionSpec()))

    def place_state(self, state):
        """Commit a freshly built engine state to its declared per-leaf
        shardings. A state left uncommitted on the default device makes
        the first step compile for THAT placement and the second step,
        fed the first one's committed output, compile all over again."""
        flat, treedef = jax.tree_util.tree_flatten_with_path(state)
        return jax.tree_util.tree_unflatten(treedef, [
            jax.device_put(leaf, NamedSharding(self.mesh, self._resolve(path)))
            for path, leaf in flat
        ])

    def place_params(self, params):
        """Place the SERVED params tree per this recipe's ``params``
        role. The replicated serve recipe degenerates to
        :meth:`place_replicated`; the tensor-serve recipe commits each
        leaf to its Megatron spec's NamedSharding — the one sanctioned
        path for sharded-param serving (engines still never touch
        PartitionSpec)."""
        spec_tree = self.roles.get("params", PartitionSpec())
        if _is_spec(spec_tree):
            return self.place_replicated(params)
        leaves, treedef = jax.tree_util.tree_flatten(params)
        specs = treedef.flatten_up_to(spec_tree)
        placed = [
            jax.device_put(leaf, NamedSharding(self.mesh, spec))
            for leaf, spec in zip(leaves, specs)
        ]
        return jax.tree_util.tree_unflatten(treedef, placed)

    # -- constructors (one per rule family) -----------------------------
    @classmethod
    def bsp(cls, mesh: Mesh, axes, ef_sharded: bool) -> "ShardingRecipe":
        """Replicated state over a data mesh; the codec's per-device
        error-feedback residual stack (when present) shards over the
        data axes. ``axes`` may be a tuple (multi-slice meshes)."""
        axes_t = (axes,) if isinstance(axes, str) else tuple(axes)
        data = PartitionSpec(axes)
        return cls(
            rule="bsp", mesh=mesh, axes=axes_t,
            roles=dict(params=PartitionSpec(), model_state=PartitionSpec(),
                       opt_state=PartitionSpec(), step=PartitionSpec(),
                       ef=data if ef_sharded else ()),
            batch_spec=data,
        )

    @classmethod
    def zero1(cls, mesh: Mesh, axis: str, opt_template,
              use_ef: bool) -> "ShardingRecipe":
        """ZeRO-1: params/BN replicated, flat optimizer accumulators
        sharded 1/n over the data axis (scalar opt leaves replicate),
        error-feedback residuals per-device."""
        opt_specs = jax.tree_util.tree_map(
            lambda l: PartitionSpec(axis) if l.ndim else PartitionSpec(),
            opt_template,
        )
        ef = ({"g": PartitionSpec(axis), "p": PartitionSpec(axis)}
              if use_ef else ())
        return cls(
            rule="zero1", mesh=mesh, axes=(axis,),
            roles=dict(params=PartitionSpec(), model_state=PartitionSpec(),
                       opt_state=opt_specs, step=PartitionSpec(), ef=ef),
            batch_spec=PartitionSpec(axis),
        )

    @classmethod
    def easgd(cls, mesh: Mesh, worker_axis: str,
              group_batch_spec: Optional[PartitionSpec] = None,
              ) -> "ShardingRecipe":
        """Worker replicas stacked (n_workers, ...) and sharded over the
        worker axis; the elastic center replicated. Group mode passes
        the 2-D (worker, data) batch spec built by
        mesh.make_worker_group_mesh."""
        w = PartitionSpec(worker_axis)
        return cls(
            rule="easgd", mesh=mesh, axes=tuple(mesh.axis_names),
            roles=dict(workers=w, center_params=PartitionSpec(),
                       center_model_state=PartitionSpec(), ef=w),
            batch_spec=group_batch_spec if group_batch_spec is not None
            else w,
        )

    @classmethod
    def gosgd(cls, mesh: Mesh, worker_axis: str,
              group_batch_spec: Optional[PartitionSpec] = None,
              ) -> "ShardingRecipe":
        """Everything per-worker: replicas, gossip shares (alpha) and
        ef residuals all stacked over the worker axis."""
        w = PartitionSpec(worker_axis)
        return cls(
            rule="gosgd", mesh=mesh, axes=tuple(mesh.axis_names),
            roles=dict(workers=w, alpha=w, ef=w),
            batch_spec=group_batch_spec if group_batch_spec is not None
            else w,
        )

    @classmethod
    def nd(cls, mesh: Mesh, axes: tuple, param_specs, opt_template,
           use_ef: bool, batch_entry, sp_axis: Optional[str],
           microbatched: bool = False) -> "ShardingRecipe":
        """Spec-driven N-D parallelism: per-leaf param specs (from the
        model's spec setup), optimizer accumulators sharded exactly like
        their parameters, ef residuals stacked over each leaf's psummed
        axes, tokens sharded ``P(batch_entry, sp)`` (microbatch-major
        adds a leading replicated dim)."""
        from theanompi_tpu.models.transformer import opt_state_specs

        opt_specs = opt_state_specs(opt_template, param_specs)
        ef: Any = ()
        if use_ef:
            ef = jax.tree_util.tree_map(
                lambda spec: PartitionSpec(
                    psum_axes(spec, axes) or None, *spec),
                param_specs, is_leaf=_is_spec,
            )
        tok_entries = (batch_entry, sp_axis)
        tok_spec = (PartitionSpec(None, *tok_entries) if microbatched
                    else PartitionSpec(*tok_entries))
        return cls(
            rule="nd", mesh=mesh, axes=tuple(axes),
            roles=dict(params=param_specs, opt_state=opt_specs,
                       step=PartitionSpec(), ef=ef),
            batch_spec=tok_spec,
        )

    @classmethod
    def serve(cls, mesh: Optional[Mesh] = None,
              device=None) -> "ShardingRecipe":
        """The serving placement: params/BN replicated on the serving
        mesh (default: one device — PR-5's single-program engine;
        ``device`` picks WHICH one, so the members of a replica fleet
        each take their own chip). The train->serve handoff check
        (SHARD004) verifies this template against the training engine's
        stamped ``__topology__`` specs."""
        if mesh is None:
            mesh = Mesh(np.array([device] if device is not None
                                 else jax.devices()[:1]), ("data",))
        return cls(
            rule="serve", mesh=mesh, axes=tuple(mesh.axis_names),
            roles=dict(params=PartitionSpec(),
                       model_state=PartitionSpec(),
                       opt_state=PartitionSpec(), step=PartitionSpec(),
                       ef=()),
            batch_spec=PartitionSpec(),
        )

    @classmethod
    def serve_tensor(cls, model, mesh: Optional[Mesh] = None,
                     tp_axis: Optional[str] = None) -> "ShardingRecipe":
        """Tensor-sharded serving (``tmpi serve --decode --shard
        tensor``): the model arch's Megatron param specs
        (``tp_param_specs`` — qkv/head column-sharded, proj/mlp_out
        row-sharded, embeddings and norms replicated) over a 1-axis
        serving mesh spanning every local device. On one device this
        degenerates to the replicated serve recipe (every spec shards
        an extent-1 axis), so the SAME CLI flags run on a CPU dev box
        and a multi-chip serving host. ``model`` is a zoo model whose
        ``arch`` exposes ``tp_param_specs`` (the LM stack)."""
        arch = getattr(model, "arch", model)
        specs_fn = getattr(arch, "tp_param_specs", None)
        if specs_fn is None:
            raise ValueError(
                f"{type(model).__name__} has no tp_param_specs — tensor-"
                "sharded serving needs the LM stack's Megatron spec "
                "table (use --shard none for replicated serving)"
            )
        if mesh is None:
            from theanompi_tpu.models.transformer import MODEL_AXIS

            mesh = Mesh(np.array(jax.devices()), (MODEL_AXIS,))
        axis = tp_axis if tp_axis is not None else mesh.axis_names[0]
        return cls(
            rule="serve_tensor", mesh=mesh, axes=tuple(mesh.axis_names),
            roles=dict(params=specs_fn(axis),
                       model_state=PartitionSpec(),
                       opt_state=PartitionSpec(), step=PartitionSpec(),
                       ef=()),
            batch_spec=PartitionSpec(),
        )
