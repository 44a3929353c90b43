"""Device-mesh runtime.

Replaces the reference's per-process device/communicator setup
(reference: ``lib/base.py`` — ``MPI_GPU_Process.init_device``,
``get_internode_comm`` (MPI world), ``get_intranode_comm`` (NCCL clique);
SURVEY.md §1 L1). On TPU there is no process-per-device or dual
MPI/NCCL hierarchy: a named ``Mesh`` spans all chips, XLA lowers
collectives onto ICI within a slice and DCN across slices, and
``jax.distributed.initialize`` (multi-host) replaces ``mpirun``.

Axis naming: today's rules are pure data parallelism, so the mesh is
1-D ``('data',)`` — but everything takes the axis names from here so a
``('data', 'model')`` mesh is additive later (SURVEY.md §5.7 note).
"""

from __future__ import annotations

from typing import Optional, Sequence, Union

import numpy as np

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

DATA_AXIS = "data"
# Cross-slice axis for multi-slice (pod-scale) meshes: collectives over
# DATA_AXIS ride ICI inside a slice, collectives over DCN_AXIS cross the
# data-center network between slices. See make_multislice_mesh.
DCN_AXIS = "dcn"
# Async-rule worker axis for (worker, data) meshes: each elastic/gossip
# "worker" is itself a data-parallel GROUP of chips (EASGD group mode).
WORKER_AXIS = "worker"


def _slice_major(devs):
    """Canonical device linearization: slice-major, then id — shared by
    every mesh builder (changing it changes per-device RNG streams)."""
    return sorted(devs, key=lambda d: (getattr(d, "slice_index", 0), d.id))


def fold_linear_index(rng, axes, mesh: Mesh):
    """Fold this device's linearized mesh index (over ``axes``, row-major)
    into ``rng`` — THE per-device RNG stream definition shared by every
    rule engine (changing the linearization changes dropout/augment
    streams everywhere at once)."""
    from jax import lax

    idx = None
    for a in axes:
        i = lax.axis_index(a)
        idx = i if idx is None else idx * mesh.shape[a] + i
    return jax.random.fold_in(rng, idx)


def batch_axes(mesh: Mesh):
    """The axis spec batches shard over: the single data axis on a 1-D
    mesh, ALL axes on a multi-axis (multi-slice) mesh."""
    names = mesh.axis_names
    return names[0] if len(names) == 1 else tuple(names)


def make_mesh(
    devices: Union[int, Sequence, None] = None,
    axis_names: tuple[str, ...] = (DATA_AXIS,),
    shape: Optional[tuple[int, ...]] = None,
) -> Mesh:
    """Build a Mesh over ``devices`` (count, explicit list, or None=all).

    ``shape`` reshapes the device list for multi-axis meshes; default is
    1-D over all requested devices.
    """
    if devices is None:
        # Order by (slice, device) so the 1-D data axis is slice-
        # contiguous: XLA then lowers the allreduce hierarchically —
        # reduce over ICI within each slice, exchange partials over DCN
        # across slices — instead of striding DCN hops through the ring.
        devs = _slice_major(jax.devices())
    elif isinstance(devices, int):
        all_devs = jax.devices()
        if devices > len(all_devs):
            raise ValueError(
                f"requested {devices} devices but only {len(all_devs)} present "
                f"({[d.platform for d in all_devs[:1]]}); for CPU-mesh testing set "
                "XLA_FLAGS=--xla_force_host_platform_device_count=N before jax import"
            )
        devs = all_devs[:devices]
    else:
        devs = list(devices)
    arr = np.array(devs)
    if shape is not None:
        arr = arr.reshape(shape)
    elif len(axis_names) > 1:
        raise ValueError("multi-axis mesh needs an explicit shape")
    return Mesh(arr, axis_names)


def make_multislice_mesh(
    devices: Union[int, Sequence, None] = None,
    n_slices: Optional[int] = None,
) -> Mesh:
    """2-D ``(DCN_AXIS, DATA_AXIS)`` mesh for multi-slice deployments —
    the 256-chip BASELINE shape (e.g. 4 slices x 64 chips).

    Rows are slices: a collective over ``DATA_AXIS`` stays on ICI inside
    one slice; a collective over ``DCN_AXIS`` crosses slices over DCN.
    The BSP gradient mean over BOTH axes is lowered by XLA into exactly
    that two-tier hierarchy — the reference built the same split by hand
    with NCCL cliques inside a node and MPI across nodes
    (``lib/exchanger_strategy.py``; SURVEY.md §5.8 "topology split").

    ``n_slices``: explicit row count — required on hardware without
    ``slice_index`` metadata (CPU simulation) and for carving a single
    real slice into virtual rows; defaults to the device-reported slice
    count.
    """
    if devices is None or isinstance(devices, int):
        devs = list(make_mesh(devices).devices.reshape(-1))
    else:
        devs = list(devices)
    # slice-contiguous ordering on EVERY path (make_mesh only sorts the
    # devices=None case): a row that straddles physical slices would put
    # DCN hops inside the 'data' axis and defeat the hierarchy
    devs = _slice_major(devs)
    slice_ids = [getattr(d, "slice_index", 0) for d in devs]
    if n_slices is None:
        n_slices = len(set(slice_ids))
    if n_slices < 1 or len(devs) % n_slices:
        raise ValueError(
            f"{len(devs)} devices do not divide into {n_slices} slices"
        )
    per = len(devs) // n_slices
    arr = np.array(devs).reshape(n_slices, per)
    if len(set(slice_ids)) > 1:
        # real slice metadata present: every row must be single-slice
        for r in range(n_slices):
            row_ids = {slice_ids[r * per + i] for i in range(per)}
            if len(row_ids) > 1:
                raise ValueError(
                    f"mesh row {r} would span physical slices {sorted(row_ids)} "
                    f"(device count {len(devs)} does not align with the "
                    "per-slice chip count); choose a device count that is a "
                    "whole number of slices"
                )
    return Mesh(arr, (DCN_AXIS, DATA_AXIS))


def slice_topology(mesh: Mesh) -> tuple[int, int]:
    """``(n_slices, per_slice)`` of a mesh, read off the DCN axis — the
    slice decomposition the hierarchical exchange strategy and the
    per-link-class traffic accounting share. A mesh without a
    ``DCN_AXIS`` is one slice: every hop is ICI."""
    names = tuple(mesh.axis_names)
    if DCN_AXIS not in names:
        return 1, int(mesh.devices.size)
    n_slices = int(mesh.shape[DCN_AXIS])
    per = 1
    for a in names:
        if a != DCN_AXIS:
            per *= int(mesh.shape[a])
    return n_slices, per


def make_worker_group_mesh(mesh: Mesh, group_size: int,
                           n_slices: Optional[int] = None):
    """Reshape a 1-D mesh for async-rule worker groups: ``(worker,
    data)`` rows are workers, columns the chips data-parallel WITHIN one
    worker. Returns ``(mesh2d, batch_spec, grad_sync)`` — the shared
    construction for EASGD/GoSGD group mode (a group must behave as ONE
    bigger worker: BSP psum inside, worker-axis collectives across).

    **Slice awareness** (BASELINE config #4 at pod scale — e.g. 16
    workers x 16 chips over multiple slices): devices are slice-major
    (the canonical ``make_mesh`` order), so with ``group_size`` dividing
    the per-slice chip count every group row sits INSIDE one slice — the
    per-step group psum rides ICI — while the worker axis spans slices,
    putting the cheap every-``avg_freq`` elastic/gossip collectives on
    DCN. The reference built the same split with NCCL-in-node /
    MPI-across-nodes (SURVEY.md §3.3, §5.8). ``n_slices`` simulates the
    slice boundaries on hardware without ``slice_index`` metadata (CPU
    meshes / carving one physical slice); with real metadata the
    physical boundaries are validated instead.
    """
    from jax.sharding import PartitionSpec

    from theanompi_tpu.parallel.strategies import get_strategy

    g = max(1, int(group_size))
    devs = list(np.asarray(mesh.devices).reshape(-1))
    n_dev = len(devs)
    if n_dev % g:
        raise ValueError(f"{n_dev} devices do not divide into groups of {g}")
    if n_slices is not None and n_slices > 1 and n_dev % n_slices:
        # validate the slice count even for ungrouped workers (g == 1),
        # so `tmpi EASGD --slices 3` fails like BSP's multislice path
        # does instead of silently ignoring the topology claim
        raise ValueError(
            f"{n_dev} devices do not divide into {n_slices} slices"
        )
    if g == 1:
        return mesh, None, None
    devs = _slice_major(devs)
    slice_ids = [getattr(d, "slice_index", 0) for d in devs]
    if n_slices is not None and n_slices > 1:
        per_slice = n_dev // n_slices
        if len(set(slice_ids)) <= 1:
            # no (or uniform) hardware metadata: impose virtual slice ids
            slice_ids = [i // per_slice for i in range(n_dev)]
    if len(set(slice_ids)) > 1:
        # every group row must be single-slice: a group straddling
        # slices would put its PER-STEP data-axis psum on DCN, defeating
        # the topology split (workers exchange rarely; groups every step)
        for w in range(n_dev // g):
            row = {slice_ids[w * g + i] for i in range(g)}
            if len(row) > 1:
                raise ValueError(
                    f"worker group {w} would span slices {sorted(row)}: "
                    f"group_size {g} must divide the per-slice chip count "
                    f"({n_dev} devices / {len(set(slice_ids))} slices)"
                )
    mesh2d = Mesh(
        np.array(devs).reshape(n_dev // g, g), (WORKER_AXIS, DATA_AXIS)
    )
    return (
        mesh2d,
        PartitionSpec((WORKER_AXIS, DATA_AXIS)),
        get_strategy("psum", DATA_AXIS, g),
    )


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec())


# --------------------------------------------------------------------------
# topology serialization + host-mediated redistribution (elastic PR):
# checkpoints stamp the mesh/spec metadata these helpers produce, and
# load_resharded (utils/checkpoint.py) rebuilds per-device shards on a
# DIFFERENT mesh from it — the collective-based redistribution scheme of
# "Memory-efficient array redistribution" (arXiv:2112.01075): every host
# materializes only the shards it owns under a computed transfer plan,
# never a full array.
# --------------------------------------------------------------------------


def mesh_topology(mesh: Mesh) -> dict:
    """JSON-serializable identity of a mesh: shape + axis names. Two
    meshes with equal topology dicts produce identical shard layouts
    for any PartitionSpec, so a checkpoint stamped with one can load on
    the other without resharding (bit-identical resume)."""
    return {
        "shape": [int(s) for s in mesh.devices.shape],
        "axes": [str(a) for a in mesh.axis_names],
    }


def spec_to_json(spec) -> Optional[list]:
    """``PartitionSpec -> per-dim JSON``: each entry is ``None``
    (replicated dim) or a list of axis names. ``None`` for a non-spec
    (fully replicated / non-NamedSharding leaf)."""
    if spec is None:
        return None
    out = []
    for dim in tuple(spec):
        if dim is None:
            out.append(None)
        elif isinstance(dim, str):
            out.append([dim])
        else:
            out.append([str(a) for a in dim])
    return out


def spec_from_json(dims: Optional[list]) -> PartitionSpec:
    if not dims:
        return PartitionSpec()
    return PartitionSpec(*[
        None if d is None else (d[0] if len(d) == 1 else tuple(d))
        for d in dims
    ])


def leaf_spec_json(leaf) -> Optional[list]:
    """The serialized PartitionSpec of one live array leaf, or None when
    the leaf carries no NamedSharding (host numpy, single-device plain
    placement) — which a reshard treats as replicated."""
    sharding = getattr(leaf, "sharding", None)
    spec = getattr(sharding, "spec", None)
    return spec_to_json(spec) if spec is not None else None


def put_resharded(mesh: Mesh, spec: PartitionSpec, shape, dtype, read_fn):
    """Build a global array on ``mesh`` where each addressable shard's
    content comes from ``read_fn(bounds)`` (bounds = ((start, stop), ...)
    in GLOBAL index space). This is the placement half of the
    arXiv:2112.01075 redistribution: each host materializes only the
    shards it owns — the cross-host "all-to-all" data movement happens
    through the shared checkpoint storage the read_fn reads from, so no
    host ever allocates the full array for a sharded leaf."""
    import jax.numpy as jnp
    import numpy as np

    sharding = NamedSharding(mesh, spec)

    def cb(idx):
        bounds = tuple(
            sl.indices(dim)[:2] for sl, dim in zip(idx, shape)
        )
        return np.asarray(read_fn(bounds), dtype=dtype)

    out = jax.make_array_from_callback(tuple(shape), sharding, cb)
    # The assembled shards can zero-copy-BORROW their host buffers
    # (checkpoint views / numpy temporaries) on the CPU backend, and
    # every engine donates its state into the first train step —
    # donating a borrowed buffer frees memory XLA does not own, which
    # surfaces as flaky heap corruption at the next compile. The jitted
    # per-shard copy re-materializes the array into XLA-owned,
    # donation-safe buffers; it is sharding-preserving, so still no
    # full-array gather on any host.
    return jax.jit(jnp.copy)(out)


def batch_sharding(mesh: Mesh, axis: Union[str, tuple, None] = None) -> NamedSharding:
    """Shard the leading (batch) dim across the data axis (1-D mesh) or
    across ALL mesh axes (multi-slice mesh)."""
    if axis is None:
        axis = batch_axes(mesh)
    return NamedSharding(mesh, PartitionSpec(axis))


def host_local_batch_slice(mesh: Mesh, global_batch: int) -> slice:
    """The slice of the global batch this host should produce.

    Single-controller: the whole batch. Multi-controller (one process
    per TPU host, reference: one loader per worker rank): each host
    feeds only its addressable shard — the analogue of the reference's
    per-rank batch-file partition (``models/data/imagenet.py``).
    """
    n_proc = jax.process_count()
    per_host = global_batch // n_proc
    idx = jax.process_index()
    return slice(idx * per_host, (idx + 1) * per_host)


def _place_batch(mesh: Mesh, x, sharding: NamedSharding, batch_dim: int,
                 global_rows: Optional[int]):
    """Shared placement core. Multi-controller: assemble the global array
    from per-process rows of ``batch_dim`` (no cross-host copy)."""
    n_proc = jax.process_count()
    if n_proc > 1:
        x = np.asarray(x)
        rows = global_rows if global_rows is not None else x.shape[batch_dim] * n_proc
        shape = list(x.shape)
        shape[batch_dim] = rows
        return jax.make_array_from_process_local_data(sharding, x, tuple(shape))
    return jax.device_put(x, sharding)


def put_global_batch(mesh: Mesh, x, axis=None, global_rows: Optional[int] = None):
    """Place a host batch onto the mesh sharded along the data axis.

    ``x`` holds THIS PROCESS's rows: in single-controller runs that is
    the whole global batch; in multi-controller runs each host passes
    only its ``host_local_batch_slice`` rows (the analogue of the
    reference's per-rank batch-file partition). ``global_rows`` overrides
    the inferred global batch (defaults to ``rows_here * process_count``,
    the equal-split case)."""
    return _place_batch(mesh, x, batch_sharding(mesh, axis), 0, global_rows)


def put_stacked_batches(mesh: Mesh, x, axis=None, global_rows: Optional[int] = None):
    """Place a STACKED group of batches ``[k, batch, ...]`` — the fused
    multi-step dispatch ships k steps of data in one transfer; dim 0 (the
    step index) is replicated, dim 1 (the batch) shards across the mesh.
    Multi-controller hosts pass their local rows of dim 1 as usual."""
    if axis is None:
        axis = batch_axes(mesh)
    sharding = NamedSharding(mesh, PartitionSpec(None, axis))
    return _place_batch(mesh, x, sharding, 1, global_rows)


def first_local_value(x):
    """First element of a (possibly multi-host sharded) array, read from
    this process's first addressable shard — ``device_get`` of a global
    array raises on non-addressable shards, this never does. For values
    replicated or stacked per-worker (engine step counters), any shard's
    first element is the answer."""
    try:
        shard = x.addressable_shards[0].data
    except AttributeError:  # plain numpy / python scalar
        shard = x
    return np.asarray(shard).reshape(-1)[0]


def stack_replicas(tree, n: int):
    """Broadcast a pytree to ``n`` stacked replicas on a new leading axis
    (per-worker state for the EASGD/GoSGD rules)."""
    import jax.numpy as jnp

    return jax.tree_util.tree_map(
        lambda a: jnp.broadcast_to(a[None], (n, *a.shape)), tree
    )
