"""Checkpoint / resume.

Rebuild of the reference's weight persistence (reference:
``lib/helper_funcs.py`` — ``save_weights``/``load_weights``: one ``.npy``
per Theano shared param, saved each epoch from rank 0, no atomicity;
SURVEY.md §5.4). Here the WHOLE TrainState pytree (params + BatchNorm
state + optimizer state + step) plus the RNG key goes into one ``.npz``
written atomically (tmp + rename), so resume restores training exactly —
including the LR schedule, which is a pure function of the restored step.

Arrays are pulled to host with ``jax.device_get``; on restore the caller
re-places them (replicated or sharded) via its usual device_put path.
Multi-host: only process 0 writes (same contract as the reference's
rank-0 save); sharded-per-host formats can layer on later without
changing this API.
"""

from __future__ import annotations

import errno
import os
import re
import tempfile
import threading
import time
import zlib
from typing import Any, Callable, Optional

import numpy as np

import jax
import jax.numpy as jnp

PyTree = Any

_CKPT_RE = re.compile(r"ckpt_(\d+)\.npz$")
_SHARD_RE = re.compile(r"ckpt_(\d+)\.proc(\d+)of(\d+)\.npz$")

# per-array integrity manifest key (fault-tolerance PR): JSON map of
# array name -> {crc32, nbytes}, embedded IN the .npz at save time so a
# checkpoint copied anywhere carries its own verification chain
_INTEGRITY_KEY = "__integrity__"

# versioned topology manifest key (elastic PR): JSON record of the mesh
# the state was saved under (shape + axis names), the per-leaf
# PartitionSpecs, and the engine's elastic reshard policies — what
# :func:`load_resharded` needs to move a checkpoint onto a DIFFERENT
# mesh without ever materializing a full array on one host. Single-file
# saves carry it as an .npz entry; per-host sharded saves embed it in
# their ``__meta__`` JSON.
_TOPOLOGY_KEY = "__topology__"
TOPOLOGY_VERSION = 1


def _path_key(path) -> str:
    """Tree path -> the flat '/'-joined leaf key used by every format."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)


def _topology_manifest(state: PyTree, topology: Optional[dict]) -> Optional[dict]:
    """The versioned ``__topology__`` manifest for one save: the caller's
    mesh identity + elastic policies (``topology`` =
    ``{"mesh": parallel.mesh.mesh_topology(mesh), "elastic": {...}}``)
    extended with the per-leaf PartitionSpec of every LIVE leaf (read
    off the arrays before the host pull — a NamedSharding-less leaf
    records None = replicated). :func:`load_resharded` validates its
    transfer plan against the stamped leaf SET (an unstamped leaf in
    the target template is a structure mismatch); the spec values are
    for inspection/debugging — the plan's source bounds come from the
    sharded-set ``__meta__`` catalogues, not from here. None when the
    caller stamps nothing (API users saving plain host trees keep the
    pre-elastic format)."""
    if topology is None:
        return None
    from theanompi_tpu.parallel.mesh import leaf_spec_json

    leaves = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        leaves[_path_key(path)] = {"spec": leaf_spec_json(leaf)}
    out = {
        "version": TOPOLOGY_VERSION,
        "mesh": topology.get("mesh"),
        "elastic": topology.get("elastic") or {},
        "leaves": leaves,
    }
    if topology.get("recipe") is not None:
        # the engine's ShardingRecipe identity (parallel/recipe.py
        # ``as_json``): the DECLARED spec source the live-array specs
        # above were placed by — the sharding analyzer's SHARD004
        # train->serve handoff check keys on this declaration
        out["recipe"] = topology["recipe"]
    return out


def _array_crc(arr: np.ndarray) -> dict:
    """{crc32, nbytes} of one saved array's raw bytes."""
    buf = np.ascontiguousarray(arr).tobytes()
    return {"crc32": zlib.crc32(buf) & 0xFFFFFFFF, "nbytes": len(buf)}


def _with_integrity(flat: dict) -> dict:
    """Append the CRC32 manifest over every entry already in ``flat``
    (called LAST before np.savez, so the manifest covers rng/meta too)."""
    import json as _json

    manifest = {k: _array_crc(np.asarray(v)) for k, v in flat.items()}
    flat[_INTEGRITY_KEY] = np.asarray(_json.dumps(manifest))
    return flat


# --------------------------------------------------------------------------
# injectable writer shim (chaos PR): storage faults — ENOSPC mid-write,
# slow/stalled writes — happen INSIDE the filesystem write, where no
# step-loop hook can reach. Both save formats funnel their serialize+
# rename through _atomic_savez, which consults the installed hook with
# the step being saved; utils/faults.FaultInjector.write_fault is the
# one production hook (deterministic KIND@STEP semantics), but any
# callable ``step -> Optional[(kind, arg)]`` works.
# --------------------------------------------------------------------------

_WRITE_FAULT_HOOK: Optional[Callable[[int], Optional[tuple]]] = None


def set_write_fault_hook(hook: Optional[Callable[[int], Optional[tuple]]]
                         ) -> None:
    """Install (or clear, with None) the process-wide checkpoint write
    fault hook. The driver installs its FaultInjector's ``write_fault``
    for the run and clears it in its finally — the hook is global
    because the async writer thread has no per-save plumbing."""
    global _WRITE_FAULT_HOOK
    _WRITE_FAULT_HOOK = hook


class _EnospcWriter:
    """File wrapper that raises ``OSError(ENOSPC)`` once ``limit``
    bytes have been written — the injected 'disk filled up mid-write':
    a torn partial file exists under the TMP name when the error
    surfaces, exactly what a real quota hit leaves behind.

    After the failure the wrapper goes DEAD: writes are absorbed into a
    simulated position instead of touching the (by then closed) real
    file. np.savez's internal ZipFile survives the exception holding
    this object as its ``fp``; its garbage-collected ``close()`` then
    flushes a central directory into the void coherently instead of
    spraying 'Exception ignored in ZipFile.__del__' noise over the
    real error."""

    def __init__(self, f, limit: int):
        self._f = f
        self._limit = int(limit)
        self._written = 0
        self._dead = False
        self._pos = 0  # simulated position once dead

    def write(self, data):
        if self._dead:
            self._pos += len(data)
            return len(data)
        if self._written + len(data) > self._limit:
            space = max(0, self._limit - self._written)
            if space:
                self._f.write(data[:space])
                self._written += space
            self._dead = True
            self._pos = self._written
            raise OSError(errno.ENOSPC,
                          "No space left on device (injected enospc)")
        self._written += len(data)
        return self._f.write(data)

    def seek(self, offset, whence=0):
        if not self._dead:
            return self._f.seek(offset, whence)
        if whence == 0:
            self._pos = offset
        elif whence == 1:
            self._pos += offset
        return self._pos

    def tell(self):
        return self._pos if self._dead else self._f.tell()

    def flush(self):
        if not self._dead:
            self._f.flush()

    def __getattr__(self, name):
        return getattr(self._f, name)


def _atomic_savez(directory: str, path: str, flat: dict, step: int) -> None:
    """The one serialize+rename both save formats use: np.savez into a
    tmp file in ``directory``, then atomic ``os.replace`` onto ``path``.
    Any failure (a real OSError or an injected write fault) removes the
    torn tmp — the chain is never left holding a partial file under a
    final name."""
    fd, tmp = tempfile.mkstemp(dir=directory, suffix=".tmp")
    try:
        with os.fdopen(fd, "wb") as f:
            sink = f
            fault = _WRITE_FAULT_HOOK(step) if _WRITE_FAULT_HOOK else None
            if fault is not None:
                kind, arg = fault
                if kind == "slow_write":
                    time.sleep(2.0 if arg is None else float(arg))
                elif kind == "enospc":
                    # default low enough that even a tiny state's save
                    # tears mid-write (any real .npz exceeds it)
                    sink = _EnospcWriter(f, 256 if arg is None else int(arg))
            np.savez(sink, **flat)
        os.replace(tmp, path)  # atomic on POSIX
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise


def _pull_to_host(leaf) -> np.ndarray:
    """Materialize one leaf on the host. Leaves sharded across OTHER
    processes (EASGD/GoSGD per-worker state under multi-controller) are
    gathered with a cross-host collective — so this is collective: every
    process must reach it, even though only rank 0 writes the file."""
    if isinstance(leaf, jax.Array) and not leaf.is_fully_addressable:
        from jax.experimental import multihost_utils

        return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))
    return np.asarray(jax.device_get(leaf))


def _flatten_with_paths(tree: PyTree) -> dict[str, np.ndarray]:
    flat = {}
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in leaves_with_paths:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        flat[key] = _pull_to_host(leaf)
    return flat


def save_checkpoint(
    directory: str,
    state: PyTree,
    step: int,
    rng: Optional[jax.Array] = None,
    keep: int = 3,
    extra_meta: Optional[dict] = None,
    topology: Optional[dict] = None,
) -> Optional[str]:
    """Atomically write ``ckpt_{step}.npz``; prune to the newest ``keep``.
    COLLECTIVE in multi-host runs: every process must call it (sharded
    leaves are gathered cross-host), then only process 0 writes; returns
    the path (or None on non-writer processes).

    ``extra_meta`` (JSON-serializable dict) is embedded in the file and
    readable via :func:`read_checkpoint_meta` — the driver records the
    pipeline stack layout here so a checkpoint copied into a fresh dir
    (without its ``pipeline_layout.json`` sidecar) still refuses to load
    layer-permuted.

    ``topology`` (``{"mesh": mesh_topology(mesh), "elastic": {...}}``)
    stamps the versioned ``__topology__`` manifest that makes the
    checkpoint mesh-portable via :func:`load_resharded`; the per-leaf
    PartitionSpecs are read off the live state before the host pull."""
    from theanompi_tpu.obs.spans import obs_span

    topo = _topology_manifest(state, topology)
    # checkpoint_gather span (obs/spans.py): the device->host gather,
    # the expensive half of a save — runs on whichever thread calls
    # (the AsyncCheckpointer's writer thread under async saves). Named
    # apart from the driver's 'checkpoint' bracket so a SYNC save does
    # not double-count the same wall time under one kind.
    with obs_span("checkpoint_gather"):
        flat = _flatten_with_paths(state)
    if topo is not None:
        import json as _json

        flat[_TOPOLOGY_KEY] = np.asarray(_json.dumps(topo))
    if extra_meta:
        import json as _json

        flat["__usermeta__"] = np.asarray(_json.dumps(extra_meta))
    if rng is not None:
        # record WHICH impl produced the key data: width alone is
        # ambiguous (rbg and unsafe_rbg share width 4 but derive
        # split/fold_in differently), and resume must reproduce the
        # exact stream of an uninterrupted run
        if jnp.issubdtype(getattr(rng, "dtype", None), jax.dtypes.prng_key):
            impl = str(jax.random.key_impl(rng))
            rng = jax.random.key_data(rng)  # typed key -> raw uint32 data
            raw = np.asarray(jax.device_get(rng))
        else:
            # raw key data: assume the process default impl, unless the
            # data width contradicts it (e.g. an explicit threefry
            # PRNGKey under the rbg default) — then infer from width so
            # the checkpoint stays loadable
            raw = np.asarray(jax.device_get(rng))
            impl = jax.config.jax_default_prng_impl
            width = raw.shape[-1] if raw.ndim else None
            if width != _KEY_WIDTH_BY_IMPL.get(impl):
                impl = _KEY_IMPL_BY_WIDTH.get(width)
                if impl is None:
                    raise ValueError(
                        f"rng has unrecognized key-data shape {raw.shape}"
                    )
        flat["__rng__"] = raw
        flat["__rng_impl__"] = np.asarray(impl)
    if jax.process_index() != 0:
        return None
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"ckpt_{step}.npz")
    _atomic_savez(directory, path, _with_integrity(flat), step)
    _prune(directory, keep)
    _prune_sharded(directory, keep)  # a dir toggled from --ckpt-sharded
    return path


def _prune(directory: str, keep: int) -> None:
    ckpts = sorted(
        (int(m.group(1)), f)
        for f in os.listdir(directory)
        if (m := _CKPT_RE.search(f))
    )
    for _, f in ckpts[:-keep] if keep else []:
        try:
            os.unlink(os.path.join(directory, f))
        except FileNotFoundError:
            # the background scrubber may have quarantined (moved) the
            # member between our listing and this unlink — gone either
            # way, and a hygiene race must not fail a save
            pass


# --------------------------------------------------------------------------
# per-host sharded checkpoints (SURVEY.md §5.4 "written per-host for
# sharded arrays"; round-3 verdict item 8)
# --------------------------------------------------------------------------


def _norm_index(index, shape) -> tuple:
    """Normalize a shard's index (tuple of slices) to ((start, stop), ...)."""
    out = []
    for sl, dim in zip(index, shape):
        start, stop, step = sl.indices(dim)
        if step != 1:
            raise ValueError(f"strided shard index {sl} unsupported")
        out.append((start, stop))
    return tuple(out)


def save_checkpoint_sharded(
    directory: str,
    state: PyTree,
    step: int,
    rng: Optional[jax.Array] = None,
    keep: int = 3,
    extra_meta: Optional[dict] = None,
    topology: Optional[dict] = None,
) -> Optional[str]:
    """Per-host sharded save: each process writes ONLY the shards it
    holds — no cross-host gather and no rank-0 host-memory spike, unlike
    :func:`save_checkpoint` (which pulls every leaf to one host; fine at
    138M params, a ceiling for ZeRO-sharded or pod-scale states).

    Layout: ``ckpt_{step}.proc{k}of{n}.npz`` per process. Array keys are
    ``{leafpath}::s{j}`` with a ``__meta__`` JSON entry recording, per
    leaf, the global shape/dtype and each saved shard's index bounds.
    Each unique shard is written by exactly ONE process (the
    minimum-process owner, decided from ``global_shards`` metadata — no
    communication). Restore (:func:`load_checkpoint`, which dispatches on
    the filename) reassembles full arrays from the complete file set
    under ANY process count — reshard-on-restore is the caller's normal
    device_put. A set missing any of its n files is ignored by
    :func:`latest_checkpoint` (atomicity without barriers: per-file
    tmp+rename, completeness by counting).
    """
    import json as _json

    n_proc = jax.process_count()
    me = jax.process_index()
    flat: dict[str, np.ndarray] = {}
    meta: dict[str, Any] = {"leaves": {}, "step": int(step)}
    if extra_meta:
        # every member file carries it: read_checkpoint_meta must work
        # from any process's file under any later process count
        meta["user"] = extra_meta
    topo = _topology_manifest(state, topology)
    if topo is not None:
        # every member carries the full manifest (like "user"): the
        # reshard plan must be computable from any one member file
        meta["topology"] = topo
    leaves_with_paths = jax.tree_util.tree_flatten_with_path(state)[0]
    for path, leaf in leaves_with_paths:
        key = _path_key(path)
        if not isinstance(leaf, jax.Array):
            if me == 0:  # host scalars/numpy: rank 0 records them whole
                arr = np.asarray(leaf)
                flat[f"{key}::s0"] = arr
                meta["leaves"][key] = {
                    "shape": list(arr.shape), "dtype": str(arr.dtype),
                    "shards": [{"bounds": [[0, d] for d in arr.shape], "file": 0}],
                }
            continue
        shape = leaf.shape
        # owner = minimum process holding each unique shard index
        owners: dict[tuple, int] = {}
        for sh in leaf.global_shards:
            b = _norm_index(sh.index, shape)
            p = sh.device.process_index
            owners[b] = min(owners.get(b, p), p)
        entry = {"shape": list(shape), "dtype": str(leaf.dtype), "shards": []}
        mine = {}
        for sh in leaf.addressable_shards:
            b = _norm_index(sh.index, shape)
            if owners[b] == me and b not in mine:
                mine[b] = np.asarray(sh.data)
        for j, (b, arr) in enumerate(sorted(mine.items())):
            flat[f"{key}::s{len(entry['shards'])}"] = arr
            entry["shards"].append({"bounds": [list(x) for x in b], "file": me})
        # every process records the SAME leaf catalogue structure for its
        # own shards only; load merges catalogues across files
        meta["leaves"][key] = entry
    if rng is not None and me == 0:
        if jnp.issubdtype(getattr(rng, "dtype", None), jax.dtypes.prng_key):
            meta["rng_impl"] = str(jax.random.key_impl(rng))
            flat["__rng__"] = np.asarray(jax.device_get(jax.random.key_data(rng)))
        else:
            raw = np.asarray(jax.device_get(rng))
            impl = jax.config.jax_default_prng_impl
            width = raw.shape[-1] if raw.ndim else None
            if width != _KEY_WIDTH_BY_IMPL.get(impl):
                impl = _KEY_IMPL_BY_WIDTH.get(width)
            meta["rng_impl"] = impl
            flat["__rng__"] = raw
    flat["__meta__"] = np.asarray(_json.dumps(meta))
    from theanompi_tpu.obs.spans import obs_span

    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, f"ckpt_{step}.proc{me}of{n_proc}.npz")
    # checkpoint_write span (obs/spans.py): the serialize+rename of
    # this host's shard files (distinct from the driver's
    # 'checkpoint' bracket — see save_checkpoint's gather span note)
    with obs_span("checkpoint_write"):
        _atomic_savez(directory, path, _with_integrity(flat), step)
    _prune_sharded(directory, keep)
    if jax.process_index() == 0:
        _prune(directory, keep)  # a dir toggled from single-file saves
    return path


def _readable_nonempty(path: str) -> bool:
    """False for a zero-byte or stat-unreadable file — on some
    filesystems a host dying mid-``os.replace`` leaves a zero-length
    entry under the final name; resume discovery must treat it as
    ABSENT (an incomplete save), not raise on it."""
    try:
        return os.path.getsize(path) > 0
    except OSError:
        return False


def _sharded_sets(directory: str) -> dict[int, list[str]]:
    """step -> sorted COMPLETE file sets (all n present); incomplete
    sets (a host died mid-save) are excluded, and a zero-byte or
    unreadable member counts as missing (see :func:`_readable_nonempty`)."""
    by_step: dict[int, dict[int, tuple[int, str]]] = {}
    # sorted: listing order is filesystem/attribute-cache dependent per
    # host; the dict fill is order-insensitive today, but resume-step
    # agreement across controllers must not hinge on that staying true
    for f in sorted(os.listdir(directory)):
        if m := _SHARD_RE.search(f):
            if not _readable_nonempty(os.path.join(directory, f)):
                continue
            step, k, n = int(m.group(1)), int(m.group(2)), int(m.group(3))
            by_step.setdefault(step, {})[k] = (n, f)
    out = {}
    for step, files in by_step.items():
        n = next(iter(files.values()))[0]
        if len(files) == n and all(v[0] == n for v in files.values()):
            out[step] = [
                os.path.join(directory, files[k][1]) for k in range(n)
            ]
    return out


def _prune_sharded(directory: str, keep: int) -> None:
    if not keep:
        return
    sets = _sharded_sets(directory)
    for step in sorted(sets)[:-keep]:
        for f in sets[step]:
            try:
                os.unlink(f)
            except FileNotFoundError:
                pass


def _load_sharded(path: str, state_template: PyTree):
    """Reassemble a sharded set from its proc-0 member path."""
    import json as _json

    m = _SHARD_RE.search(os.path.basename(path))
    if not m:
        raise ValueError(f"{path!r} is not a sharded checkpoint member")
    directory = os.path.dirname(path) or "."
    step = int(m.group(1))
    files = _sharded_sets(directory).get(step)
    if files is None:
        raise FileNotFoundError(
            f"sharded checkpoint set for step {step} in {directory} is "
            "incomplete (a host's file is missing)"
        )
    datas = [np.load(f) for f in files]
    metas = [_json.loads(str(d["__meta__"])) for d in datas]
    # merged catalogue: leaf -> (shape, dtype, [(bounds, file_idx, key)])
    catalogue: dict[str, Any] = {}
    for fi, meta in enumerate(metas):
        for key, entry in meta["leaves"].items():
            cat = catalogue.setdefault(
                key, {"shape": tuple(entry["shape"]), "dtype": entry["dtype"],
                      "pieces": []}
            )
            for j, sh in enumerate(entry["shards"]):
                cat["pieces"].append((sh["bounds"], fi, f"{key}::s{j}"))
    rng = None
    if "__rng__" in datas[0].files:
        rng = wrap_saved_rng(datas[0]["__rng__"], impl=metas[0].get("rng_impl"))

    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(state_template)
    new_leaves = []
    for p, leaf in leaves_with_paths:
        key = "/".join(str(getattr(q, "key", getattr(q, "idx", q))) for q in p)
        if key not in catalogue:
            raise KeyError(
                f"sharded checkpoint step {step} is missing {key!r} — "
                f"structure mismatch (available: {sorted(catalogue)[:8]}...)"
            )
        cat = catalogue[key]
        want_shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        want_dtype = getattr(leaf, "dtype", None) or np.result_type(leaf)
        if cat["shape"] != want_shape:
            raise ValueError(
                f"checkpoint leaf {key!r} has shape {cat['shape']}, "
                f"expected {want_shape}"
            )
        full = np.empty(cat["shape"], dtype=cat["dtype"])
        filled = 0
        for bounds, fi, akey in cat["pieces"]:
            sl = tuple(slice(b[0], b[1]) for b in bounds)
            piece = datas[fi][akey]
            full[sl] = piece
            filled += piece.size
        if filled < full.size:
            raise ValueError(
                f"checkpoint leaf {key!r}: shards cover {filled} of "
                f"{full.size} elements — incomplete save"
            )
        new_leaves.append(full.astype(want_dtype))
    return jax.tree_util.tree_unflatten(treedef, new_leaves), rng


def read_checkpoint_meta(path: str) -> dict:
    """The ``extra_meta`` dict embedded at save time (empty dict if the
    checkpoint predates the field). Dispatches on the filename like
    :func:`load_checkpoint`; for per-host sharded sets any member file
    carries the meta, so the given member alone suffices."""
    import json as _json

    data = np.load(path)
    if _SHARD_RE.search(os.path.basename(path)):
        meta = _json.loads(str(data["__meta__"]))
        return meta.get("user", {})
    if "__usermeta__" in data.files:
        return _json.loads(str(data["__usermeta__"]))
    return {}


def checkpoint_step(path: Optional[str]) -> int:
    """The step number encoded in a checkpoint filename; -1 for None
    (used to compare resume decisions across controller processes)."""
    if path is None:
        return -1
    base = os.path.basename(path)
    m = _SHARD_RE.search(base) or _CKPT_RE.search(base)
    if not m:
        raise ValueError(f"{path!r} is not a checkpoint path")
    return int(m.group(1))


def _verify_npz(path: str) -> bool:
    """One .npz member checks out: every array decompresses, and when an
    integrity manifest is embedded (post-fault-tolerance saves) each
    array's CRC32 matches it exactly. Truncation is caught either way
    (np.savez's zip central directory lives at the END of the file);
    the manifest adds end-to-end bit-corruption coverage and detects a
    manifest/content mismatch. Never raises — a corrupt file is a False,
    not an exception out of resume discovery."""
    import json as _json

    if not _readable_nonempty(path):
        return False
    try:
        data = np.load(path)
        manifest = None
        if _INTEGRITY_KEY in data.files:
            manifest = _json.loads(str(data[_INTEGRITY_KEY]))
            if set(manifest) != {k for k in data.files if k != _INTEGRITY_KEY}:
                return False
        for k in data.files:
            if k == _INTEGRITY_KEY:
                continue
            arr = data[k]  # decompress (zip-level CRC checked here)
            if manifest is not None and _array_crc(arr) != manifest[k]:
                return False
        return True
    except Exception:  # noqa: BLE001 — any read failure means corrupt
        return False


def verify_checkpoint(path: str) -> bool:
    """True when ``path`` is a restorable checkpoint: for a single-file
    save, the file itself verifies (:func:`_verify_npz`); for a per-host
    sharded member, EVERY member of its complete set verifies (one
    host's corrupt shard poisons the whole step). Filename-dispatched
    like :func:`load_checkpoint`."""
    if _SHARD_RE.search(os.path.basename(path)):
        directory = os.path.dirname(path) or "."
        m = _SHARD_RE.search(os.path.basename(path))
        files = _sharded_sets(directory).get(int(m.group(1)))
        if files is None:
            return False
        return all(_verify_npz(f) for f in files)
    return _verify_npz(path)


def _keep_chain(directory: str) -> list[tuple[int, int, str]]:
    """The keep-chain, newest first: every restorable-looking candidate
    as ``(step, tie_break, path)`` — single-file ``ckpt_N.npz`` plus
    COMPLETE per-host sharded sets (as their proc-0 member path).
    Zero-byte files (a host died mid-``os.replace``) are absent. The
    tie-break makes a single file win a step tie with a sharded set
    (matches the pre-verify resolution order). Shared by
    :func:`latest_checkpoint` and :func:`newer_verified_checkpoint` so
    the two discovery paths can never order the chain differently."""
    if not os.path.isdir(directory):
        return []
    candidates: list[tuple[int, int, str]] = []
    # sorted for cross-host determinism: every controller must walk the
    # keep-chain in the same order (rank-divergence lint SPMD302)
    for f in sorted(os.listdir(directory)):
        if m := _CKPT_RE.search(f):
            p = os.path.join(directory, f)
            if _readable_nonempty(p):
                candidates.append((int(m.group(1)), 1, p))
    for step, files in _sharded_sets(directory).items():
        candidates.append((step, 0, files[0]))
    return sorted(candidates, reverse=True)


def _walk_verified(candidates, verify: bool) -> Optional[str]:
    """First candidate that verifies (or the first outright when
    ``verify`` is False); corrupt entries are skipped loudly."""
    for _, _, path in candidates:
        if not verify or verify_checkpoint(path):
            return path
        print(
            f"[checkpoint] skipping corrupt/truncated {path!r} "
            "(integrity check failed); walking back the keep-chain",
            flush=True,
        )
    return None


def latest_checkpoint(directory: str, verify: bool = False) -> Optional[str]:
    """Newest restorable checkpoint: single-file ``ckpt_N.npz`` or a
    COMPLETE per-host sharded set (returned as its proc-0 member path;
    ``load_checkpoint`` dispatches on the name). Zero-byte files (a
    host died mid-``os.replace``) are treated as absent.

    ``verify=True`` walks BACK the keep-chain past corrupt/truncated
    checkpoints (per-array CRC manifest + decompress check,
    :func:`verify_checkpoint`) instead of returning a newest file that
    will explode at load — the resume/rollback contract."""
    return _walk_verified(_keep_chain(directory), verify)


def newer_verified_checkpoint(directory: str, than_step: int) -> Optional[str]:
    """Newest VERIFIED checkpoint strictly newer than ``than_step``, or
    None — the serving hot-reloader's poll (serve/reload.py): "is there
    a newer verified step than the one I already serve?".

    Short-circuits at ``than_step``: the walk stops BEFORE reaching the
    file the caller already holds, so a steady-state poll (no new saves)
    verifies nothing at all — it never re-decompresses and re-CRCs the
    multi-hundred-MB checkpoint it is already serving, and a corrupt
    NEWER file is skipped (walking back) without ever touching the
    served one. Always verifies: an unverified path handed to a live
    serving engine would explode mid-swap."""
    return _walk_verified(
        [c for c in _keep_chain(directory) if c[0] > than_step], verify=True
    )


def load_checkpoint(
    path: str, state_template: PyTree
) -> tuple[PyTree, Optional[np.ndarray]]:
    """Restore a pytree matching ``state_template``'s structure (the
    template supplies structure + dtypes; values are ignored). Returns
    ``(state, rng_or_None)``: state leaves as host numpy arrays (caller
    device_puts), rng as a typed PRNG key wrapped with the impl that
    wrote it (see :func:`wrap_saved_rng`).

    A structure mismatch (renamed layer, different optimizer) raises
    KeyError naming the missing entry, rather than silently reinitializing
    — resume must be exact or explicit.

    Dispatches on the filename: per-host sharded sets
    (``ckpt_N.procKofM.npz``, :func:`save_checkpoint_sharded`) are
    reassembled from ALL member files — restorable under any process
    count.
    """
    if _SHARD_RE.search(os.path.basename(path)):
        return _load_sharded(path, state_template)
    data = np.load(path)
    rng = None
    if "__rng__" in data.files:
        impl = str(data["__rng_impl__"]) if "__rng_impl__" in data.files else None
        rng = wrap_saved_rng(data["__rng__"], impl=impl)

    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(state_template)
    new_leaves = []
    for p, leaf in leaves_with_paths:
        key = "/".join(str(getattr(q, "key", getattr(q, "idx", q))) for q in p)
        if key not in data.files:
            raise KeyError(
                f"checkpoint {path} is missing {key!r} — structure mismatch "
                f"(available: {sorted(data.files)[:8]}...)"
            )
        arr = data[key]
        # Read shape/dtype WITHOUT materializing the template leaf: a
        # non-fully-addressable (multi-host sharded) template would raise
        # on np.asarray, and resume templates are allowed to be the live
        # sharded state.
        want_shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        want_dtype = getattr(leaf, "dtype", None) or np.result_type(leaf)
        if tuple(arr.shape) != want_shape:
            raise ValueError(
                f"checkpoint leaf {key!r} has shape {arr.shape}, expected {want_shape}"
            )
        if arr.dtype.kind == "V" and arr.dtype.itemsize == np.dtype(want_dtype).itemsize:
            # an .npz keeps a bfloat16 leaf as raw 2-byte records: the same bits
            arr = arr.view(want_dtype)
        new_leaves.append(arr.astype(want_dtype))
    return jax.tree_util.tree_unflatten(treedef, new_leaves), rng


# key-data width -> the impl that produced it (rbg and unsafe_rbg share a
# width; rbg is what this framework defaults to, see theanompi_tpu.__init__)
_KEY_IMPL_BY_WIDTH = {2: "threefry2x32", 4: "rbg"}
_KEY_WIDTH_BY_IMPL = {"threefry2x32": 2, "rbg": 4, "unsafe_rbg": 4}


# --------------------------------------------------------------------------
# mesh-portable restore (elastic PR): read the __topology__ manifest and
# rebuild the state on a DIFFERENT mesh via a computed transfer plan —
# the collective-based redistribution scheme of "Memory-efficient array
# redistribution" (arXiv:2112.01075). Each host materializes only the
# shard regions its target devices own; the cross-host data movement
# rides the shared checkpoint storage (the npz members double as the
# all-to-all buffers), so no host ever assembles a full array for a
# sharded leaf in the per-host sharded-set format.
# --------------------------------------------------------------------------


def read_topology_manifest(path: str) -> Optional[dict]:
    """The versioned ``__topology__`` manifest stamped at save time, or
    None for a pre-elastic checkpoint. Filename-dispatched like
    :func:`load_checkpoint`; any member of a sharded set carries the
    full manifest."""
    import json as _json

    data = np.load(path)
    if _SHARD_RE.search(os.path.basename(path)):
        meta = _json.loads(str(data["__meta__"]))
        return meta.get("topology")
    if _TOPOLOGY_KEY in data.files:
        return _json.loads(str(data[_TOPOLOGY_KEY]))
    return None


def _intersect(a, b):
    """Intersection of two ``((start, stop), ...)`` bound tuples, or
    None when empty along any dim."""
    out = []
    for (a0, a1), (b0, b1) in zip(a, b):
        lo, hi = max(a0, b0), min(a1, b1)
        if lo >= hi:
            return None
        out.append((lo, hi))
    return tuple(out)


class _ShardedSource:
    """Region reader over a per-host sharded checkpoint set: the member
    files' ``__meta__`` catalogues record every saved shard's GLOBAL
    bounds, so any ``(key, bounds)`` region is assembled from exactly
    the overlapping pieces — never the whole leaf. ``reads`` records the
    largest single region fetched per key (the no-full-materialization
    proof hook tests assert on)."""

    def __init__(self, path: str):
        import json as _json

        m = _SHARD_RE.search(os.path.basename(path))
        directory = os.path.dirname(path) or "."
        step = int(m.group(1))
        files = _sharded_sets(directory).get(step)
        if files is None:
            raise FileNotFoundError(
                f"sharded checkpoint set for step {step} in {directory} "
                "is incomplete"
            )
        self._datas = [np.load(f) for f in files]
        self._metas = [_json.loads(str(d["__meta__"])) for d in self._datas]
        # key -> {shape, dtype, pieces: [(bounds, file_idx, array_key)]}
        self.catalogue: dict[str, Any] = {}
        for fi, meta in enumerate(self._metas):
            for key, entry in meta["leaves"].items():
                cat = self.catalogue.setdefault(
                    key, {"shape": tuple(entry["shape"]),
                          "dtype": entry["dtype"], "pieces": []}
                )
                for j, sh in enumerate(entry["shards"]):
                    cat["pieces"].append(
                        (tuple(tuple(b) for b in sh["bounds"]), fi,
                         f"{key}::s{j}")
                    )
        self._cache: dict = {}
        self.reads: dict[str, int] = {}

    def shape(self, key):
        return self.catalogue[key]["shape"]

    def read(self, key: str, bounds) -> np.ndarray:
        if key not in self.catalogue:
            raise KeyError(
                f"sharded checkpoint is missing {key!r} — structure "
                f"mismatch (available: {sorted(self.catalogue)[:8]}...)"
            )
        cat = self.catalogue[key]
        bounds = tuple(tuple(b) for b in bounds)
        shape = tuple(hi - lo for lo, hi in bounds)
        out = np.zeros(shape, dtype=cat["dtype"])
        want = int(np.prod(shape)) if shape else 1
        self.reads[key] = max(self.reads.get(key, 0), want)
        covered = 0
        for pbounds, fi, akey in cat["pieces"]:
            inter = _intersect(pbounds, bounds) if bounds else ()
            if bounds and inter is None:
                continue
            piece = self._cache.get((fi, akey))
            if piece is None:
                piece = self._cache[(fi, akey)] = self._datas[fi][akey]
            if not bounds:  # scalar leaf
                return np.asarray(piece)
            dst = tuple(slice(lo - b[0], hi - b[0])
                        for (lo, hi), b in zip(inter, bounds))
            srcsl = tuple(slice(lo - p[0], hi - p[0])
                          for (lo, hi), p in zip(inter, pbounds))
            out[dst] = piece[srcsl]
            covered += int(np.prod([hi - lo for lo, hi in inter]))
        if covered < want:
            raise ValueError(
                f"checkpoint leaf {key!r}: saved shards cover only "
                f"{covered} of {want} requested elements — incomplete set"
            )
        return out

    def end_leaf(self) -> None:
        """Drop decompressed piece buffers between leaves — the reshard
        holds at most one leaf's touched pieces in host memory."""
        self._cache.clear()

    def rng(self):
        if "__rng__" in self._datas[0].files:
            return wrap_saved_rng(self._datas[0]["__rng__"],
                                  impl=self._metas[0].get("rng_impl"))
        return None


class _SingleFileSource:
    """Region reader over a single-file checkpoint. The npz member IS
    the full array, so a read materializes the whole leaf on this host
    (the format already implies that — it was saved by a rank-0 gather);
    the per-host memory guarantee belongs to the sharded-set format."""

    def __init__(self, path: str):
        self._data = np.load(path)
        self._cache: dict = {}
        self.reads: dict[str, int] = {}

    def shape(self, key):
        if key not in self._data.files:
            raise KeyError(
                f"checkpoint is missing {key!r} — structure mismatch"
            )
        arr = self._cache.get(key)
        if arr is None:
            arr = self._cache[key] = self._data[key]
        return tuple(arr.shape)

    def read(self, key: str, bounds) -> np.ndarray:
        arr = self._cache.get(key)
        if arr is None:
            arr = self._cache[key] = self._data[key]
        shape = tuple(hi - lo for lo, hi in bounds)
        self.reads[key] = max(self.reads.get(key, 0),
                              int(np.prod(shape)) if shape else 1)
        return arr[tuple(slice(lo, hi) for lo, hi in bounds)]

    def end_leaf(self) -> None:
        self._cache.clear()

    def rng(self):
        if "__rng__" in self._data.files:
            impl = (str(self._data["__rng_impl__"])
                    if "__rng_impl__" in self._data.files else None)
            return wrap_saved_rng(self._data["__rng__"], impl=impl)
        return None


def _policy_for(key: str, policies: dict) -> dict:
    """Longest-prefix policy entry for one leaf key (prefixes are leaf-
    path prefixes like ``.opt_state``); default is ``global`` — the
    leaf's global content is mesh-invariant and moves by bounds."""
    best, best_len = {"policy": "global"}, -1
    for prefix, entry in policies.items():
        if (key == prefix or key.startswith(prefix + "/")) and \
                len(prefix) > best_len:
            best, best_len = entry, len(prefix)
    return best


def _region_reader(src, key: str, policy: dict, tgt_shape, tgt_dtype):
    """``read_fn(bounds) -> np.ndarray`` for one target leaf under its
    reshard policy (bounds in TARGET global index space):

    - ``global``: source and target global shapes are identical; the
      region is read straight through.
    - ``flat_padded``: a flat 1-D buffer whose logical content is its
      first ``logical`` elements, zero-padded to a mesh-dependent
      length (ZeRO's per-rank segment padding) — reads clip to the
      logical prefix and zero-fill the target's own padding.
    - ``reset``: state that is meaningless across a topology change
      (wire-codec error-feedback residuals): zeros at the target shape.
    - ``worker_consensus``: leading worker/replica axis resized by
      consensus — float leaves get the mean over the saved workers,
      integer leaves (per-worker step counters) the first worker's
      value, broadcast to the new worker count.
    - ``worker_uniform``: fresh uniform share weights ``1/W`` (GoSGD's
      ``alpha``; re-seeding mass uniformly keeps ``sum == 1`` exact).
    """
    kind = policy.get("policy", "global")
    if kind == "reset":
        def read_reset(bounds):
            return np.zeros(tuple(hi - lo for lo, hi in bounds), tgt_dtype)
        return read_reset
    if kind == "worker_uniform":
        w = int(tgt_shape[0]) if tgt_shape else 1

        def read_uniform(bounds):
            return np.full(tuple(hi - lo for lo, hi in bounds),
                           1.0 / w, tgt_dtype)
        return read_uniform
    src_shape = src.shape(key)
    if kind == "worker_consensus" and tuple(src_shape) != tuple(tgt_shape):
        w_src = int(src_shape[0])

        def read_consensus(bounds):
            (w0, w1), rest = bounds[0], tuple(bounds[1:])
            stack = src.read(key, ((0, w_src),) + rest)
            one = (stack[:1] if np.issubdtype(np.dtype(tgt_dtype), np.integer)
                   else stack.mean(axis=0, keepdims=True))
            return np.broadcast_to(
                one.astype(tgt_dtype), (w1 - w0, *one.shape[1:])
            )
        return read_consensus
    if kind == "flat_padded" and tuple(src_shape) != tuple(tgt_shape):
        logical = int(policy["logical"])

        def read_flat(bounds):
            (a, b), = bounds
            out = np.zeros((b - a,), tgt_dtype)
            hi = min(b, logical)
            if a < hi:
                out[: hi - a] = src.read(key, ((a, hi),))
            return out
        return read_flat
    # identical global shape (covers same-shape leaves under any policy)
    if tuple(src_shape) != tuple(tgt_shape):
        raise ValueError(
            f"checkpoint leaf {key!r} has global shape {src_shape}, "
            f"expected {tuple(tgt_shape)} and no shape-adapting elastic "
            "policy covers it — the saving engine must declare one in "
            "its elastic_spec()"
        )

    def read_global(bounds):
        return src.read(key, tuple(bounds))
    return read_global


def load_resharded(
    path: str, state_template: PyTree, target_mesh,
) -> tuple[PyTree, Optional[jax.Array], dict]:
    """Restore a checkpoint onto ``target_mesh``, resharding if the mesh
    it was saved under differs. Returns ``(state, rng, info)``.

    - Saved and target topologies equal (or the checkpoint predates
      topology manifests but loads cleanly): behaves exactly like
      :func:`load_checkpoint` — host arrays the caller places, so a
      same-mesh resume stays bit-identical. ``info['resharded']`` is
      False.
    - Topologies differ: every leaf of ``state_template`` (whose live
      arrays define the TARGET shapes and shardings — build it with the
      engine's ``init_state`` on the target mesh) is rebuilt with
      :func:`~theanompi_tpu.parallel.mesh.put_resharded`: each
      addressable target shard's content is read from the checkpoint by
      GLOBAL bounds under the leaf's elastic policy (see
      ``_region_reader``), so the sharded-set format never assembles a
      full array on one host. Returns device-placed global arrays;
      ``info`` carries from/to world sizes, the leaf count, and the
      per-key max read sizes (``reads``).

    A pre-elastic checkpoint (no ``__topology__`` manifest) that does
    NOT load on the target mesh raises a ValueError naming the missing
    metadata — there is no plan to compute without it.
    """
    manifest = read_topology_manifest(path)
    from theanompi_tpu.parallel.mesh import mesh_topology, put_resharded

    tgt_topo = mesh_topology(target_mesh)
    if manifest is None:
        try:
            state, rng = load_checkpoint(path, state_template)
        except (KeyError, ValueError) as e:
            raise ValueError(
                f"checkpoint {path!r} carries no {_TOPOLOGY_KEY!r} "
                "topology manifest (it was saved before elastic-resume "
                "stamping) and its leaves do not match the current mesh "
                f"{tgt_topo} — a reshard cannot be planned without the "
                "saved mesh/PartitionSpec metadata. Resume on the "
                "original topology, or re-save once with a stamped "
                "save_checkpoint(..., topology=...) first."
            ) from e
        return state, rng, {"resharded": False, "reason": "no-manifest"}
    if manifest.get("mesh") == tgt_topo:
        state, rng = load_checkpoint(path, state_template)
        return state, rng, {"resharded": False, "reason": "same-mesh"}

    from jax.sharding import NamedSharding, PartitionSpec

    src = (_ShardedSource(path)
           if _SHARD_RE.search(os.path.basename(path))
           else _SingleFileSource(path))
    policies = (manifest.get("elastic") or {}).get("policies") or {}
    leaves_with_paths, treedef = \
        jax.tree_util.tree_flatten_with_path(state_template)
    # The stamped per-leaf block describes the SOURCE layout — validate
    # the plan against it before any region read: every target leaf
    # whose policy reads the checkpoint must have been stamped at save
    # time, so an engine/structure mismatch fails as one batched error
    # naming the leaves instead of a KeyError deep in the first read.
    stamped = manifest.get("leaves")
    if stamped is not None:
        _READLESS = ("reset", "worker_uniform")
        missing = sorted(
            k for k in (_path_key(p) for p, _ in leaves_with_paths)
            if k not in stamped
            and _policy_for(k, policies).get("policy", "global")
            not in _READLESS
        )
        if missing:
            raise ValueError(
                f"cannot plan a reshard of {path!r}: the target state "
                f"template has leaves the checkpoint's {_TOPOLOGY_KEY!r} "
                f"manifest never stamped: {missing} — the saving and "
                "resuming engines disagree on the state structure "
                "(same rule/model/wire-codec on both sides?)"
            )
    new_leaves = []
    for p, leaf in leaves_with_paths:
        key = _path_key(p)
        policy = _policy_for(key, policies)
        tgt_shape = tuple(getattr(leaf, "shape", np.shape(leaf)))
        tgt_dtype = getattr(leaf, "dtype", None) or np.result_type(leaf)
        read_fn = _region_reader(src, key, policy, tgt_shape, tgt_dtype)
        if not isinstance(leaf, jax.Array):
            new_leaves.append(
                read_fn(tuple((0, d) for d in tgt_shape)).astype(tgt_dtype)
            )
            src.end_leaf()
            continue
        sharding = getattr(leaf, "sharding", None)
        spec = (sharding.spec if isinstance(sharding, NamedSharding)
                else PartitionSpec())
        new_leaves.append(
            put_resharded(target_mesh, spec, tgt_shape, tgt_dtype, read_fn)
        )
        src.end_leaf()
    state = jax.tree_util.tree_unflatten(treedef, new_leaves)
    saved_shape = (manifest.get("mesh") or {}).get("shape") or [0]
    info = {
        "resharded": True,
        "from_world": int(np.prod(saved_shape)),
        "to_world": int(target_mesh.devices.size),
        "from_mesh": manifest.get("mesh"),
        "leaves": len(new_leaves),
        "reads": dict(src.reads),
    }
    return state, src.rng(), info


class AsyncCheckpointer:
    """Checkpoint writes overlapped with training (beyond-parity: the
    reference saved synchronously from rank 0 each epoch, stalling the
    workers for the full serialize+write — SURVEY.md §5.4 "no async
    checkpointing").

    ``save()`` first takes a DEVICE-SIDE snapshot (an HBM->HBM copy of
    every ``jax.Array`` leaf, ~ms) and hands that to a single background
    thread for the host pull + write. The copy is what makes overlap
    sound under buffer DONATION: every multi-device engine jits its step
    with ``donate_argnums=(0,)``, so the next dispatched step marks the
    live state's buffers deleted — a background ``device_get`` on the
    originals would race it and crash ("Array has been deleted"); the
    snapshot buffers are referenced only by the writer. Costs one
    transient extra TrainState in HBM until the pull completes.
    Semantics match :func:`save_checkpoint` (atomic tmp+rename, rank-0
    writes, prune-to-keep), with orbax-style discipline:

    - ONE save in flight: a new ``save()`` first waits for the previous
      one, so checkpoints land in step order.
    - worker errors don't vanish: they re-raise at the next ``save()`` /
      ``wait()`` / ``close()`` — EXCEPT *transient* storage-exhaustion
      errors (ENOSPC, EDQUOT, EIO, ESTALE: a full disk, a flaky NFS
      mount), which fail the ATTEMPT without failing the run: the torn
      tmp was already cleaned (``os.replace`` never ran, the keep-chain
      is untouched), so the failure is logged, counted in
      ``storage_failures`` (newest exception in ``last_storage_error``),
      and training continues to the next boundary save — a full disk
      must degrade checkpoint cadence, not kill a healthy training run
      whose older checkpoints remain valid. Configuration errors
      (ENOTDIR, EACCES, EEXIST...) are NOT transient: they still
      re-raise, because every future attempt would fail identically
      and an epoch whose checkpoint silently never lands must not
      return a success summary.
    - ``close()`` drains the queue — call before reading "the latest
      checkpoint" or letting the process exit.

    Multi-host: leaves that are NOT fully addressable need cross-host
    collectives to gather; those must stay on the thread that issues the
    training step's collectives (two threads interleaving collectives
    deadlock). Such saves transparently run synchronously instead.
    """

    def __init__(self, sharded: bool = False):
        from concurrent.futures import ThreadPoolExecutor

        self._pool = ThreadPoolExecutor(1, thread_name_prefix="tmpi-ckpt")
        self._pending = None  # (future, step) of the in-flight save
        self.storage_failures = 0
        self.last_storage_error: Optional[OSError] = None
        # per-host sharded writes touch only ADDRESSABLE shards, so they
        # are collective-free and async-safe even in multi-host runs —
        # the gather-to-rank-0 sync fallback below applies to the
        # single-file format only
        self._sharded = bool(sharded)

    def save(
        self,
        directory: str,
        state: PyTree,
        step: int,
        rng: Optional[jax.Array] = None,
        keep: int = 3,
        extra_meta: Optional[dict] = None,
        topology: Optional[dict] = None,
    ) -> None:
        self.wait()
        save_fn = save_checkpoint_sharded if self._sharded else save_checkpoint
        if not self._sharded:
            leaves = jax.tree_util.tree_leaves(state)
            if any(
                isinstance(l, jax.Array) and not l.is_fully_addressable
                for l in leaves
            ):
                # cross-host gather required -> synchronous, on this thread
                save_checkpoint(directory, state, step, rng=rng, keep=keep,
                                extra_meta=extra_meta, topology=topology)
                return

        def snap(leaf):
            # new device buffer: immune to donation of the original
            # (jnp.copy preserves the sharding, so the topology
            # manifest's per-leaf specs read identically off the copy)
            return jnp.copy(leaf) if isinstance(leaf, jax.Array) else leaf

        state = jax.tree_util.tree_map(snap, state)
        if rng is not None:
            rng = snap(rng)
        self._pending = (self._pool.submit(
            save_fn, directory, state, step, rng, keep, extra_meta, topology
        ), int(step))

    # errnos that mean "storage is full/flaky RIGHT NOW", not "this
    # path will never work" — the only failures an attempt may absorb
    _TRANSIENT_ERRNOS = frozenset(
        e for e in (errno.ENOSPC, getattr(errno, "EDQUOT", None),
                    errno.EIO, getattr(errno, "ESTALE", None))
        if e is not None
    )

    def wait(self) -> None:
        """Block until the in-flight save (if any) is durable; re-raises
        its error here if it failed — except transient storage-
        exhaustion errors (class docstring), which fail only the
        attempt: logged, counted, swallowed, keep-chain intact."""
        if self._pending is None:
            return
        (pending, step), self._pending = self._pending, None
        try:
            pending.result()
        except OSError as e:
            if e.errno not in self._TRANSIENT_ERRNOS:
                raise
            self.storage_failures += 1
            self.last_storage_error = e
            print(
                f"[checkpoint] async save at step {step} failed on a "
                f"storage error ({e!r}); the torn attempt left the "
                "keep-chain intact — training continues, next boundary "
                "save retries",
                flush=True,
            )

    def close(self) -> None:
        try:
            self.wait()
        finally:
            self._pool.shutdown(wait=True)


# --------------------------------------------------------------------------
# checkpoint scrubber (chaos PR): at-rest bit-rot is silent until the
# moment of resume — and a corrupt member sitting in the keep-chain
# makes EVERY verify=True discovery re-pay a decompress+CRC walk past
# it. The scrubber re-verifies the chain in the background and moves
# corrupt members into <ckpt_dir>/quarantine/ (moved, not deleted: the
# bytes stay available for forensics), so the next latest_checkpoint
# walk-back is O(1) and a flipped-bit newest file can never shadow the
# last good checkpoint. The supervisor also runs one synchronous pass
# before each retry's resume discovery (launch/supervisor.py).
# --------------------------------------------------------------------------

QUARANTINE_DIR = "quarantine"


def scrub_checkpoint_dir(directory: str,
                         quarantine: str = QUARANTINE_DIR,
                         memo: Optional[dict] = None) -> dict:
    """One scrub pass over ``directory``'s keep-chain: every
    checkpoint-looking file (single-file saves AND individual sharded
    members — a set with one bad member is poisoned whole, but only the
    bad member is quarantined) is re-verified (:func:`_verify_npz`) and
    corrupt members are MOVED into ``<directory>/<quarantine>/``.
    Files pruned underneath the pass are skipped silently. Returns
    ``{"checked", "corrupt", "quarantined": [names], "seconds"}``.

    ``memo`` (a dict the caller owns across passes): members already
    verified at an unchanged ``(size, mtime_ns)`` are skipped — a
    steady-state pass over multi-GB checkpoints then costs stats, not
    a full decompress+CRC of every byte. The memo deliberately canNOT
    see disk-level rot that leaves metadata untouched, so a periodic
    memo-free full pass is still required (the background scrubber
    does one every :data:`CheckpointScrubber.FULL_EVERY` passes; the
    supervisor's retry-time call is always memo-free).

    Safe against a concurrent writer: visible final-name files are
    complete (tmp+rename atomicity), ``.tmp`` spill files never match
    the checkpoint patterns, and a valid file can never fail verify.
    Quarantined names keep their filename (suffixed ``.N`` on
    collision), so a quarantined member is inert: nothing under
    ``quarantine/`` matches the keep-chain walk."""
    t0 = time.perf_counter()
    out = {"checked": 0, "corrupt": 0, "quarantined": [], "seconds": 0.0}
    if not os.path.isdir(directory):
        return out
    names = [f for f in sorted(os.listdir(directory))
             if _CKPT_RE.search(f) or _SHARD_RE.search(f)]
    for f in names:
        p = os.path.join(directory, f)
        try:
            st = os.stat(p)
        except OSError:
            continue  # pruned underneath the listing
        out["checked"] += 1
        sig = (st.st_size, st.st_mtime_ns)
        if memo is not None and memo.get(f) == sig:
            continue  # verified before at this exact size+mtime
        if _verify_npz(p):
            if memo is not None:
                memo[f] = sig
            continue
        if not os.path.exists(p):
            continue  # pruned mid-verify: absence is not corruption
        qdir = os.path.join(directory, quarantine)
        os.makedirs(qdir, exist_ok=True)
        dst = os.path.join(qdir, f)
        n = 1
        while os.path.exists(dst):
            dst = os.path.join(qdir, f"{f}.{n}")
            n += 1
        try:
            os.replace(p, dst)
        except OSError:
            continue  # raced a prune; the member is gone either way
        out["quarantined"].append(f)
        print(f"[scrub] quarantined corrupt checkpoint member {f!r} "
              f"-> {dst!r}", flush=True)
    out["corrupt"] = len(out["quarantined"])
    out["seconds"] = time.perf_counter() - t0
    return out


class CheckpointScrubber:
    """Background keep-chain scrubber: run
    :func:`scrub_checkpoint_dir` every ``interval`` seconds until
    :meth:`stop`. ``on_result`` (e.g. ``Observability.note_scrub``)
    receives each pass's result dict — ``kind=scrub`` records and the
    ``tmpi_scrub_*`` gauges ride it; a callback failure is suppressed
    (telemetry must never take down the scrubber, and the scrubber
    must never take down training). ``scrub_once()`` is the
    deterministic unit tests drive directly.

    Passes are memoized on ``(size, mtime_ns)`` so steady-state scrubs
    of multi-GB checkpoints cost stats, not bytes — with a memo-FREE
    full pass every :data:`FULL_EVERY` passes (and on the first), since
    disk-level rot can flip bits without touching file metadata."""

    FULL_EVERY = 10

    def __init__(self, ckpt_dir: str, *, interval: float = 60.0,
                 on_result=None):
        self.ckpt_dir = ckpt_dir
        self.interval = float(interval)
        self.on_result = on_result
        self.runs = 0
        self.quarantined_total = 0
        self._memo: dict = {}
        # serializes passes: scrub_once is both the background loop's
        # body AND a public entry (the supervisor's retry-time pass,
        # unit tests) — two concurrent passes would race on the memo
        # dict and the counters
        self._pass_lock = threading.Lock()
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def scrub_once(self) -> dict:
        with self._pass_lock:
            if self.runs % self.FULL_EVERY == 0:
                self._memo.clear()  # periodic full re-verify (docstring)
            res = scrub_checkpoint_dir(self.ckpt_dir, memo=self._memo)
            self.runs += 1
            self.quarantined_total += res["corrupt"]
        if self.on_result is not None:
            try:
                self.on_result(res)
            except Exception as e:  # noqa: BLE001
                print(f"[scrub] result callback failed (suppressed): "
                      f"{e!r}", flush=True)
        return res

    def start(self) -> None:
        if self._thread is not None:
            raise RuntimeError("scrubber already started")
        self._thread = threading.Thread(
            target=self._loop, name="tmpi-ckpt-scrub", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._stop.wait(self.interval):
            try:
                self.scrub_once()
            except Exception as e:  # noqa: BLE001
                print(f"[scrub] pass failed ({e!r}); retrying next "
                      "interval", flush=True)

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10.0)
            self._thread = None


# --------------------------------------------------------------------------
# resumable-run marker (fault-tolerance PR): the SIGTERM grace path
# (launch/worker.py) checkpoints and drops this marker; the supervisor
# (launch/supervisor.py) reads it to auto-resume the next invocation.
# --------------------------------------------------------------------------

_RESUMABLE_MARKER = "resumable.json"


def write_resumable_marker(ckpt_dir: str, step: int, reason: str) -> str:
    """Atomically mark the run in ``ckpt_dir`` as cleanly-interrupted-
    and-resumable (rank 0 only, like the checkpoint writes)."""
    import json as _json

    os.makedirs(ckpt_dir, exist_ok=True)
    path = os.path.join(ckpt_dir, _RESUMABLE_MARKER)
    fd, tmp = tempfile.mkstemp(dir=ckpt_dir, suffix=".tmp")
    try:
        with os.fdopen(fd, "w") as f:
            _json.dump({"step": int(step), "reason": str(reason),
                        "t": time.time()}, f)
        os.replace(tmp, path)
    except BaseException:
        if os.path.exists(tmp):
            os.unlink(tmp)
        raise
    return path


def read_resumable_marker(ckpt_dir: str) -> Optional[dict]:
    """The marker dict, or None when absent/unreadable (an unreadable
    marker is treated as absent — it only gates an auto-resume hint)."""
    import json as _json

    try:
        with open(os.path.join(ckpt_dir, _RESUMABLE_MARKER)) as f:
            return _json.load(f)
    except (OSError, ValueError):
        return None


def clear_resumable_marker(ckpt_dir: str) -> None:
    try:
        os.unlink(os.path.join(ckpt_dir, _RESUMABLE_MARKER))
    except OSError:
        pass


def wrap_saved_rng(raw: np.ndarray, impl: Optional[str] = None) -> jax.Array:
    """Turn a checkpoint's raw ``__rng__`` uint32 data back into a usable
    PRNG key, honoring the impl that WROTE it rather than the process
    default — a checkpoint saved under threefry (width-2 key data) must
    resume correctly in a process whose default impl is rbg (width 4) and
    vice versa. ``impl`` comes from the checkpoint's ``__rng_impl__``
    entry; pre-impl-tracking checkpoints fall back to width inference.
    Returns a typed key; all jax.random consumers accept it."""
    arr = jnp.asarray(raw)
    impl = impl or _KEY_IMPL_BY_WIDTH.get(arr.shape[-1] if arr.ndim else None)
    if impl is None:
        raise ValueError(
            f"checkpoint rng has unrecognized key-data shape {np.shape(raw)}"
        )
    return jax.random.wrap_key_data(arr, impl=impl)
