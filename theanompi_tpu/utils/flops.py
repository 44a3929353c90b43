"""FLOP + HBM cost accounting and MFU (model FLOPs utilization) — the
single per-compiled-executable cost authority.

The reference had no FLOPs accounting at all — its recorder reported
images/sec only (reference: ``lib/recorder.py``, SURVEY.md §5.1). On TPU
the honest scaling story needs achieved TFLOP/s vs the chip's peak, so
the recorder reports MFU alongside img/s (BASELINE metric
"scaling eff" is defined in those terms).

FLOPs and HBM bytes come from XLA's own cost model on the COMPILED
program (``Compiled.cost_analysis()``: ``flops`` + ``bytes accessed``) —
the same HLO the chip executes, so fusion/rematerialization are
accounted for. Peak numbers are small device-kind tables (public
spec-sheet bf16 FLOP/s and HBM GB/s); unknown devices (CPU test meshes)
report ``mfu=None`` rather than a made-up number.

Every consumer shares this module (attribution-profiler PR):
the ``tmpi profile`` subcommand (tools/profile.py), the
live ``tmpi_mfu``/``tmpi_hbm_gbps`` gauges (obs/attribution.py via each
engine's ``cost_model()`` hook), and the run summary's ``mfu`` field —
one :class:`CostModel` per compiled step, no hand-rolled duplicates.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

# public spec-sheet dense bf16 peak FLOP/s per chip; substring-matched
# against jax.Device.device_kind (ORDER MATTERS: first match wins)
_PEAK_BF16 = (
    ("v5 lite", 197e12),  # v5e ("TPU v5 lite")
    ("v5litepod", 197e12),
    ("v5e", 197e12),
    ("v6 lite", 918e12),  # v6e / Trillium
    ("v6e", 918e12),
    ("v5p", 459e12),
    ("v5", 459e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

# public spec-sheet HBM bandwidth (bytes/s) per chip — the roofline's
# other ceiling; same substring-match convention as _PEAK_BF16
_PEAK_HBM = (
    ("v5 lite", 819e9),  # v5e: 819 GB/s
    ("v5litepod", 819e9),
    ("v5e", 819e9),
    ("v6 lite", 1640e9),  # v6e / Trillium: 1640 GB/s
    ("v6e", 1640e9),
    ("v5p", 2765e9),
    ("v5", 2765e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
)

# public spec-sheet HBM CAPACITY (bytes) per chip — the memory
# pre-flight's budget ceiling (tools/analyze/memory.py, `tmpi
# preflight`); same substring-match convention as the peak tables.
# Unknown devices (CPU test meshes) report None — the pre-flight then
# needs an explicit ``--budget-gb``.
_HBM_CAPACITY = (
    ("v5 lite", 16e9),  # v5e: 16 GB
    ("v5litepod", 16e9),
    ("v5e", 16e9),
    ("v6 lite", 32e9),  # v6e / Trillium: 32 GB
    ("v6e", 32e9),
    ("v5p", 95e9),
    ("v5", 95e9),
    ("v4", 32e9),
    ("v3", 32e9),
    ("v2", 16e9),
)


def match_device_table(table, device=None) -> Optional[float]:
    """First entry of a ``(kind_substring, value)`` table matching
    ``device.device_kind`` (default: first visible device). None for a
    device that is no TPU (CPU test meshes: consumers then calibrate or
    skip the ratio); a TPU the table does not know RAISES — on the chip
    a missing peak must not quietly turn MFU and the drift watchdog
    into self-calibrated numbers."""
    import jax

    if device is None:
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "")
    for key, value in table:
        if key in kind.lower():
            return value
    if getattr(device, "platform", "") == "tpu":
        raise ValueError(
            f"TPU device_kind {kind!r} matches no entry of the spec table "
            f"{[k for k, _ in table]} (theanompi_tpu/utils/flops.py, "
            "obs/attribution.py): add its published peak"
        )
    return None


def peak_flops(device=None) -> Optional[float]:
    """Per-chip peak bf16 FLOP/s for ``device`` (default: first visible
    device); None when unknown (e.g. CPU)."""
    return match_device_table(_PEAK_BF16, device)


def peak_hbm_bytes_per_sec(device=None) -> Optional[float]:
    """Per-chip peak HBM bytes/s (spec sheet); None when unknown."""
    return match_device_table(_PEAK_HBM, device)


def hbm_capacity_bytes(device=None) -> Optional[float]:
    """Per-chip HBM capacity in bytes (spec sheet); None when unknown
    (e.g. CPU test meshes — the memory pre-flight then requires an
    explicit ``--budget-gb``)."""
    return match_device_table(_HBM_CAPACITY, device)


@dataclass
class CostModel:
    """XLA's cost analysis of ONE compiled executable invocation (one
    training step, usually), paired with the device's spec-sheet peaks.

    ``flops``/``hbm_bytes`` are per-invocation totals from the compiled
    HLO (``cost_analysis()``: ``flops`` + ``bytes accessed``). Peaks are
    None on devices without a spec entry (CPU test meshes) — consumers
    must then either skip utilization ratios (:meth:`mfu` returns None)
    or calibrate against measured time (obs/attribution.py documents
    that convention)."""

    flops: float
    hbm_bytes: float
    device_kind: str = ""
    peak_flops_per_sec: Optional[float] = None
    peak_hbm_bytes_per_sec: Optional[float] = None

    def mfu(self, step_seconds: Optional[float]) -> Optional[float]:
        """Achieved / peak FLOP/s for a measured per-step time; None
        when the peak is unknown or the time unmeasurable."""
        if not step_seconds or step_seconds <= 0 or not self.peak_flops_per_sec:
            return None
        return mfu(self.flops / step_seconds,
                   peak=self.peak_flops_per_sec)

    def hbm_gbps(self, step_seconds: Optional[float]) -> Optional[float]:
        """Achieved HBM GB/s implied by a measured per-step time (bytes
        accessed / time) — computable on every backend."""
        if not step_seconds or step_seconds <= 0:
            return None
        return self.hbm_bytes / step_seconds / 1e9

    def compute_seconds(self) -> Optional[float]:
        """Roofline lower bound on the step's device time: the larger of
        the FLOP time at peak compute and the HBM time at peak
        bandwidth. None when the peaks are unknown."""
        if not self.peak_flops_per_sec or not self.peak_hbm_bytes_per_sec:
            return None
        return max(self.flops / self.peak_flops_per_sec,
                   self.hbm_bytes / self.peak_hbm_bytes_per_sec)

    def hbm_bound(self) -> Optional[bool]:
        """True when the roofline's binding ceiling is HBM bandwidth,
        False when compute; None when the peaks are unknown."""
        if not self.peak_flops_per_sec or not self.peak_hbm_bytes_per_sec:
            return None
        return (self.hbm_bytes / self.peak_hbm_bytes_per_sec
                > self.flops / self.peak_flops_per_sec)

    def as_metrics(self) -> dict:
        """Numeric gauge map (obs facade prefixes ``tmpi_``)."""
        out = {
            "cost_flops_per_step": self.flops,
            "cost_hbm_bytes_per_step": self.hbm_bytes,
        }
        if self.peak_flops_per_sec:
            out["cost_peak_tflops"] = self.peak_flops_per_sec / 1e12
        if self.peak_hbm_bytes_per_sec:
            out["cost_peak_hbm_gbps"] = self.peak_hbm_bytes_per_sec / 1e9
        return out


def compiled_cost(jitted, *args, device=None, **kwargs) -> Optional[CostModel]:
    """:class:`CostModel` of one invocation of an already-jitted
    function, from XLA's cost analysis of the lowered+compiled program
    (abstract ``ShapeDtypeStruct`` args work — nothing executes). None
    when the backend provides no cost model, or off the TPU when the
    lowering fails; on a TPU a failed lowering raises."""
    import jax

    if device is None:
        device = jax.devices()[0]
    try:
        compiled = jitted.lower(*args, **kwargs).compile()
        ca = compiled.cost_analysis()
    except Exception:
        if device.platform == "tpu":
            raise  # on the chip a step that does not lower is a fault
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    flops = float(ca.get("flops", 0.0))
    if flops <= 0:
        return None
    return CostModel(
        flops=flops,
        hbm_bytes=float(ca.get("bytes accessed", 0.0)),
        device_kind=getattr(device, "device_kind", ""),
        peak_flops_per_sec=peak_flops(device),
        peak_hbm_bytes_per_sec=peak_hbm_bytes_per_sec(device),
    )


def compiled_flops(jitted, *args, **kwargs) -> Optional[float]:
    """Total FLOPs of one invocation of an already-jitted function
    (thin view over :func:`compiled_cost`). None when the backend
    provides no cost model."""
    cost = compiled_cost(jitted, *args, **kwargs)
    return cost.flops if cost is not None else None


def abstract_batch(model, global_batch: int):
    """``(x, y)`` ShapeDtypeStructs for one global training batch of
    ``model`` — the abstract operands every engine's ``cost_model()``
    lowers its compiled step over (LM models: x IS the label stream)."""
    import jax
    import jax.numpy as jnp

    sds = jax.ShapeDtypeStruct
    ishape = tuple(model.recipe.input_shape)
    if getattr(model, "is_lm", False):
        x = sds((global_batch, *ishape), jnp.int32)
        return x, x
    return (sds((global_batch, *ishape), jnp.float32),
            sds((global_batch,), jnp.int32))


def mfu(flops_per_sec: Optional[float], device=None,
        peak: Optional[float] = None) -> Optional[float]:
    """Achieved / peak FLOP/s. ``peak`` overrides the device-table
    lookup (CostModel carries its own)."""
    if peak is None:
        peak = peak_flops(device)
    if not peak or not flops_per_sec:
        return None
    return flops_per_sec / peak


# --------------------------------------------------------------------------
# per-leaf state HBM residency — the `memory_model()` engine hook
# (mirrors obs/comm.py's `traffic_model()`: an ANALYTIC declaration the
# static analyzer cross-checks against the lowered program;
# tools/analyze/memory.py, `tmpi preflight`)
# --------------------------------------------------------------------------


@dataclass(frozen=True)
class MemoryLeaf:
    """One engine-state leaf's HBM residency: the global logical array
    and the slice of it each device actually holds (``global_bytes /
    shard_factor``, the mesh extent over the leaf's sharded axes)."""

    path: str  # jax.tree_util.keystr of the leaf (".params['h']['w']")
    dtype: str
    shape: tuple  # global logical shape
    global_bytes: int
    shard_factor: int  # mesh extent the leaf is divided over (>= 1)
    # serialized PartitionSpec (parallel/mesh.spec_to_json) the factor
    # derives from — the engine's ShardingRecipe declaration, so the
    # preflight byte table and the sharding analyzer read ONE source
    # (None on legacy callers that still pass bare factors)
    spec: Optional[list] = None

    @property
    def per_device_bytes(self) -> int:
        return -(-self.global_bytes // max(1, self.shard_factor))

    @property
    def category(self) -> str:
        """Top-level state field the leaf lives under (params,
        opt_state, workers, ef, ...)."""
        return self.path.lstrip(".").split("[")[0].split(".")[0]

    def as_json(self) -> dict:
        return {"path": self.path, "dtype": self.dtype,
                "shape": list(self.shape),
                "global_bytes": int(self.global_bytes),
                "per_device_bytes": int(self.per_device_bytes),
                "shard_factor": int(self.shard_factor),
                "spec": self.spec}


@dataclass
class MemoryModel:
    """An engine's declared per-leaf state residency on ONE device —
    what the persistent training state costs in HBM before any
    activations/temps (XLA's `memory_analysis()` adds those;
    tools/analyze/memory.py reconciles the two)."""

    rule: str
    n_devices: int
    leaves: list  # list[MemoryLeaf]
    detail: dict = None  # type: ignore[assignment]

    def __post_init__(self):
        if self.detail is None:
            self.detail = {}

    @property
    def state_bytes_per_device(self) -> int:
        return sum(l.per_device_bytes for l in self.leaves)

    @property
    def state_bytes_global(self) -> int:
        return sum(l.global_bytes for l in self.leaves)

    def category_bytes_per_device(self) -> dict:
        out: dict = {}
        for l in self.leaves:
            out[l.category] = out.get(l.category, 0) + l.per_device_bytes
        return out

    def params_bytes_per_device(self) -> int:
        """Bytes of the parameter leaves proper on one device (the
        MEM003 rematerialization-smell denominator). Worker-stacked
        engines keep their replicas under ``.workers`` — those count
        too (each device's slice of the stack IS its params)."""
        total = 0
        for l in self.leaves:
            if l.category in ("params", "workers", "center_params"):
                total += l.per_device_bytes
        return total

    def top_leaves(self, k: int = 10) -> list:
        return sorted(self.leaves, key=lambda l: -l.per_device_bytes)[:k]

    def as_json(self) -> dict:
        return {"rule": self.rule, "n_devices": int(self.n_devices),
                "state_bytes_per_device": int(self.state_bytes_per_device),
                "leaves": [l.as_json() for l in self.leaves],
                "detail": dict(self.detail)}


def state_memory_model(state, rule: str, n_devices: int, shard_factor,
                       detail: Optional[dict] = None,
                       specs: Optional[dict] = None) -> MemoryModel:
    """Build a :class:`MemoryModel` from a (possibly abstract) engine
    state pytree. ``shard_factor(path_str, leaf) -> int`` is the
    engine's own per-leaf sharding knowledge — the mesh extent the
    leaf's global shape is divided over (1 = replicated). ``specs``
    optionally maps each leaf path to the declared PartitionSpec the
    factor derives from (the engine's ShardingRecipe table — see
    parallel/recipe.py ``leaf_factors``); it rides every leaf into the
    preflight byte table and the residency goldens. Works on
    ``jax.eval_shape`` structs: only ``.shape``/``.dtype`` are read."""
    import jax

    from theanompi_tpu.parallel.mesh import spec_to_json

    leaves = []
    for path, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", None)
        if dtype is None:
            continue
        import numpy as _np

        n_elems = 1
        for d in shape:
            n_elems *= int(d)
        nbytes = int(n_elems * _np.dtype(dtype).itemsize)
        pstr = jax.tree_util.keystr(path)
        spec = (specs or {}).get(pstr)
        leaves.append(MemoryLeaf(
            path=pstr, dtype=str(dtype), shape=shape,
            global_bytes=nbytes,
            shard_factor=max(1, int(shard_factor(pstr, leaf))),
            spec=spec_to_json(spec) if spec is not None else None,
        ))
    return MemoryModel(rule=rule, n_devices=int(n_devices), leaves=leaves,
                       detail=dict(detail or {}))
