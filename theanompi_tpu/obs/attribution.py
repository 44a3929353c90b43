"""Step-time attribution: where does the training step go?

The evidence for where a step's time goes was scattered across
pieces that did not compose: XLA cost analysis
(``utils/flops.compiled_cost``), ``tools/op_profile.py`` needed a
manually captured trace, spans measure
host wall only, and ``traffic_model()`` comm bytes were never
reconciled against measured step time. This module is the one place
the pieces meet (GC3, PAPERS.md arXiv:2201.11840: you can't schedule
what you can't measure):

- :func:`attribute_step` reconciles a MEASURED per-step wall time
  against the analytic models — compute (XLA cost-analysis FLOPs + HBM
  bytes vs the chip's roofline, :class:`~theanompi_tpu.utils.flops.
  CostModel`), collective (``traffic_model()`` effective bytes over the
  chip's ICI link bandwidth, per engine and codec), host-blocked (the
  dispatcher's measured drain tax) — and books what none of them
  explain as the ``residual`` fraction. Fractions sum to 1.0 by
  construction (residual may go negative when a model over-explains the
  step — that is itself a finding, flagged in ``detail``).
- :class:`Attribution` carries the fractions, the roofline
  classification (compute-bound / hbm-bound / comm-bound / host-bound),
  and the ``kind=profile`` JSONL record / ``tmpi_*`` gauge views the
  obs facade emits at snapshot time (obs/__init__.py).
- :func:`join_op_table` joins a ``tools/op_profile.py`` per-op table
  against the analytic model, naming the top ops the model does NOT
  explain — the exact input ROADMAP item 2's fusion work needs.
- :func:`traced_wire_bytes` re-prices the engine's traced jaxpr with
  the SPMD analyzer's collective accounting so ``tmpi profile`` can
  cross-check the declared ``traffic_model()`` at runtime (same
  tolerance as lint rule SPMD101).

**Calibrated fallback (CPU test meshes):** devices without spec-sheet
peaks cannot split device time into compute-vs-HBM, so the non-host,
non-comm remainder of the measured step is attributed to compute
(``peak_source="calibrated"``, residual 0 by construction) — honest
about what it is, and it keeps the fraction-sum invariant checkable on
every backend. Spec-peak devices get the real roofline split and a real
residual.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field
from typing import Optional

# Approximate public per-chip aggregate ICI bandwidth (bytes/s, one
# direction) — the collective-time ceiling traffic bytes divide by.
# Same substring-match convention as utils/flops._PEAK_BF16. DCN-
# attached axes are far slower; the ND engine's figure is dp-only
# (obs/comm.py) so this stays a per-chip ICI number.
_LINK_BYTES_PER_SEC = (
    ("v5 lite", 200e9),  # v5e: 1600 Gbps ICI
    ("v5litepod", 200e9),
    ("v5e", 200e9),
    ("v6 lite", 448e9),  # v6e / Trillium: 3584 Gbps
    ("v6e", 448e9),
    ("v5p", 600e9),
    ("v5", 600e9),
    ("v4", 300e9),
    ("v3", 140e9),
    ("v2", 62.5e9),
)

# roofline classification thresholds (README "Profiling & attribution"):
# host-bound needs a material host share even when nothing else
# dominates; comm/host win ties only when they actually dominate
HOST_BOUND_MIN = 0.4

PROFILE_GAUGE_PREFIX = "tmpi_step_"  # + {compute,comm,host,residual}_frac
# the live gauge family the MetricsDispatcher drain path feeds
# (obs/__init__.py note_step_seconds): tmpi_mfu, tmpi_mfu_calibrated,
# tmpi_hbm_gbps, tmpi_step_*_frac — plus the static tmpi_cost_* family
# from CostModel.as_metrics()


# Approximate per-chip DCN share (bytes/s, one direction) for
# cross-slice hops: a multislice pod's data-center network is shared by
# the whole slice, so the per-chip figure is the slice NIC bandwidth
# divided across its chips — public multislice material puts the
# usable per-chip share near 25 GB/s, an order of magnitude under any
# ICI tier above. This single number is deliberately device-agnostic
# (DCN is the facility fabric, not the chip); override per-run with
# ``attribute_step(dcn_bps=...)`` when the deployment's share is known.
_DCN_BYTES_PER_SEC_DEFAULT = 25e9


def link_bytes_per_sec(device=None) -> Optional[float]:
    """Per-chip ICI bytes/s for ``device`` (default: first visible);
    None off the TPU (CPU test meshes), an unknown TPU raises."""
    from theanompi_tpu.utils.flops import match_device_table

    return match_device_table(_LINK_BYTES_PER_SEC, device)


def dcn_bytes_per_sec() -> float:
    """Per-chip cross-slice (DCN) bytes/s — the flat approximate share
    documented on ``_DCN_BYTES_PER_SEC_DEFAULT``."""
    return _DCN_BYTES_PER_SEC_DEFAULT


@dataclass
class Attribution:
    """One reconciled step-time decomposition (see module docstring).

    ``fractions`` always carries the four keys and sums to 1.0 exactly
    (residual is the booked remainder; negative residual = the models
    over-explain the measured step, named in ``detail``)."""

    step_seconds: float
    fractions: dict  # {compute, comm, host, residual}
    seconds: dict  # same keys, absolute model/measured seconds
    classification: str  # compute-bound|hbm-bound|comm-bound|host-bound
    mfu: Optional[float] = None  # vs spec peak (None on unknown devices)
    mfu_calibrated: Optional[float] = None  # vs calibrated peak (= the
    # compute fraction; the CPU-runnable stand-in the perf gate diffs)
    hbm_gbps: Optional[float] = None  # achieved HBM GB/s (any backend)
    peak_source: str = "spec"  # spec | calibrated
    detail: dict = field(default_factory=dict)

    @property
    def fractions_sum(self) -> float:
        return float(sum(self.fractions.values()))

    def as_metrics(self) -> dict:
        """Live gauge map (obs facade prefixes ``tmpi_``): the MFU /
        HBM / step-fraction family the ISSUE's drain-path gauges carry."""
        out = {f"step_{k}_frac": float(v) for k, v in self.fractions.items()}
        if self.mfu is not None:
            out["mfu"] = float(self.mfu)
        if self.mfu_calibrated is not None:
            out["mfu_calibrated"] = float(self.mfu_calibrated)
        if self.hbm_gbps is not None:
            out["hbm_gbps"] = float(self.hbm_gbps)
        return out

    def as_record(self, step: int, rank: int = 0,
                  rule: Optional[str] = None) -> dict:
        """The ``kind=profile`` JSONL record body (schema:
        tools/check_obs_schema.py) — one per metrics snapshot, written
        by ``Observability.snapshot`` next to the kind=metrics line."""
        import time as _time

        rec = {
            "kind": "profile", "rank": int(rank), "t": _time.time(),
            "step": int(step),
            "step_seconds": float(self.step_seconds),
            "fractions": {k: float(v) for k, v in self.fractions.items()},
            "classification": self.classification,
            "peak_source": self.peak_source,
        }
        if rule:
            rec["rule"] = rule
        if self.mfu is not None:
            rec["mfu"] = float(self.mfu)
        if self.mfu_calibrated is not None:
            rec["mfu_calibrated"] = float(self.mfu_calibrated)
        if self.hbm_gbps is not None:
            rec["hbm_gbps"] = float(self.hbm_gbps)
        return rec


def attribute_step(
    step_seconds: float,
    cost=None,  # utils.flops.CostModel (or None)
    traffic=None,  # obs.comm.TrafficModel (or None)
    host_frac: Optional[float] = None,
    link_bps: Optional[float] = None,
    overlap_frac: Optional[float] = None,
    dcn_bps: Optional[float] = None,
) -> Attribution:
    """Reconcile one measured per-step wall time against the analytic
    models (see module docstring for the calibrated-fallback rules).

    ``host_frac``: the measured fraction of the step the host spent
    blocked (dispatcher drain tax) or dispatching. ``link_bps``
    overrides the device-table ICI bandwidth (tests); ``dcn_bps``
    overrides the flat cross-slice share (``dcn_bytes_per_sec``).
    When the traffic model carries a per-link split
    (``dcn_bytes_per_step > 0``), each link class is priced at its own
    bandwidth — the DCN hop is ~10-25x slower per chip than ICI, so a
    byte there books proportionally more comm seconds (this is exactly
    the asymmetry the hierarchical strategy exploits by sending only
    the scattered shard, codec'd, across slices).

    ``overlap_frac``: fraction of the collective that HIDES under
    backward compute (the bucketed allreduce's schedule estimate —
    parallel/strategies.py::bucket_overlap_frac; defaults to the
    traffic model's ``detail["overlap_frac"]``). Before this knob the
    comm model priced the whole exchange as serial post-backward
    traffic, so an overlapped wire double-counted against compute; now
    only the EXPOSED ``(1 - overlap)`` share books as the comm
    fraction, the hidden seconds land in ``detail["comm_hidden_s"]``."""
    if not step_seconds or step_seconds <= 0:
        raise ValueError(f"step_seconds must be > 0, got {step_seconds}")
    detail: dict = {}
    host = min(1.0, max(0.0, float(host_frac or 0.0)))
    if overlap_frac is None and traffic is not None:
        overlap_frac = traffic.detail.get("overlap_frac")
    overlap = min(1.0, max(0.0, float(overlap_frac or 0.0)))

    comm_s = 0.0
    wire = float(traffic.bytes_per_step_amortized) if traffic is not None else 0.0
    dcn_wire = float(traffic.dcn_bytes_per_step) if traffic is not None else 0.0
    if wire > 0:
        if link_bps is None:
            link_bps = link_bytes_per_sec()
        if link_bps:
            if dcn_wire > 0:
                # per-link pricing: in-slice bytes at ICI speed, the
                # cross-slice shard at the (much slower) DCN share
                ici_s = max(0.0, wire - dcn_wire) / link_bps
                dcn_s = dcn_wire / float(dcn_bps or dcn_bytes_per_sec())
                comm_s = ici_s + dcn_s
                detail["comm_ici_s"] = ici_s
                detail["comm_dcn_s"] = dcn_s
            else:
                comm_s = wire / link_bps
            if overlap > 0:
                detail["overlap_frac"] = overlap
                detail["comm_hidden_s"] = comm_s * overlap
                comm_s = comm_s * (1.0 - overlap)
        else:
            detail["comm_note"] = (
                "link bandwidth unknown on this device kind: collective "
                "time folds into compute/residual (bytes still reported)"
            )
    comm = comm_s / step_seconds

    compute_s = cost.compute_seconds() if cost is not None else None
    hbm_gbps = cost.hbm_gbps(step_seconds) if cost is not None else None
    if compute_s is not None:
        # spec roofline: model compute time vs the measured step; the
        # unexplained remainder is the residual the fusion work attacks
        compute = compute_s / step_seconds
        residual = 1.0 - compute - comm - host
        peak_source = "spec"
        mfu_spec = cost.mfu(step_seconds)
        if residual < -0.02:
            detail["model_overrun"] = (
                f"models explain {compute + comm + host:.3f}x the "
                "measured step — check the traffic/cost inputs"
            )
    else:
        # calibrated fallback: no spec peaks (CPU) — attribute the
        # non-host, non-comm remainder to compute, residual 0
        compute = max(0.0, 1.0 - comm - host)
        residual = 1.0 - compute - comm - host  # 0 unless comm+host > 1
        if abs(residual) < 1e-12:
            residual = 0.0  # float noise from the subtraction chain
        peak_source = "calibrated"
        mfu_spec = None
        detail["calibrated_note"] = (
            "no spec-sheet peak for this device kind: compute is the "
            "non-host non-comm remainder of the measured step"
        )

    fractions = {"compute": compute, "comm": comm, "host": host,
                 "residual": residual}
    seconds = {k: v * step_seconds for k, v in fractions.items()}

    # roofline classification: the dominant booked share names the
    # bottleneck; host only wins with a material share (threshold) —
    # when it loses on the threshold, the verdict falls to whichever of
    # compute/comm actually dominates between themselves
    dominant = max(("compute", "comm", "host"), key=lambda k: fractions[k])
    if dominant == "host" and host < HOST_BOUND_MIN:
        dominant = max(("compute", "comm"), key=lambda k: fractions[k])
    if dominant == "host":
        classification = "host-bound"
    elif dominant == "comm":
        classification = "comm-bound"
    else:
        hbm = cost.hbm_bound() if cost is not None else None
        classification = "hbm-bound" if hbm else "compute-bound"

    return Attribution(
        step_seconds=float(step_seconds),
        fractions=fractions,
        seconds=seconds,
        classification=classification,
        mfu=mfu_spec,
        mfu_calibrated=compute if peak_source == "calibrated" else None,
        hbm_gbps=hbm_gbps,
        peak_source=peak_source,
        detail=detail,
    )


# -- op-table join (tools/op_profile.py x the analytic model) ----------------

# XLA op-name patterns that are collective wire time (the analytic comm
# model's measured counterpart); everything else is compute
_COMM_OP = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|psum|ppermute",
    re.IGNORECASE,
)


def join_op_table(rows: list, attribution: Attribution) -> dict:
    """Join a ``tools/op_profile.py`` per-op table against the analytic
    model: classify each op comm/compute by name, compare the measured
    per-class ms against the model's booked seconds, and name the top
    ops in whichever class the model under-explains — the per-op face
    of the ``residual`` fraction.

    ``rows``: ``op_table()`` output (may be empty — CPU captures have
    no device op track; the join then reports only the model side)."""
    measured = {"compute": 0.0, "comm": 0.0}
    tagged = []
    for r in rows:
        cls = "comm" if _COMM_OP.search(r["op"]) else "compute"
        measured[cls] += float(r["ms_per_step"])
        tagged.append({**r, "class": cls})
    model_ms = {
        "compute": attribution.seconds["compute"] * 1e3,
        "comm": attribution.seconds["comm"] * 1e3,
    }
    overshoot = {
        k: max(0.0, measured[k] - model_ms[k]) for k in measured
    }
    # the class the model under-explains the most owns the residual;
    # its biggest ops are the fusion-work candidates
    worst = max(overshoot, key=lambda k: overshoot[k])
    top_unattributed = [
        {"op": r["op"], "ms_per_step": r["ms_per_step"],
         "share": r["share"], "class": r["class"]}
        for r in sorted(tagged, key=lambda r: -r["ms_per_step"])
        if r["class"] == worst
    ][:8] if overshoot[worst] > 0 else []
    return {
        "measured_ms": measured,
        "model_ms": model_ms,
        "unattributed_ms": overshoot,
        "top_unattributed": top_unattributed,
        "rows": tagged,
    }


def format_join(join: dict, top: int = 10) -> str:
    """Text table for the joined op view (``tmpi profile`` stdout)."""
    lines = [
        "measured vs analytic (ms/step): "
        + "  ".join(
            f"{k}: {join['measured_ms'][k]:.3f} measured / "
            f"{join['model_ms'][k]:.3f} model"
            for k in ("compute", "comm")
        )
    ]
    if not join["rows"]:
        lines.append("(no device op track in trace — CPU capture? "
                     "per-op attribution needs a TPU trace)")
        return "\n".join(lines)
    lines.append(f"{'ms/step':>10}  {'share':>6}  {'class':>7}  op")
    for r in sorted(join["rows"], key=lambda r: -r["ms_per_step"])[:top]:
        lines.append(
            f"{r['ms_per_step']:10.3f}  {r['share'] * 100:5.1f}%  "
            f"{r['class']:>7}  {r['op'][:70]}"
        )
    if join["top_unattributed"]:
        names = ", ".join(r["op"] for r in join["top_unattributed"][:5])
        worst = max(join["unattributed_ms"],
                    key=lambda k: join["unattributed_ms"][k])
        lines.append(
            f"top unattributed ({worst}, "
            f"{join['unattributed_ms'][worst]:.3f} ms/step beyond the "
            f"model): {names}"
        )
    return "\n".join(lines)


# -- runtime traffic cross-check (the SPMD101 contract, live) ----------------

def traced_wire_bytes(parts, codec_bytes: Optional[float] = None) -> float:
    """Amortized per-step wire bytes of an engine's traced programs,
    priced with the SPMD analyzer's collective accounting
    (tools/analyze/signature.py) — the measured-side half of the
    ``tmpi profile`` traffic cross-check.

    ``parts``: ``[(fn, args, weight), ...]`` — each traced with
    ``jax.make_jaxpr`` over (abstract) args; ``weight`` amortizes
    periodic programs (EASGD exchange = 1/avg_freq). ``codec_bytes``:
    price quantization-evidenced collectives at this bytes-per-element
    (codec-on runs; None = raw dtype pricing, the SPMD101 convention)."""
    import jax

    from theanompi_tpu.tools.analyze.signature import (
        extract_signature,
        signature_effective_bytes,
        signature_raw_bytes,
    )

    total = 0.0
    for fn, args, weight in parts:
        sig, axis_sizes = extract_signature(jax.make_jaxpr(fn)(*args))
        if codec_bytes is not None:
            total += signature_effective_bytes(sig, axis_sizes,
                                               codec_bytes) * weight
        else:
            total += signature_raw_bytes(sig, axis_sizes) * weight
    return total


def crosscheck_traffic(traced: float, declared: float) -> dict:
    """Compare traced vs declared raw wire bytes under the SPMD101
    tolerance (tools/analyze/rules.py): ok within
    ``max(512 B, 8% of the larger)``."""
    from theanompi_tpu.tools.analyze.rules import (
        TRAFFIC_ABS_TOL,
        TRAFFIC_REL_TOL,
    )

    tol = max(TRAFFIC_ABS_TOL, TRAFFIC_REL_TOL * max(traced, declared))
    return {
        "traced_bytes": float(traced),
        "declared_bytes": float(declared),
        "tolerance_bytes": float(tol),
        "ok": abs(traced - declared) <= tol,
    }
