"""Model-drift watchdog: do the static truth sources still match the
hardware? (ISSUE 18 tentpole, the forcing function ROADMAP item 4's
planner requires before it can trust a *predicted* step time.)

The repo holds three analytic models nobody continuously audits:
:class:`~theanompi_tpu.utils.flops.CostModel` (FLOPs/HBM roofline →
predicted step wall), :class:`~theanompi_tpu.obs.comm.TrafficModel`
(per-link wire bytes → predicted comm seconds), and
:class:`~theanompi_tpu.utils.flops.MemoryModel` (per-leaf state bytes →
predicted HBM high-water). At every dispatcher drain sync the obs
facade feeds this watchdog the MEASURED counterparts — step wall from
the dispatcher, comm share as the non-compute non-host remainder,
HBM high-water from ``jax.local_devices()[i].memory_stats()`` where the
backend exposes it — and the watchdog maintains one EWMA relative
error per model, surfaced three ways:

- live gauges ``tmpi_model_err_{cost,traffic,memory}``;
- change-gated ``kind=drift`` JSONL records in ``metrics.jsonl`` naming
  the worst-offending component (per-link for traffic, per-leaf-family
  for memory) — schema: tools/check_obs_schema.py;
- a ``drift`` anomaly (flight-recorder bundle ``anomaly_rank{r}-drift/``)
  when an EWMA crosses the configured tolerance band
  (``--drift-tolerance``, default :data:`DRIFT_TOLERANCE_DEFAULT`), so
  the PR-3 triage bundle captures the step where the model lost touch
  with reality.

**Calibrated fallback (CPU test meshes):** like obs/attribution.py,
devices without spec-sheet peaks cannot price a predicted wall, so an
observation calibrates the un-modeled remainder (the LOWEST implied
compute seconds seen for cost — warm-up/compile drains must not pin an
inflated baseline — the first drain's wire bytes for traffic, the
prediction itself for memory when ``memory_stats()`` is absent) and
later errors measure drift AGAINST THAT CALIBRATION — honest about
what it is (``peak_source="calibrated"`` rides the record), and it
keeps the gauges live and the gate non-vacuous on every backend. The
calibrated COST error is gauge-only (exempt from the breach anomaly):
a baseline that is the run's own step wall fed back swings with drain-
window composition, which is signal worth plotting but not worth a
forensic bundle.
"""

from __future__ import annotations

from typing import Optional

DRIFT_TOLERANCE_DEFAULT = 0.25
# EWMA smoothing — one convention across the obs plane (obs/fleet.py
# EWMA_ALPHA): new samples weigh 0.2, so a single noisy drain cannot
# trip the tolerance band on its own
DRIFT_EWMA_ALPHA = 0.2
DRIFT_GAUGE_PREFIX = "model_err_"  # facade prefixes tmpi_ -> tmpi_model_err_*
DRIFT_SOURCES = ("cost", "traffic", "memory")
# change-gate quantum: a record is worth a line when any EWMA moves at
# the third decimal or the breached set changes (mirrors the fleet
# tailer's change-gated kind=fleet records)
_GATE_DECIMALS = 3
# relative-error floor for the measured-comm denominator: a model that
# predicts comm where the measured remainder is ~0 must read as a large
# finite error, not a division blowup
_COMM_MEAS_FLOOR_FRAC = 0.01

# memory_stats() key preference — TPU runtimes report peak_bytes_in_use;
# fall back to the instantaneous figure when the peak is not kept
_MEM_STAT_KEYS = ("peak_bytes_in_use", "bytes_in_use")


def device_peak_bytes() -> Optional[float]:
    """Max measured HBM high-water across local devices via
    ``memory_stats()``; None when the backend keeps no stats (CPU)."""
    try:
        import jax

        peaks = []
        for d in jax.local_devices():
            stats = getattr(d, "memory_stats", None)
            stats = stats() if callable(stats) else None
            if not stats:
                continue
            for key in _MEM_STAT_KEYS:
                if stats.get(key):
                    peaks.append(float(stats[key]))
                    break
        return max(peaks) if peaks else None
    except Exception:
        return None


class DriftWatchdog:
    """Per-run EWMA tracker of predicted-vs-measured error for the three
    analytic models. One instance per rank (the facade owns it); feed it
    every drain via :meth:`observe`, which returns ``(record, breaches)``
    — ``record`` a change-gated ``kind=drift`` body (None when nothing
    moved), ``breaches`` the sources that newly crossed the tolerance
    band this drain (each fires at most one anomaly per run until it
    recovers below the band)."""

    def __init__(self, tolerance: float = DRIFT_TOLERANCE_DEFAULT, *,
                 alpha: float = DRIFT_EWMA_ALPHA, rank: int = 0,
                 link_bps: Optional[float] = None,
                 dcn_bps: Optional[float] = None):
        self.tolerance = float(tolerance)
        self.alpha = float(alpha)
        self.rank = int(rank)
        # test injection points; None = device-table lookup like
        # obs/attribution.py
        self._link_bps = link_bps
        self._dcn_bps = dcn_bps
        self.ewma: dict = {k: None for k in DRIFT_SOURCES}
        self.worst: dict = {k: None for k in DRIFT_SOURCES}
        self.breached: set = set()
        self.peak_source = "spec"
        self._calib_compute_s: Optional[float] = None
        self._calib_wire_bytes: Optional[float] = None
        self._calib_mem_bytes: Optional[float] = None
        self._cost_calibrated = False
        self._last_sig = None

    # -- per-model error terms -------------------------------------------

    def _priced_comm(self, traffic, step_seconds: float):
        """(exposed_comm_s, ici_s, dcn_s) for the traffic model at the
        chip's link bandwidths — the attribute_step pricing, reused —
        or None when the bandwidth is unknown (CPU fallback)."""
        wire = float(traffic.bytes_per_step_amortized)
        if wire <= 0:
            return 0.0, 0.0, 0.0
        link_bps = self._link_bps
        if link_bps is None:
            from theanompi_tpu.obs.attribution import link_bytes_per_sec

            link_bps = link_bytes_per_sec()
        if not link_bps:
            return None
        dcn_wire = float(traffic.dcn_bytes_per_step)
        if dcn_wire > 0:
            from theanompi_tpu.obs.attribution import dcn_bytes_per_sec

            ici_s = max(0.0, wire - dcn_wire) / link_bps
            dcn_s = dcn_wire / float(self._dcn_bps or dcn_bytes_per_sec())
        else:
            ici_s, dcn_s = wire / link_bps, 0.0
        overlap = min(1.0, max(0.0, float(
            traffic.detail.get("overlap_frac") or 0.0)))
        exposed = (ici_s + dcn_s) * (1.0 - overlap)
        return exposed, ici_s, dcn_s

    def _observe_cost(self, cost, step_seconds: float, comm_s: float,
                      host_s: float) -> Optional[float]:
        compute_s = cost.compute_seconds()
        if compute_s is not None:
            hbm = cost.hbm_bound()
            self.worst["cost"] = "hbm" if hbm else "flops"
            self._cost_calibrated = False
        else:
            self._cost_calibrated = True
            # calibrated: the LOWEST implied compute seen pins the
            # un-modeled compute seconds — the first drains amortize
            # compile/warm-up, and pricing every later (faster) step
            # against that inflated baseline would read as permanent
            # drift, so a faster step re-pins the floor and only
            # SLOW-DOWNS against it count as drift
            self.peak_source = "calibrated"
            implied = max(0.0, step_seconds - comm_s - host_s)
            if (self._calib_compute_s is None
                    or implied < self._calib_compute_s):
                self._calib_compute_s = implied
            compute_s = self._calib_compute_s
            self.worst["cost"] = "calibrated-compute"
        predicted = compute_s + comm_s + host_s
        return abs(predicted - step_seconds) / step_seconds

    def _observe_traffic(self, traffic, step_seconds: float,
                         compute_s: Optional[float],
                         host_s: float) -> Optional[float]:
        priced = self._priced_comm(traffic, step_seconds)
        if priced is not None:
            exposed, ici_s, dcn_s = priced
            self.worst["traffic"] = "dcn" if dcn_s > ici_s else "ici"
            if compute_s is None:
                compute_s = self._calib_compute_s
            if compute_s is None:
                # first drain on a calibrated device: cost path has not
                # pinned its baseline yet — nothing measured to diff
                return None
            measured = max(0.0, step_seconds - compute_s - host_s)
            floor = _COMM_MEAS_FLOOR_FRAC * step_seconds
            return abs(exposed - measured) / max(measured, floor)
        # unpriceable link (CPU): drift is the model's own wire bytes
        # moving against the first-drain calibration (a reshard or codec
        # change that nobody re-calibrated shows up here)
        wire = float(traffic.bytes_per_step_amortized)
        if wire <= 0:
            return None
        self.peak_source = "calibrated"
        self.worst["traffic"] = (
            "dcn" if float(traffic.dcn_bytes_per_step) > 0 else "ici")
        if self._calib_wire_bytes is None:
            self._calib_wire_bytes = wire
        return abs(wire - self._calib_wire_bytes) / self._calib_wire_bytes

    def _observe_memory(self, memory,
                        measured_bytes: Optional[float]) -> Optional[float]:
        predicted = float(memory.state_bytes_per_device)
        if predicted <= 0:
            return None
        cats = memory.category_bytes_per_device()
        if cats:
            self.worst["memory"] = max(cats, key=lambda k: cats[k])
        if measured_bytes is None:
            measured_bytes = device_peak_bytes()
        if measured_bytes is None:
            # no memory_stats() on this backend: calibrate the measured
            # high-water to the prediction — error stays 0 until the
            # MODEL moves (a reshard that changes state residency)
            self.peak_source = "calibrated"
            if self._calib_mem_bytes is None:
                self._calib_mem_bytes = predicted
            measured_bytes = self._calib_mem_bytes
        return abs(measured_bytes - predicted) / predicted

    # -- the drain-path entry point --------------------------------------

    def observe(self, step_seconds: float, *, step: int = 0,
                cost=None, traffic=None, memory=None,
                host_frac: Optional[float] = None,
                measured_hbm_bytes: Optional[float] = None):
        """Fold one drain's measurements into the EWMAs.

        Returns ``(record, breaches)``: the change-gated ``kind=drift``
        record body (None when the gate holds it back) and the list of
        sources that newly crossed the tolerance band — the facade turns
        those into the ``drift`` anomaly + flight bundle."""
        if not step_seconds or step_seconds <= 0:
            return None, []
        host_s = min(1.0, max(0.0, float(host_frac or 0.0))) * step_seconds
        comm_s, compute_s = 0.0, None
        if traffic is not None:
            priced = self._priced_comm(traffic, step_seconds)
            if priced is not None:
                comm_s = priced[0]
        if cost is not None:
            compute_s = cost.compute_seconds()

        errs = {
            "cost": self._observe_cost(cost, step_seconds, comm_s, host_s)
            if cost is not None else None,
            "traffic": self._observe_traffic(
                traffic, step_seconds, compute_s, host_s)
            if traffic is not None else None,
            "memory": self._observe_memory(memory, measured_hbm_bytes)
            if memory is not None else None,
        }
        for src, err in errs.items():
            if err is None:
                continue
            prev = self.ewma[src]
            self.ewma[src] = err if prev is None else (
                self.alpha * err + (1.0 - self.alpha) * prev)

        now_breached = {src for src in DRIFT_SOURCES
                        if self.ewma[src] is not None
                        and self.ewma[src] > self.tolerance
                        # a calibrated cost "prediction" is the run's own
                        # step wall fed back — drift against it is timing
                        # noise (epoch-boundary drain windows swing it
                        # 100x on micro-steps), a gauge-worthy signal but
                        # never a forensic-bundle anomaly; the spec
                        # roofline path keeps full breach semantics, as
                        # do the calibrated traffic/memory paths, which
                        # diff exact model outputs, not timers
                        and not (src == "cost" and self._cost_calibrated)}
        breaches = sorted(now_breached - self.breached)
        self.breached = now_breached

        sig = tuple(
            None if self.ewma[src] is None
            else round(self.ewma[src], _GATE_DECIMALS)
            for src in DRIFT_SOURCES
        ) + (frozenset(now_breached),)
        record = None
        if sig != self._last_sig and any(
                v is not None for v in self.ewma.values()):
            self._last_sig = sig
            record = self._record(step, step_seconds)
        return record, breaches

    def _record(self, step: int, step_seconds: float) -> dict:
        """``kind=drift`` JSONL body — all-scalar fields so the schema
        checker's extra-field rule holds; caller stamps ``t``."""
        rec = {
            "kind": "drift", "rank": self.rank, "step": int(step),
            "step_seconds": float(step_seconds),
            "tolerance": self.tolerance,
            "peak_source": self.peak_source,
            "breached": ",".join(sorted(self.breached)),
        }
        for src in DRIFT_SOURCES:
            if self.ewma[src] is not None:
                rec[f"model_err_{src}"] = float(self.ewma[src])
            if self.worst[src]:
                rec[f"worst_{src}"] = str(self.worst[src])
        return rec

    def as_metrics(self) -> dict:
        """Live gauge map (facade prefixes ``tmpi_``):
        ``model_err_{cost,traffic,memory}`` for every source that has
        at least one sample."""
        return {f"{DRIFT_GAUGE_PREFIX}{src}": float(self.ewma[src])
                for src in DRIFT_SOURCES if self.ewma[src] is not None}
