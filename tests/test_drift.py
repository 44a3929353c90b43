"""Model-drift watchdog (ISSUE 18 tentpole): obs/drift.py — EWMA
predicted-vs-measured error per truth source (cost/traffic/memory),
change-gated ``kind=drift`` records, breach-once anomaly semantics —
and the facade integration: ``note_step_seconds`` feeding the watchdog
at every dispatcher drain, ``tmpi_model_err_*`` gauges, the drift
anomaly line + ``anomaly_rank{r}-drift/`` flight bundle, and the
resulting obs dir staying schema-clean."""

import json
import os

import pytest

from theanompi_tpu.obs import Observability
from theanompi_tpu.obs.drift import (
    DRIFT_SOURCES,
    DRIFT_TOLERANCE_DEFAULT,
    DriftWatchdog,
)
from theanompi_tpu.tools.check_obs_schema import main as schema_main
from theanompi_tpu.tools.check_obs_schema import validate_record
from theanompi_tpu.utils.flops import CostModel


def _spec_cost(compute_s=1.0):
    """A CostModel with spec peaks: compute_seconds() == compute_s."""
    return CostModel(flops=compute_s * 1e9, hbm_bytes=1e3,
                     device_kind="tpu v4", peak_flops_per_sec=1e9,
                     peak_hbm_bytes_per_sec=1e12)


def _cpu_cost():
    """No spec peaks (the CPU test-mesh shape): compute_seconds() None."""
    return CostModel(flops=1e9, hbm_bytes=1e3, device_kind="cpu",
                     peak_flops_per_sec=None, peak_hbm_bytes_per_sec=None)


class _Traffic:
    """Duck-typed TrafficModel: the three attributes _priced_comm reads."""

    def __init__(self, wire, dcn=0.0, overlap=0.0):
        self.bytes_per_step_amortized = wire
        self.dcn_bytes_per_step = dcn
        self.detail = {"overlap_frac": overlap}


class _Memory:
    """Duck-typed MemoryModel: prediction + per-leaf-family split."""

    def __init__(self, state_bytes, cats):
        self.state_bytes_per_device = state_bytes
        self.n_devices = 1
        self._cats = cats

    def category_bytes_per_device(self):
        return dict(self._cats)


# --------------------------------------------------------------------------
# watchdog unit behavior
# --------------------------------------------------------------------------


def test_spec_cost_error_ewma_and_change_gate():
    w = DriftWatchdog()
    assert w.tolerance == DRIFT_TOLERANCE_DEFAULT
    # predicted 1.0s vs measured 1.25s -> relative error 0.2
    rec, br = w.observe(1.25, step=1, cost=_spec_cost())
    assert br == []
    assert rec is not None and rec["kind"] == "drift"
    assert rec["model_err_cost"] == pytest.approx(0.2)
    assert rec["worst_cost"] == "flops"  # flops-bound roofline term
    assert rec["breached"] == ""
    assert validate_record({**rec, "t": 1.0}) == []
    # identical reading: EWMA unchanged at the gate quantum -> no record
    rec2, _ = w.observe(1.25, step=2, cost=_spec_cost())
    assert rec2 is None
    # a different reading moves the EWMA: 0.2*0.5 + 0.8*0.2 = 0.26
    rec3, br3 = w.observe(2.0, step=3, cost=_spec_cost())
    assert rec3 is not None
    assert rec3["model_err_cost"] == pytest.approx(0.26)
    assert br3 == ["cost"]  # 0.26 > the 0.25 default band
    assert rec3["breached"] == "cost"
    # still above the band: already-breached sources do NOT re-fire
    _, br4 = w.observe(2.0, step=4, cost=_spec_cost())
    assert br4 == []


def test_calibrated_cost_fallback_pins_first_drain():
    w = DriftWatchdog()
    rec, _ = w.observe(1.0, step=1, cost=_cpu_cost())
    # first drain IS the calibration: zero error, flagged honestly
    assert rec["model_err_cost"] == pytest.approx(0.0)
    assert rec["peak_source"] == "calibrated"
    assert rec["worst_cost"] == "calibrated-compute"
    # the step wall moving 50% against the pinned baseline is drift
    rec2, _ = w.observe(2.0, step=2, cost=_cpu_cost())
    assert w.ewma["cost"] == pytest.approx(0.2 * 0.5)
    # a FASTER drain re-pins the floor (the first drains amortize
    # compile/warm-up; pricing later steps against that inflated
    # baseline would read as permanent drift)
    rec3, _ = w.observe(0.5, step=3, cost=_cpu_cost())
    assert w._calib_compute_s == pytest.approx(0.5)
    rec4, _ = w.observe(0.5, step=4, cost=_cpu_cost())
    # re-pinned baseline == measurement: this sample's error is zero
    assert w.ewma["cost"] < 0.2 * 0.5


def test_calibrated_cost_never_breaches():
    """A calibrated cost 'prediction' is the run's own step wall fed
    back — epoch-boundary drain windows swing it 100x on micro-steps,
    so it must stay a gauge-only signal: EWMA over tolerance, record
    written, but NO drift anomaly (the spec roofline path keeps full
    breach semantics — test_breach above)."""
    w = DriftWatchdog(tolerance=0.1, alpha=1.0)
    w.observe(1.0, step=1, cost=_cpu_cost())
    rec, br = w.observe(5.0, step=2, cost=_cpu_cost())
    assert w.ewma["cost"] > w.tolerance
    assert br == [] and w.breached == set()
    assert rec["breached"] == ""


def test_priced_traffic_error_and_worst_link():
    # injected link bandwidths (no device lookup): ici 100 B/s, dcn 10
    w = DriftWatchdog(link_bps=100.0, dcn_bps=10.0)
    t = _Traffic(wire=100.0, dcn=50.0)
    # ici_s = 50/100 = 0.5, dcn_s = 50/10 = 5.0 -> exposed 5.5s; with
    # compute 1.0s the measured comm remainder of a 7s step is 6.0s
    rec, _ = w.observe(7.0, step=1, cost=_spec_cost(1.0), traffic=t)
    assert rec["model_err_traffic"] == pytest.approx(0.5 / 6.0)
    assert rec["worst_traffic"] == "dcn"  # dcn_s dominates ici_s
    # ici-dominated wire flips the worst-offender label
    w2 = DriftWatchdog(link_bps=10.0, dcn_bps=1e9)
    rec2, _ = w2.observe(12.0, step=1, cost=_spec_cost(1.0),
                         traffic=_Traffic(wire=100.0, dcn=1.0))
    assert rec2["worst_traffic"] == "ici"


def test_unpriced_traffic_drifts_against_wire_calibration():
    # no injected bandwidth and no TPU -> unpriceable: the wire bytes
    # themselves calibrate on the first drain
    w = DriftWatchdog()
    t = _Traffic(wire=100.0)
    rec, _ = w.observe(1.0, step=1, traffic=t)
    assert rec["model_err_traffic"] == pytest.approx(0.0)
    assert rec["peak_source"] == "calibrated"
    t.bytes_per_step_amortized = 150.0  # a reshard nobody re-calibrated
    w.observe(1.0, step=2, traffic=t)
    assert w.ewma["traffic"] == pytest.approx(0.2 * 0.5)
    assert w.worst["traffic"] == "ici"


def test_memory_error_names_worst_leaf_family():
    w = DriftWatchdog()
    m = _Memory(1000.0, {"conv": 600.0, "fc": 400.0})
    rec, _ = w.observe(1.0, step=1, memory=m, measured_hbm_bytes=1500.0)
    assert rec["model_err_memory"] == pytest.approx(0.5)
    assert rec["worst_memory"] == "conv"  # the largest declared family
    # without memory_stats() the prediction self-calibrates: error 0
    w2 = DriftWatchdog()
    rec2, _ = w2.observe(1.0, step=1, memory=m)
    assert rec2["model_err_memory"] == pytest.approx(0.0)
    assert rec2["peak_source"] == "calibrated"


def test_breach_recovery_rearms_the_anomaly():
    w = DriftWatchdog(tolerance=0.1, alpha=1.0)  # no smoothing
    m = _Memory(1000.0, {"w": 1000.0})
    _, br = w.observe(1.0, step=1, memory=m, measured_hbm_bytes=1500.0)
    assert br == ["memory"]
    # recovery below the band clears the latch...
    _, br = w.observe(1.0, step=2, memory=m, measured_hbm_bytes=1000.0)
    assert br == [] and w.breached == set()
    # ...so the next crossing fires again
    _, br = w.observe(1.0, step=3, memory=m, measured_hbm_bytes=1500.0)
    assert br == ["memory"]


def test_as_metrics_only_sampled_sources():
    w = DriftWatchdog()
    assert w.as_metrics() == {}
    w.observe(1.25, step=1, cost=_spec_cost())
    assert set(w.as_metrics()) == {"model_err_cost"}
    assert w.as_metrics()["model_err_cost"] == pytest.approx(0.2)
    assert set(DRIFT_SOURCES) == {"cost", "traffic", "memory"}


# --------------------------------------------------------------------------
# facade integration: the dispatcher-drain path end to end
# --------------------------------------------------------------------------


def test_facade_drain_writes_record_anomaly_and_bundle(tmp_path):
    """note_step_seconds with a cost model declared: drift record in
    metrics.jsonl, tmpi_model_err_cost gauge live, and a tolerance
    breach raising the drift anomaly + its own flight bundle — the
    whole dir staying schema-clean."""
    obs_dir = str(tmp_path / "obs")
    obs = Observability(obs_dir=obs_dir, rank=0, drift_tolerance=0.05)
    obs.set_cost_model(_spec_cost(1.0))
    obs.on_step(step=10, step_seconds=None)
    obs.note_step_seconds(2.0)  # predicted 1.0 vs 2.0 -> EWMA 0.5
    obs.close()

    drift_recs = [json.loads(ln) for ln in
                  open(os.path.join(obs_dir, "metrics.jsonl"))
                  if '"drift"' in ln]
    assert len(drift_recs) == 1
    rec = drift_recs[0]
    assert rec["step"] == 10 and rec["breached"] == "cost"
    assert rec["model_err_cost"] == pytest.approx(0.5)
    assert "t" in rec and validate_record(rec) == []

    anomalies = [json.loads(ln) for ln in
                 open(os.path.join(obs_dir, "numerics_rank0.jsonl"))
                 if '"anomaly"' in ln]
    assert len(anomalies) == 1
    a = anomalies[0]
    assert a["metric"] == "model_err_cost" and a["reason"] == "drift"
    assert a["step"] == 10
    # the breach gets its OWN flight bundle dir (not the numerics
    # anomaly budget)
    assert os.path.isdir(os.path.join(obs_dir, "anomaly_rank0-drift"))
    # gauges are live
    prom = obs.registry.to_prometheus()
    assert "tmpi_model_err_cost 0.5" in prom
    assert "tmpi_drift_breaches_total 1" in prom
    assert schema_main([obs_dir, "-q"]) == 0


def test_facade_change_gate_holds_across_steady_drains(tmp_path):
    obs_dir = str(tmp_path / "obs")
    obs = Observability(obs_dir=obs_dir, rank=0)
    obs.set_cost_model(_spec_cost(1.0))
    for step in (1, 2, 3):
        obs.on_step(step=step, step_seconds=None)
        obs.note_step_seconds(1.1)  # steady 0.0909 error, below band
    obs.close()
    lines = [ln for ln in open(os.path.join(obs_dir, "metrics.jsonl"))
             if '"drift"' in ln]
    # first drain emits, the steady tail is change-gated away
    assert len(lines) == 1
    assert not os.path.exists(os.path.join(obs_dir, "numerics_rank0.jsonl"))


def test_facade_without_models_stays_silent(tmp_path):
    obs_dir = str(tmp_path / "obs")
    obs = Observability(obs_dir=obs_dir, rank=0)
    obs.on_step(step=1, step_seconds=None)
    obs.note_step_seconds(1.0)
    obs.close()
    assert not any('"drift"' in ln for ln in
                   open(os.path.join(obs_dir, "metrics.jsonl")))


def test_facade_memory_model_hook(tmp_path):
    obs_dir = str(tmp_path / "obs")
    obs = Observability(obs_dir=obs_dir, rank=0)
    obs.set_memory_model(_Memory(1000.0, {"w": 1000.0}))
    obs.on_step(step=5, step_seconds=None)
    obs.note_step_seconds(1.0)
    obs.close()
    recs = [json.loads(ln) for ln in
            open(os.path.join(obs_dir, "metrics.jsonl"))
            if '"drift"' in ln]
    assert recs and "model_err_memory" in recs[0]
    assert recs[0]["worst_memory"] == "w"
    prom_path = os.path.join(obs_dir, "metrics.prom")
    assert os.path.exists(prom_path)
    assert "tmpi_memory_state_bytes_per_device" in open(prom_path).read()
