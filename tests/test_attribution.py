"""Step-time attribution profiler (obs/attribution.py, tools/profile.py):
fraction math + roofline classification, the live
gauge/record path through the obs facade, the `tmpi profile` report
(cross-checked against traffic_model under the SPMD101 tolerance), and the
op-table join on the checked-in synthetic trace fixture."""

import json
import os

import pytest

import jax

from theanompi_tpu.obs.attribution import (
    attribute_step,
    crosscheck_traffic,
    format_join,
    join_op_table,
    link_bytes_per_sec,
)
from theanompi_tpu.obs.comm import TrafficModel
from theanompi_tpu.utils.flops import CostModel

FIXTURE_TRACE = os.path.join(os.path.dirname(__file__), "fixtures",
                             "op_profile_trace")


def _spec_cost(flops=1e9, hbm=1e6, peak_f=100e12, peak_b=1000e9):
    return CostModel(flops=flops, hbm_bytes=hbm, device_kind="fake v9",
                     peak_flops_per_sec=peak_f,
                     peak_hbm_bytes_per_sec=peak_b)


# -- attribute_step ----------------------------------------------------------

def test_spec_mode_fractions_and_mfu():
    """Known inputs -> exact fractions; residual books the remainder
    and the sum is 1.0 by construction."""
    cost = _spec_cost()  # compute time = 1e9/100e12 = 10us (flops-bound)
    tm = TrafficModel(rule="bsp", n_workers=4, bytes_per_step=1e6)
    a = attribute_step(100e-6, cost=cost, traffic=tm, host_frac=0.1,
                       link_bps=100e9)  # comm time = 1e6/100e9 = 10us
    assert a.peak_source == "spec"
    assert a.fractions["compute"] == pytest.approx(0.1)
    assert a.fractions["comm"] == pytest.approx(0.1)
    assert a.fractions["host"] == pytest.approx(0.1)
    assert a.fractions["residual"] == pytest.approx(0.7)
    assert a.fractions_sum == pytest.approx(1.0)
    # mfu = (1e9 flops / 100us) / 100e12 peak = 0.1
    assert a.mfu == pytest.approx(0.1)
    assert a.mfu_calibrated is None
    assert a.hbm_gbps == pytest.approx(1e6 / 100e-6 / 1e9)
    assert a.classification == "compute-bound"


def test_hbm_bound_classification():
    """When bytes/peak_bw exceeds flops/peak_flops the roofline verdict
    flips to hbm-bound."""
    cost = _spec_cost(flops=1e9, hbm=1e9)  # 10us compute, 1ms HBM
    a = attribute_step(2e-3, cost=cost)
    assert cost.hbm_bound() is True
    assert a.classification == "hbm-bound"
    assert a.fractions["compute"] == pytest.approx(0.5)  # max() roofline


def test_comm_and_host_bound_classifications():
    tm = TrafficModel(rule="bsp", n_workers=8, bytes_per_step=80e6)
    a = attribute_step(1e-3, cost=_spec_cost(), traffic=tm,
                       link_bps=100e9)  # comm 800us of a 1ms step
    assert a.classification == "comm-bound"
    b = attribute_step(1e-3, cost=_spec_cost(), host_frac=0.9)
    assert b.classification == "host-bound"
    # a small host share never wins host-bound even if largest
    c = attribute_step(1e-3, host_frac=0.2)
    assert c.classification != "host-bound" or c.fractions["host"] >= 0.4
    # ... and when host dominates but misses the threshold, the verdict
    # falls to whichever of compute/comm actually dominates — here comm
    # (0.35) beats compute (0.2), so a compute-bound label would steer
    # the fusion work at the wrong target
    tm2 = TrafficModel(rule="bsp", n_workers=8, bytes_per_step=35e6)
    d = attribute_step(
        1e-3, cost=_spec_cost(flops=20e9), traffic=tm2,  # compute 0.2
        host_frac=0.38, link_bps=100e9,  # comm 0.35, host 0.38 < 0.4
    )
    assert d.fractions["host"] == pytest.approx(0.38)
    assert d.fractions["comm"] == pytest.approx(0.35)
    assert d.classification == "comm-bound"


def test_calibrated_mode_on_peakless_device():
    """No spec peaks (CPU): compute is the non-host non-comm remainder,
    residual exactly 0, and the calibrated MFU stand-in is numeric so
    the perf gate still has a ratio to diff."""
    cost = CostModel(flops=1e9, hbm_bytes=1e6, device_kind="cpu")
    a = attribute_step(1e-3, cost=cost, host_frac=0.25)
    assert a.peak_source == "calibrated"
    assert a.mfu is None
    assert a.fractions["compute"] == pytest.approx(0.75)
    assert a.mfu_calibrated == pytest.approx(0.75)
    assert a.fractions["residual"] == 0.0
    assert a.fractions_sum == pytest.approx(1.0)
    assert "calibrated_note" in a.detail


def test_model_overrun_flagged():
    """Models explaining more than the measured step leave a negative
    residual (sum still 1.0) and a detail flag — a finding, not a
    crash."""
    cost = _spec_cost(flops=1e9)  # 10us at peak
    a = attribute_step(5e-6, cost=cost, host_frac=0.5)  # 10us > 5us step
    assert a.fractions["residual"] < -0.02
    assert a.fractions_sum == pytest.approx(1.0)
    assert "model_overrun" in a.detail


def test_overlap_frac_discounts_comm():
    """The bucketed-allreduce fix: only the EXPOSED (1 - overlap) share
    of the collective books as comm; the hidden seconds are named in
    detail rather than double-counted against compute."""
    cost = _spec_cost()  # 10us compute
    tm = TrafficModel(rule="bsp", n_workers=4, bytes_per_step=1e6)
    serial = attribute_step(100e-6, cost=cost, traffic=tm, host_frac=0.1,
                            link_bps=100e9)  # comm model = 10us
    overlapped = attribute_step(100e-6, cost=cost, traffic=tm,
                                host_frac=0.1, link_bps=100e9,
                                overlap_frac=0.75)
    assert serial.fractions["comm"] == pytest.approx(0.1)
    assert overlapped.fractions["comm"] == pytest.approx(0.025)
    # the hidden share moves to the residual, not into thin air
    assert overlapped.fractions["residual"] == pytest.approx(
        serial.fractions["residual"] + 0.075)
    assert overlapped.fractions_sum == pytest.approx(1.0)
    assert overlapped.detail["overlap_frac"] == pytest.approx(0.75)
    assert overlapped.detail["comm_hidden_s"] == pytest.approx(7.5e-6)


def test_overlap_frac_defaults_from_traffic_detail():
    """The bucketed engine's traffic_model carries the schedule's
    overlap estimate in detail — attribute_step must pick it up without
    an explicit argument (the obs facade path passes none)."""
    cost = _spec_cost()
    tm = TrafficModel(rule="bsp", n_workers=4, bytes_per_step=1e6,
                      detail={"n_buckets": 4, "overlap_frac": 0.75})
    a = attribute_step(100e-6, cost=cost, traffic=tm, link_bps=100e9)
    assert a.fractions["comm"] == pytest.approx(0.025)
    # explicit argument overrides the detail block
    b = attribute_step(100e-6, cost=cost, traffic=tm, link_bps=100e9,
                       overlap_frac=0.0)
    assert b.fractions["comm"] == pytest.approx(0.1)


def test_attribute_step_rejects_bad_wall():
    with pytest.raises(ValueError, match="step_seconds"):
        attribute_step(0.0)


def test_link_table_unknown_device_is_none():
    class Cpu:
        device_kind = "cpu"

    assert link_bytes_per_sec(Cpu()) is None

    class V5e:
        device_kind = "TPU v5 lite"

    assert link_bytes_per_sec(V5e()) == 200e9


# -- kind=profile record + schema -------------------------------------------

def test_profile_record_passes_schema_and_sum_is_enforced():
    from theanompi_tpu.tools.check_obs_schema import validate_record

    a = attribute_step(1e-3, cost=_spec_cost(), host_frac=0.1)
    rec = a.as_record(step=7, rank=0, rule="bsp")
    assert rec["kind"] == "profile"
    assert validate_record(rec) == []
    bad = dict(rec, fractions={"compute": 0.5, "comm": 0.1,
                               "host": 0.1, "residual": 0.1})  # sums 0.8
    errs = validate_record(bad)
    assert errs and "sum" in errs[0]


# -- op-table join on the checked-in fixture ---------------------------------

def test_fixture_trace_op_table():
    """The checked-in synthetic trace parses to the expected per-op
    rows (container dropped, host track ignored, instances collapsed)."""
    from theanompi_tpu.tools.op_profile import format_table, op_table

    rows = op_table(FIXTURE_TRACE, steps=4)
    ops = {r["op"]: r for r in rows}
    assert set(ops) == {"conv_fusion.#", "convert_reduce_fusion.#",
                        "all-reduce.#"}
    assert ops["conv_fusion.#"]["ms_per_step"] == pytest.approx(0.6)
    assert ops["all-reduce.#"]["share"] == pytest.approx(0.15)
    assert "conv_fusion.#" in format_table(rows)


def test_join_op_table_classifies_and_names_unattributed():
    """all-reduce ops book as comm; the class the model under-explains
    owns the top-unattributed list."""
    from theanompi_tpu.tools.op_profile import op_table

    rows = op_table(FIXTURE_TRACE, steps=4)
    # model explains 0.2ms compute + all the comm: compute overshoots
    a = attribute_step(1e-3, cost=_spec_cost(flops=20e9), host_frac=0.0,
                       traffic=TrafficModel(rule="bsp", n_workers=4,
                                            bytes_per_step=15e6),
                       link_bps=100e9)  # comm model 0.15ms
    join = join_op_table(rows, a)
    assert join["measured_ms"]["comm"] == pytest.approx(0.15)
    assert join["measured_ms"]["compute"] == pytest.approx(0.85)
    assert join["model_ms"]["compute"] == pytest.approx(0.2)
    assert join["unattributed_ms"]["compute"] == pytest.approx(0.65)
    assert join["unattributed_ms"]["comm"] == pytest.approx(0.0, abs=1e-9)
    tops = [r["op"] for r in join["top_unattributed"]]
    assert tops and tops[0] == "conv_fusion.#"
    assert all(
        r["class"] == "compute" for r in join["top_unattributed"]
    )
    txt = format_join(join)
    assert "conv_fusion.#" in txt and "top unattributed" in txt


def test_join_empty_rows_degrades():
    a = attribute_step(1e-3, cost=_spec_cost())
    join = join_op_table([], a)
    assert join["rows"] == [] and join["top_unattributed"] == []
    assert "CPU capture" in format_join(join)


# -- crosscheck --------------------------------------------------------------

def test_crosscheck_tolerance_matches_spmd101():
    from theanompi_tpu.tools.analyze.rules import (
        TRAFFIC_ABS_TOL,
        TRAFFIC_REL_TOL,
    )

    ok = crosscheck_traffic(100_000.0, 104_000.0)  # 4% < 8%
    assert ok["ok"]
    assert ok["tolerance_bytes"] == pytest.approx(
        max(TRAFFIC_ABS_TOL, TRAFFIC_REL_TOL * 104_000.0)
    )
    assert not crosscheck_traffic(100_000.0, 200_000.0)["ok"]
    assert crosscheck_traffic(0.0, 0.0)["ok"]  # single-device: 0 vs 0


# -- engine cost_model hooks -------------------------------------------------

@pytest.mark.parametrize("engine_name", ["bsp", "zero1"])
def test_engine_cost_model(mesh8, engine_name):
    from theanompi_tpu.tools.analyze.harness import _tiny_model

    model = _tiny_model()
    if engine_name == "bsp":
        from theanompi_tpu.parallel.bsp import BSPEngine

        eng = BSPEngine(model, mesh8)
    else:
        from theanompi_tpu.parallel.zero import ZeroEngine

        eng = ZeroEngine(model, mesh8)
    state = eng.init_state(jax.random.PRNGKey(0))
    cost = eng.cost_model(state, 16)
    assert cost is not None and cost.flops > 0
    assert cost.hbm_bytes > 0
    assert cost.peak_flops_per_sec is None  # CPU mesh: no spec peak
    assert cost.mfu(0.01) is None
    assert cost.hbm_gbps(0.01) == pytest.approx(cost.hbm_bytes / 0.01 / 1e9)


# -- obs facade: live gauges + snapshot record -------------------------------

def test_obs_live_gauges_and_snapshot_record(tmp_path):
    """set_cost_model arms the drain-path attribution: note_step_seconds
    refreshes tmpi_mfu/tmpi_hbm_gbps/tmpi_step_*_frac (host floats only,
    no syncs) and the next snapshot writes a schema-valid kind=profile
    record."""
    from theanompi_tpu.obs import Observability
    from theanompi_tpu.tools.check_obs_schema import check_file

    obs = Observability(str(tmp_path))
    try:
        obs.set_traffic_model(TrafficModel(rule="bsp", n_workers=4,
                                           bytes_per_step=1e6))
        obs.set_cost_model(_spec_cost())
        obs.note_step_seconds(100e-6)
        g = obs.registry
        assert g.gauge("tmpi_mfu").value() == pytest.approx(0.1)
        assert g.gauge("tmpi_step_compute_frac").value() == pytest.approx(0.1)
        assert g.gauge("tmpi_hbm_gbps").value() == pytest.approx(10.0)
        assert g.gauge("tmpi_cost_flops_per_step").value() == 1e9
        obs.snapshot(step=3)
    finally:
        obs.close()
    kinds = []
    with open(tmp_path / "metrics.jsonl") as f:
        for line in f:
            rec = json.loads(line)
            kinds.append(rec["kind"])
            if rec["kind"] == "profile":
                assert rec["step"] == 3 and rec["rule"] == "bsp"
                assert rec["mfu"] == pytest.approx(0.1)
                assert sum(rec["fractions"].values()) == pytest.approx(1.0)
    assert "profile" in kinds
    assert check_file(str(tmp_path / "metrics.jsonl")) == []


def test_obs_without_cost_model_emits_no_profile_record(tmp_path):
    from theanompi_tpu.obs import Observability

    obs = Observability(str(tmp_path))
    try:
        obs.note_step_seconds(1e-3)
        obs.snapshot(step=1)
    finally:
        obs.close()
    kinds = [json.loads(l)["kind"]
             for l in open(tmp_path / "metrics.jsonl") if l.strip()]
    assert "profile" not in kinds


# -- run_training integration ------------------------------------------------

def test_run_training_live_attribution(tmp_path):
    """An obs-enabled run wires the engine's cost model automatically:
    live gauges in the snapshots, kind=profile records on the snapshot
    cadence, the shared-module mfu/tflops in the summary, and the whole
    obs dir stays schema-clean. Hot-loop lint is separately pinned by
    tests/test_check_hot_loop.py — this run proves the gauges come from
    the drain path, not new syncs."""
    from theanompi_tpu.launch.worker import run_training
    from theanompi_tpu.models.mlp import MLP
    from theanompi_tpu.tools.check_obs_schema import check_file

    obs_dir = str(tmp_path / "obs")
    summary = run_training(
        rule="bsp", model_cls=MLP, devices=4, max_steps=6, n_epochs=100,
        dataset="synthetic",
        dataset_kwargs={"n_train": 128, "n_val": 64,
                        "image_shape": (16, 16, 3)},
        obs_dir=obs_dir, metrics_snapshot_freq=2, print_freq=0,
        dispatch_depth=2,
    )
    assert "mfu" in summary  # None on CPU (no spec peak) — key present
    assert summary["mfu"] is None
    assert summary["tflops_per_sec"] > 0
    profiles = []
    gauge_keys = set()
    with open(os.path.join(obs_dir, "metrics.jsonl")) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("kind") == "profile":
                profiles.append(rec)
            if rec.get("kind") == "metrics":
                gauge_keys |= set(rec["metrics"])
    assert profiles, "no kind=profile records on the snapshot cadence"
    for rec in profiles:
        assert sum(rec["fractions"].values()) == pytest.approx(1.0,
                                                               abs=0.02)
        assert rec["peak_source"] == "calibrated"  # CPU mesh
    assert {"tmpi_step_compute_frac", "tmpi_step_host_frac",
            "tmpi_hbm_gbps", "tmpi_cost_flops_per_step"} <= gauge_keys
    assert check_file(os.path.join(obs_dir, "metrics.jsonl")) == []


# -- tmpi profile ------------------------------------------------------------

def test_profile_report_end_to_end(tmp_path):
    """run_profile on the CPU mesh: fractions sum to 1 +/- 0.02, the
    collective bytes cross-check the engine's traffic_model() within
    the SPMD101 tolerance, and report.json lands — the acceptance
    path, in-process."""
    from theanompi_tpu.tools.profile import format_report, run_profile

    report = run_profile(model_name="mlp", engine_name="bsp", steps=3,
                         devices=4, out_dir=str(tmp_path / "prof"))
    assert os.path.exists(tmp_path / "prof" / "report.json")
    a = report["attribution"]
    assert abs(a["fractions_sum"] - 1.0) <= 0.02
    cc = report["traffic"]["crosscheck"]
    assert cc["ok"], cc
    assert cc["declared_bytes"] == pytest.approx(
        report["traffic"]["raw_bytes_per_step"]
    )
    assert cc["traced_bytes"] > 0  # 4-device psum: real wire volume
    assert report["mfu"] is not None and 0 < report["mfu"] <= 1
    assert report["mfu_source"] == "calibrated"
    txt = format_report(report)
    assert "step-time attribution" in txt and "cross-check" in txt


def test_profile_easgd_crosschecks_amortized_exchange(tmp_path):
    """EASGD's periodic elastic exchange is traced at 1/avg_freq weight
    — the cross-check must land within tolerance of the declared
    amortized model, not the per-exchange bytes."""
    from theanompi_tpu.tools.profile import run_profile

    report = run_profile(model_name="mlp", engine_name="easgd", steps=4,
                         devices=4, avg_freq=2, batch=16,
                         out_dir=str(tmp_path / "prof_easgd"))
    cc = report["traffic"]["crosscheck"]
    assert cc["ok"], cc
    assert cc["traced_bytes"] > 0


def test_profile_rejects_bad_args(tmp_path):
    from theanompi_tpu.tools.profile import run_profile

    with pytest.raises(ValueError, match="steps"):
        run_profile(steps=0, out_dir=str(tmp_path))
    with pytest.raises(ValueError, match="engine"):
        run_profile(engine_name="nope", out_dir=str(tmp_path))
    with pytest.raises(ValueError, match="LM models"):
        run_profile(model_name="mlp", engine_name="nd",
                    out_dir=str(tmp_path))
