"""``tmpi report`` (ISSUE 18 tentpole): the unified run report over a
fabricated 4-rank failure dir — one retry (crash cause), one reshard,
one drift-tolerance breach, one straggler verdict. The acceptance bar:
the causally-grouped timeline names every incident's evidence records
(file:line), the ``--json`` body schema-validates, the markdown and
HTML renderings carry the same story, and the tool is read-only and
byte-deterministic over a finished dir."""

import json
import os

from theanompi_tpu.cli import main as cli_main
from theanompi_tpu.tools.check_obs_schema import validate_record
from theanompi_tpu.tools.report import build_report, report_main


def write_failure_dir(obs):
    """The ISSUE 18 acceptance scenario, every record schema-valid:
    drift breach (t=80) -> reshard 4->3 (t=90) -> nonfinite halt
    anomaly (t=99) -> supervisor retry (t=100, the adopter), plus a
    persistent-straggler verdict on rank 2 and per-rank span
    summaries."""
    os.makedirs(obs, exist_ok=True)
    with open(os.path.join(obs, "metrics.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "drift", "rank": 0, "t": 70.0, "step": 20,
            "tolerance": 0.25, "breached": "",
            "model_err_cost": 0.08, "worst_cost": "flops",
            "step_seconds": 1.0, "peak_source": "spec"}) + "\n")
        f.write(json.dumps({
            "kind": "drift", "rank": 0, "t": 80.0, "step": 30,
            "tolerance": 0.25, "breached": "cost",
            "model_err_cost": 0.31, "worst_cost": "flops",
            "step_seconds": 1.4, "peak_source": "spec"}) + "\n")
        f.write(json.dumps({
            "kind": "reshard", "rank": 0, "t": 90.0, "step": 35,
            "from_world": 4, "to_world": 3, "seconds": 2.5}) + "\n")
    with open(os.path.join(obs, "numerics_rank1.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "anomaly", "rank": 1, "t": 99.0, "step": 39,
            "metric": "nm_grad_norm", "reason": "nonfinite",
            "policy": "halt"}) + "\n")
    with open(os.path.join(obs, "supervisor.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "retry", "rank": 0, "t": 100.0, "attempt": 1,
            "step": 40, "error": "InjectedCrash('boom')",
            "backoff_s": 0.5, "cause": "crash"}) + "\n")
    with open(os.path.join(obs, "fleet.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "fleet", "t": 75.0, "step": 25, "ranks": 4,
            "stragglers": "2"}) + "\n")
        f.write(json.dumps({
            "kind": "fleet", "t": 85.0, "step": 32, "ranks": 4,
            "stragglers": "2"}) + "\n")
    for r in range(4):
        with open(os.path.join(obs, f"spans_rank{r}.jsonl"), "w") as f:
            f.write(json.dumps({
                "kind": "span_summary", "rank": r, "t0": 40.0,
                "wall_s": 60.0,
                "fractions": {"step": 0.8, "data_wait": 0.1,
                              "checkpoint": 0.05},
                "totals_s": {"step": 48.0, "data_wait": 6.0,
                             "checkpoint": 3.0},
                "counts": {"step": 40, "data_wait": 40,
                           "checkpoint": 2}}) + "\n")


def test_causal_grouping_names_every_evidence_record(tmp_path):
    obs = str(tmp_path / "obs")
    write_failure_dir(obs)
    rep = build_report(obs)

    assert rep["verdict"] == "degraded"  # retried past the halt: not halted
    assert rep["ranks"] == 4
    assert rep["n_incidents"] == 1
    inc = rep["incidents"][0]
    assert inc["kind"] == "retry" and inc["src"] == "supervisor.jsonl:1"
    # the retry ADOPTED its cause chain, in time order, each citing the
    # exact record line: drift breach -> reshard -> crash anomaly
    assert [e["src"] for e in inc["evidence"]] == [
        "metrics.jsonl:2", "metrics.jsonl:3", "numerics_rank1.jsonl:1"]
    assert [e["kind"] for e in inc["evidence"]] == [
        "drift", "reshard", "anomaly"]
    # the straggler verdict annotates the steps it covered
    anns = rep["fleet"]["stragglers"]
    assert len(anns) == 1
    assert anns[0]["rank"] == "2" and anns[0]["flag"] == "straggler"
    assert anns[0]["step_lo"] == 25 and anns[0]["step_hi"] == 32
    assert anns[0]["src"] == "fleet.jsonl:1"
    # drift trajectory: the breach is cited, the pre-breach record isn't
    assert rep["drift"]["breaches"] == [
        {"step": 30, "src": "metrics.jsonl:2", "breached": "cost"}]
    assert rep["drift"]["last"]["model_err_cost"] == 0.31
    # per-phase wall breakdown rolled up over all 4 ranks
    assert rep["phases"]["_wall_s"] == 240.0
    assert rep["phases"]["step"]["seconds"] == 192.0
    assert rep["phases"]["data_wait"]["frac"] == 0.1
    # timeline is monotonic and every notable event carries provenance
    ts = [e["t"] for e in rep["timeline"]]
    assert ts == sorted(ts)
    assert all(":" in e["src"] for e in rep["timeline"])


def test_json_body_schema_validates_and_is_deterministic(tmp_path, capsys):
    obs = str(tmp_path / "obs")
    write_failure_dir(obs)
    assert report_main([obs, "--json"]) == 0
    out1 = capsys.readouterr().out
    rep = json.loads(out1)
    assert rep["kind"] == "report"
    assert validate_record(rep) == []
    # a second invocation is byte-identical: nothing wall-clock-derived
    # rides the body
    assert report_main([obs, "--json"]) == 0
    assert capsys.readouterr().out == out1


def test_markdown_and_html_renderings(tmp_path, capsys):
    obs = str(tmp_path / "obs")
    write_failure_dir(obs)
    assert report_main([obs]) == 0
    md = capsys.readouterr().out
    assert "Verdict: DEGRADED" in md
    assert "caused by [anomaly]" in md and "numerics_rank1.jsonl:1" in md
    assert "rank 2 flagged straggler over steps 25–32" in md
    assert "## Per-phase wall breakdown" in md
    assert "**breach** at step 30" in md
    out_md = tmp_path / "report.md"
    out_html = tmp_path / "report.html"
    assert report_main([obs, "--out", str(out_md)]) == 0
    assert report_main([obs, "--out", str(out_html)]) == 0
    assert out_md.read_text() == md
    html = out_html.read_text()
    assert html.startswith("<!doctype html>")
    assert "InjectedCrash(&#x27;boom&#x27;)" in html  # escaped, present


def test_read_only_and_cli_dispatch(tmp_path, capsys):
    """A viewer must never grow the dir it reads: the file set is
    byte-identical after reporting, and `tmpi report` dispatches
    without touching jax platform setup."""
    obs = str(tmp_path / "obs")
    write_failure_dir(obs)
    before = {f: os.path.getsize(os.path.join(obs, f))
              for f in sorted(os.listdir(obs))}
    assert cli_main(["report", obs, "--json"]) == 0
    capsys.readouterr()
    after = {f: os.path.getsize(os.path.join(obs, f))
             for f in sorted(os.listdir(obs))}
    assert after == before


def test_stall_forces_halted_verdict(tmp_path):
    obs = tmp_path / "obs"
    obs.mkdir()
    (obs / "stall_rank0.json").write_text(json.dumps({
        "kind": "stall", "rank": 0, "t": 50.0, "step": 12,
        "stall_s": 130.0, "timeout_s": 120.0,
        "stacks": {"MainThread": ["step()"]}}))
    rep = build_report(str(obs))
    assert rep["verdict"] == "halted"
    assert any("stall_rank0.json:1" in ev for ev in rep["evidence"])


def test_unadopted_halt_anomaly_is_halted(tmp_path):
    """A halt-policy anomaly with NO later retry means the supervisor
    never recovered past it — the run halted there."""
    obs = tmp_path / "obs"
    obs.mkdir()
    (obs / "numerics_rank0.jsonl").write_text(json.dumps({
        "kind": "anomaly", "rank": 0, "t": 10.0, "step": 5,
        "metric": "nm_loss", "reason": "nonfinite",
        "policy": "halt"}) + "\n")
    rep = build_report(str(obs))
    assert rep["verdict"] == "halted"
    assert rep["n_incidents"] == 1  # the anomaly stands alone


def test_clean_dir_reads_completed(tmp_path):
    obs = tmp_path / "obs"
    obs.mkdir()
    (obs / "metrics.jsonl").write_text(json.dumps({
        "kind": "metrics", "t": 1.0, "step": 10,
        "metrics": {"tmpi_mfu": 0.5}}) + "\n")
    rep = build_report(str(obs))
    assert rep["verdict"] == "completed"
    assert rep["evidence"] == [] and rep["incidents"] == []
    assert rep["steps"] == 10


def test_profile_report_only_dir_is_reportable(tmp_path, capsys):
    """A `tmpi profile` output dir (one ``report.json`` holding a
    ``profile_report`` record, no JSONL stream) is a valid `tmpi report`
    input: ``--json`` is schema-valid, says ``completed`` and prints the
    same bytes twice (nothing wall-clock-derived rides the body)."""
    (tmp_path / "report.json").write_text(json.dumps({
        "kind": "profile_report", "model": "mlp", "engine": "bsp",
        "codec": "none", "n_devices": 1, "device_kind": "cpu", "steps": 8,
        "mfu": 0.5, "mfu_source": "calibrated",
        "attribution": {"fractions": {"compute": 0.9, "comm": 0.0,
                                      "host": 0.1, "residual": 0.0}}}))
    assert report_main([str(tmp_path), "--json"]) == 0
    out = capsys.readouterr().out
    assert report_main([str(tmp_path), "--json"]) == 0
    assert capsys.readouterr().out == out
    rep = json.loads(out)
    assert rep["verdict"] == "completed" and rep["n_events"] == 0
    assert validate_record(rep) == []


def write_serving_dir(obs, with_drop=False):
    """A serving-fleet failure dir (ISSUE 19 satellite): a replica
    crash (t=10) re-homes two in-flight requests (t=11, t=12), the
    supervisor restarts the member (t=15) — plus a training-track retry
    (t=20) that must NOT adopt the serving records."""
    os.makedirs(obs, exist_ok=True)
    with open(os.path.join(obs, "router.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "router", "t": 10.0, "event": "health",
            "replica_id": 0, "from_state": "healthy", "to_state": "down",
            "error": "EngineDead('replica 0 killed')"}) + "\n")
        f.write(json.dumps({
            "kind": "router", "t": 11.0, "event": "failover",
            "replica_id": 0, "to_replica": 1,
            "error": "EngineDead('replica 0 killed')"}) + "\n")
        f.write(json.dumps({
            "kind": "router", "t": 12.0, "event": "failover",
            "replica_id": 0, "to_replica": 1,
            "error": "EngineDead('replica 0 killed')"}) + "\n")
        if with_drop:
            f.write(json.dumps({
                "kind": "router", "t": 13.0, "event": "drop",
                "replica_id": 0,
                "error": "RequestDropped('budget exhausted')"}) + "\n")
        f.write(json.dumps({
            "kind": "router", "t": 15.0, "event": "restart",
            "replica_id": 0, "from_state": "restarting",
            "to_state": "healthy", "backoff_s": 0.31}) + "\n")
    with open(os.path.join(obs, "supervisor.jsonl"), "w") as f:
        f.write(json.dumps({
            "kind": "retry", "rank": 0, "t": 20.0, "attempt": 1,
            "step": 8, "error": "InjectedCrash('boom')",
            "backoff_s": 0.5, "cause": "crash"}) + "\n")


def test_replica_restart_adopts_serving_chain_not_training(tmp_path):
    """ISSUE 19 satellite: serving incidents ride the causal timeline
    on their OWN track — the replica restart adopts the crash and both
    failovers with exact record citations, the later training retry
    adopts none of them, and a replica lost with zero drops reads
    DEGRADED (traffic absorbed), never halted."""
    obs = str(tmp_path / "obs")
    write_serving_dir(obs)
    rep = build_report(obs)

    assert rep["verdict"] == "degraded"
    restarts = [i for i in rep["incidents"]
                if i["kind"] == "replica_restart"]
    assert len(restarts) == 1
    inc = restarts[0]
    assert inc["src"] == "router.jsonl:4"
    assert "traffic absorbed by survivors" in inc["what"]
    assert [e["src"] for e in inc["evidence"]] == [
        "router.jsonl:1", "router.jsonl:2", "router.jsonl:3"]
    assert [e["kind"] for e in inc["evidence"]] == ["router"] * 3
    # the training retry stands alone: no serving record crossed tracks
    retries = [i for i in rep["incidents"] if i["kind"] == "retry"]
    assert len(retries) == 1 and retries[0]["evidence"] == []
    # markdown carries the serving story verbatim
    import io
    from contextlib import redirect_stdout

    buf = io.StringIO()
    with redirect_stdout(buf):
        assert report_main([obs]) == 0
    md = buf.getvalue()
    assert "Verdict: DEGRADED" in md
    assert "traffic absorbed by survivors" in md
    assert "re-admitted from replica 0 to replica 1" in md


def test_router_drop_forces_halted_verdict(tmp_path):
    """ANY dropped request is a halt-class violation of the serving
    contract — even though the fleet restarted and kept serving, the
    request is gone, so the verdict is halted and cites the drop."""
    obs = str(tmp_path / "obs")
    write_serving_dir(obs, with_drop=True)
    rep = build_report(obs)
    assert rep["verdict"] == "halted"
    assert any("router.jsonl:4" in ev and "DROPPED" in ev
               for ev in rep["evidence"])
