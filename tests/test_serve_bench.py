"""Acceptance: ``python bench.py --serve-bench`` runs on
JAX_PLATFORMS=cpu and reports sustained throughput + p99 latency in the
standard snapshot schema; ``tmpi serve --selftest`` serves a real
checkpoint end-to-end from the CLI."""

import json
import os
import subprocess
import sys

import jax

from theanompi_tpu.tools.check_obs_schema import validate_record

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(cmd, timeout=600):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        cmd, cwd=REPO_ROOT, env=env, capture_output=True, text=True,
        timeout=timeout,
    )
    assert p.returncode == 0, f"{cmd} failed:\n{p.stderr[-3000:]}"
    return [l for l in p.stdout.strip().splitlines() if l.strip()]


def test_serve_bench_cpu_snapshot_schema():
    lines = _run([
        sys.executable, "bench.py", "--serve-bench",
        "--serve-duration", "0.6", "--serve-clients", "3",
        "--serve-buckets", "1,4",
    ])
    # driver contract: LAST line is the raw result object
    result = json.loads(lines[-1])
    assert result["metric"] == "serve_cifar10_requests_per_sec"
    assert result["unit"] == "requests/sec"
    assert result["value"] > 0
    assert result["p99_ms"] > 0 and result["p50_ms"] <= result["p99_ms"]
    assert 0 < result["batch_fill"] <= 1.0
    assert result["compiled_programs"] == 2  # one per bucket
    # satellite: the result ALSO rides the metrics-snapshot schema
    snapshot = json.loads(lines[-2])
    assert snapshot["kind"] == "metrics"
    assert validate_record(snapshot) == []
    assert snapshot["metrics"]["bench_p99_ms"] == result["p99_ms"]
    assert snapshot["metrics"]["bench_value"] == result["value"]


def test_cli_serve_selftest_roundtrip(tmp_path):
    """tmpi serve over a checkpoint this test saves: load -> AOT warm ->
    closed-loop selftest requests -> schema-valid serve stats line."""
    from theanompi_tpu.models.cifar10 import Cifar10_model
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    model = Cifar10_model()
    state = init_train_state(model, jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), state, 2, rng=jax.random.PRNGKey(1))

    obs = tmp_path / "obs"
    lines = _run([
        sys.executable, "-m", "theanompi_tpu.cli", "serve",
        "--ckpt-dir", str(tmp_path), "--model", "cifar10",
        "--buckets", "1,4", "--selftest", "5", "--obs-dir", str(obs),
    ])
    stats = json.loads(lines[-1])
    assert stats["params_step"] == 2
    assert stats["metrics"]["tmpi_serve_served_total"] == 5.0
    assert validate_record(stats) == []
    # the obs sink landed and validates too
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(obs / "serve.jsonl")) == []


def test_serve_fleet_bench_open_loop_with_midrun_kill():
    """ISSUE 19 acceptance: ``bench.py --serve-bench --replicas 2``
    runs the OPEN-LOOP load generator (Poisson arrivals) over a
    2-replica router, reports p50/p99/p999 + goodput, kills a replica
    mid-run, and the post-kill goodput recovers to within 10% of the
    pre-kill rate — with zero dropped requests and the failover/restart
    counters showing the fleet actually absorbed the loss."""
    lines = _run([
        sys.executable, "bench.py", "--serve-bench", "--replicas", "2",
        "--serve-duration", "3.0", "--serve-buckets", "1,8",
    ])
    result = json.loads(lines[-1])
    assert result["metric"] == "serve_fleet_goodput_rps_2r"
    assert result["replicas"] == 2
    assert result["serve_goodput_rps"] > 0
    assert (0 < result["serve_p50_ms"] <= result["serve_p99_ms"]
            <= result["serve_p999_ms"])
    # the mid-run replica kill was absorbed: traffic failed over, the
    # supervisor restarted the member, nothing was dropped, and the
    # tail window served >= 0.9x the pre-kill fraction of its offered
    # arrivals (a served-fraction ratio — immune to Poisson shot noise
    # and box slowdown, but tail rejects/drops/failures score against it)
    assert result["failovers"] >= 0 and result["restarts"] >= 1
    assert result["dropped"] == 0 and result["failed"] == 0
    assert result["recovery_ratio"] >= 0.9, result
    # overload probe: the fleet sheds load via rejects, not drops
    assert result["overload_rejected"] >= 0
    # snapshot schema (second-to-last line), perf_gate's input shape:
    # the gated serve_p99_ms / serve_goodput_rps gauges are extractable
    snapshot = json.loads(lines[-2])
    assert snapshot["kind"] == "metrics"
    assert validate_record(snapshot) == []
    assert snapshot["metrics"]["bench_serve_p99_ms"] == result["serve_p99_ms"]
    from theanompi_tpu.tools.perf_gate import extract_invariants

    inv = extract_invariants(snapshot)
    assert inv["serve_p99_ms"] == result["serve_p99_ms"]
    assert inv["serve_goodput_rps"] == result["serve_goodput_rps"]


def test_serve_fleet_baseline_gates(tmp_path):
    """The committed experiments/serve_bench/baseline.json is a usable
    perf_gate baseline: gating it against itself passes, and a 2x p99
    regression (the drift the gate exists to catch) fails."""
    from theanompi_tpu.tools.perf_gate import main as gate_main

    base = os.path.join(REPO_ROOT, "experiments", "serve_bench",
                        "baseline.json")
    assert gate_main([base, base]) == 0
    snap = json.loads(open(base).read())
    snap["metrics"]["bench_serve_p99_ms"] *= 2.0
    cur = tmp_path / "regressed.json"
    cur.write_text(json.dumps(snap))
    assert gate_main([base, str(cur)]) == 1
