"""tools/lint_all.py + tools/lint.py: the one-command CI lint
(hot-loop + serve hot path + codec coverage + telemetry schemas + the
SPMD safety analyzer) — wired as a tier-1 test so the tree can never
merge with a train-loop host sync, a schema-drifting telemetry
emitter, or a collective-schedule change nobody reviewed."""

import json
import os
import time

from theanompi_tpu.tools.lint import RULES, main as lint_main, run_lint
from theanompi_tpu.tools.lint_all import main, telemetry_files


def test_lint_all_passes_on_the_tree():
    """The committed tree must be lint-clean: worker train loops free of
    host syncs, every committed telemetry JSONL schema-valid."""
    assert main([]) == 0


def test_full_lint_includes_analyzer_and_stays_in_budget():
    """`tmpi lint` runs the SPMD analyzer (golden signatures, traffic
    cross-check, donation audit, AST lints), the memory & precision
    pre-flight families (ISSUE 12 — every engine x codec x fused
    config lowered for XLA memory analysis), AND the sharding & layout
    analyzer (ISSUE 15 — the same executables' input_shardings +
    optimized-HLO collective set vs the ShardingRecipe declarations),
    and the whole pass stays tier-1-runnable under the 90 s CPU
    budget. Per-family wall time is recorded so a budget regression is
    attributable to the family that grew; the sharding family must ride
    the memory family's compiled executables (tools/analyze/lowering.py
    cache), so its marginal cost is parsing, not a second 20-config
    compile."""
    t0 = time.monotonic()
    report = run_lint()
    elapsed = time.monotonic() - t0
    assert report.ok, [f.as_json() for f in report.findings]
    assert elapsed < 90.0, f"tmpi lint took {elapsed:.1f}s"
    assert set(report.timings_s) >= {
        "hot_loop", "codec_coverage", "schema", "spmd", "memory",
        "precision", "concurrency", "sharding",
    }
    assert all(v >= 0 for v in report.timings_s.values())
    # the compiling families dominate; their time is attributed to
    # them, not smeared over the trace-only ones
    assert sum(report.timings_s.values()) <= elapsed + 1.0


def test_lint_json_report_shape(capsys):
    assert lint_main(["--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["ok"] is True
    assert out["counts"]["findings"] == 0
    # stable rule IDs ship with the report so CI can key on them
    assert "SPMD002" in out["rules"] and "HOT002" in out["rules"]
    assert "MEM002" in out["rules"] and "PREC003" in out["rules"]
    assert "RACE001" in out["rules"] and "RACE005" in out["rules"]
    assert "SHARD001" in out["rules"] and "SHARD101" in out["rules"]
    assert set(out["rules"]) == set(RULES)
    # per-rule-family wall time rides the CI report (ISSUE 12/14/15
    # satellite) so future budget regressions are attributable
    t = out["timings_s"]
    assert {"memory", "precision", "spmd", "concurrency",
            "sharding"} <= set(t)
    assert all(isinstance(v, (int, float)) for v in t.values())


def test_telemetry_discovery_skips_caches(tmp_path):
    (tmp_path / ".jax_cache").mkdir()
    (tmp_path / ".jax_cache" / "junk.jsonl").write_text("not json\n")
    (tmp_path / "run.jsonl").write_text(
        json.dumps({"kind": "train", "step": 1, "loss": 1.0}) + "\n"
    )
    (tmp_path / "heartbeat_rank0.json").write_text(
        json.dumps({"kind": "heartbeat", "rank": 0, "t": 1.0, "step": 1,
                    "pid": 42}) + "\n"
    )
    files = telemetry_files([str(tmp_path)])
    names = sorted(f.split("/")[-1] for f in files)
    assert names == ["heartbeat_rank0.json", "run.jsonl"]


def test_lint_all_fails_on_bad_telemetry(tmp_path):
    (tmp_path / "bad.jsonl").write_text(
        json.dumps({"kind": "train"}) + "\n"  # missing required step
    )
    assert main([str(tmp_path)]) == 1


def test_lint_all_ok_when_no_telemetry(tmp_path, capsys):
    assert main([str(tmp_path)]) == 0
    assert "no telemetry files" in capsys.readouterr().out
