"""Chaos campaign runner acceptance (tools/chaos.py, `tmpi chaos`).

Three contracts: (1) the tier-1 smoke campaign — fuzzed schedules over
the storage-inclusive smoke matrix — completes with zero invariant
violations inside its CI budget, with wall time attributed like lint's
timings_s; (2) a deliberately seeded recovery bug (--mutate refeed: one
re-fed batch on mid-epoch resume) is CAUGHT by the invariant oracle and
SHRUNK to a <=2-fault repro — the proof the oracle is alive; (3) the
headline storage-hardening path: a bitrot flip on the newest committed
checkpoint is quarantined by the scrubber and the supervised resume
lands on the prior verified step at parity with an uninterrupted
baseline."""

import json
import os
import subprocess
import sys
import time

import pytest

from theanompi_tpu.tools.chaos import (
    BaselineCache,
    ChaosConfig,
    MATRIX,
    check_invariants,
    generate_schedule,
    run_schedule,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# CI smoke budget (satellite): `tmpi chaos --smoke --seeds 5` — a cold
# subprocess (fresh jax import + compiles, warm persistent cache) must
# land well inside this
SMOKE_BUDGET_S = 120.0


def test_smoke_campaign_zero_violations_under_budget(tmp_path):
    """The tier-1 acceptance: 5 fuzzed seeds over the smoke matrix
    (crash/ckpt_truncate/enospc/bitrot — storage kinds included), CPU,
    small MLP/BSP, in a real subprocess, zero invariant violations,
    under the 120 s budget, wall time reported in timings_s."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    out = tmp_path / "campaign"
    t0 = time.monotonic()
    p = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", "chaos",
         "--smoke", "--seeds", "5", "--out", str(out)],
        env=env, capture_output=True, text=True,
        timeout=SMOKE_BUDGET_S + 60, cwd=_REPO,
    )
    wall = time.monotonic() - t0
    assert p.returncode == 0, (p.stdout[-2000:], p.stderr[-2000:])
    assert wall < SMOKE_BUDGET_S, f"smoke campaign took {wall:.1f}s"
    report = json.loads((out / "report.json").read_text())
    assert report["schedules"] == 5 and report["violated"] == 0
    # wall attribution, lint-style: the budget is enforceable per phase
    assert set(report["timings_s"]) >= {"baseline", "runs", "shrink",
                                        "total"}
    assert report["timings_s"]["total"] > 0
    # every chaos record validates against the documented schema
    from theanompi_tpu.tools.check_obs_schema import check_file

    log = out / "chaos.jsonl"
    assert log.exists() and check_file(str(log)) == []
    recs = [json.loads(l) for l in log.read_text().splitlines()]
    assert len(recs) == 5 and all(r["ok"] for r in recs)
    # the storage kinds are actually in the fuzzed pool (seeded: the
    # same 5 seeds always draw the same schedules)
    drawn = {k.partition("@")[0]
             for r in recs for k in r["schedule"].split("+")}
    assert drawn & {"enospc", "bitrot", "ckpt_truncate"}


def test_generate_schedule_seeded_and_constrained():
    import random

    cfg = ChaosConfig("bsp_none")
    kinds = list(MATRIX)
    a = generate_schedule(random.Random(7), cfg, kinds, 3)
    b = generate_schedule(random.Random(7), cfg, kinds, 3)
    assert a == b  # seeded: same seed, same schedule
    # constraints over many draws: steps in range, rollback kinds past
    # the first save boundary, at most one sigkill
    for seed in range(50):
        sched = generate_schedule(random.Random(seed), cfg, kinds, 3)
        assert 1 <= len(sched) <= 3
        kills = 0
        for spec in sched:
            kind, _, rest = spec.partition("@")
            step = int(rest.partition(":")[0])
            assert 1 <= step <= cfg.total_steps
            if MATRIX[kind].get("rollback"):
                assert step > cfg.steps_per_epoch
            kills += kind == "sigkill"
        assert kills <= 1


@pytest.mark.slow
def test_mutation_is_caught_and_shrunk(tmp_path):
    """Acceptance: the seeded oracle-mutation (a re-fed batch via
    disabled skip accounting on resume, TMPI_CHAOS_MUTATE=refeed) is
    caught by the invariant oracle and shrunk to a <=2-fault repro,
    while the SAME schedule without the mutation is absorbed clean —
    the oracle detects the bug, not the faults."""
    from theanompi_tpu.tools.chaos import chaos_main

    out_bad = tmp_path / "mutated"
    rc = chaos_main(["--schedule", "crash@5", "--mutate", "refeed",
                     "--out", str(out_bad)])
    assert rc == 1
    report = json.loads((out_bad / "report.json").read_text())
    assert report["violated"] == 1
    rec = report["results"][0]
    assert not rec["ok"] and rec["violations"]
    assert "parity" in rec["violations"] or "completed" in rec["violations"]
    minimal = rec["shrunk_schedule"].split("+")
    assert 1 <= len(minimal) <= 2
    assert rec["repro"].startswith("--inject-fault ")

    out_ok = tmp_path / "clean"
    rc = chaos_main(["--schedule", "crash@5", "--out", str(out_ok)])
    assert rc == 0
    report = json.loads((out_ok / "report.json").read_text())
    assert report["violated"] == 0


@pytest.mark.slow
def test_bitrot_quarantined_and_resume_lands_on_prior_verified(tmp_path):
    """Acceptance: a bitrot@K flip on the newest committed checkpoint
    is quarantined (supervisor retry-time scrub -> quarantine/) and the
    supervised resume lands on the PRIOR verified step, finishing at
    parity with an uninterrupted baseline."""
    # 3 epochs x 3 steps: saves at 3/6/9; bitrot@6 flips ckpt_6 the
    # moment it lands, crash@7 kills the attempt with no newer save —
    # the retry must scrub ckpt_6 into quarantine and resume from 3
    cfg = ChaosConfig("bsp_none", n_epochs=3)
    schedule = ["bitrot@6", "crash@7"]
    wd = tmp_path / "run"
    res = run_schedule(cfg, schedule, str(wd))
    baseline = BaselineCache(str(tmp_path / "base"))
    assert check_invariants(cfg, schedule, res, baseline) == []

    # the flipped file was quarantined, not deleted; the replay then
    # re-saved a CLEAN ckpt_6 at the same boundary — both must verify
    # as what they are
    from theanompi_tpu.utils.checkpoint import verify_checkpoint

    qdir = os.path.join(res.ckpt_dir, "quarantine")
    assert os.path.isdir(qdir) and "ckpt_6.npz" in os.listdir(qdir)
    assert not verify_checkpoint(os.path.join(qdir, "ckpt_6.npz"))
    replayed = os.path.join(res.ckpt_dir, "ckpt_6.npz")
    assert os.path.exists(replayed) and verify_checkpoint(replayed)

    # the retry resumed from the PRIOR verified step (3, not 6)
    recs = [json.loads(l) for l in
            open(os.path.join(res.obs_dir, "supervisor.jsonl"))]
    retry = [r for r in recs if r["kind"] == "retry"]
    assert retry and retry[0]["step"] == 3
    assert retry[0]["cause"] == "crash"

    # ... and the scrub that made the walk-back O(1) was recorded
    mrecs = [json.loads(l) for l in
             open(os.path.join(res.obs_dir, "metrics.jsonl"))]
    scrubs = [r for r in mrecs if r.get("kind") == "scrub"]
    assert scrubs and "ckpt_6.npz" in scrubs[0]["quarantined"]
    from theanompi_tpu.tools.check_obs_schema import validate_record

    assert all(validate_record(r) == [] for r in scrubs)


@pytest.mark.slow
def test_partial_set_dropped_member_reads_absent(tmp_path):
    """partial_set on a sharded config: the torn set reads as absent
    (completeness-by-counting) and the supervised run still ends at
    parity — the sharded-format counterpart of the bitrot path."""
    cfg = ChaosConfig("zero1_none", zero=1, sharded_ckpt=True)
    schedule = ["partial_set@3", "crash@4"]
    wd = tmp_path / "run"
    res = run_schedule(cfg, schedule, str(wd))
    baseline = BaselineCache(str(tmp_path / "base"))
    assert check_invariants(cfg, schedule, res, baseline) == []
    retry = [json.loads(l) for l in
             open(os.path.join(res.obs_dir, "supervisor.jsonl"))
             if json.loads(l)["kind"] == "retry"]
    # the step-3 set lost its only member -> absent -> the retry had
    # nothing verified to resume from (crash-save path may still have
    # provided a mid-epoch anchor; either way parity held above)
    assert retry


def test_slice_down_absorbed_by_elastic_reshard(tmp_path):
    """Directed smoke for the topology fault (hierarchical-collectives
    PR): a whole-slice loss mid-run (slice_down@4 on a 2x2 multislice
    mesh) is absorbed by the elastic supervisor — the survivors' world
    (2 chips, 1 slice) resumes from the last committed checkpoint and
    finishes every step with zero invariant violations."""
    cfg = ChaosConfig("bsp_none")
    schedule = ["slice_down@4"]
    res = run_schedule(cfg, schedule, str(tmp_path / "run"))
    assert res.launches == ["ok"]
    assert res.final_summary and res.final_summary["steps"] == cfg.total_steps
    baseline = BaselineCache(str(tmp_path / "base"))
    assert check_invariants(cfg, schedule, res, baseline) == []


# --------------------------------------------------------------------------
# serving-path chaos (`tmpi chaos --serve`, ISSUE 19): the fuzzed fault
# matrix over a replica fleet under live load, the serving invariant
# oracle, and the seeded drop_inflight mutation self-test
# --------------------------------------------------------------------------


def test_generate_serve_schedule_seeded_and_constrained():
    import random

    from theanompi_tpu.tools.chaos import (
        SERVE_MATRIX,
        generate_serve_schedule,
        parse_serve_spec,
    )

    a = generate_serve_schedule(random.Random(7), 2.0, 2)
    b = generate_serve_schedule(random.Random(7), 2.0, 2)
    assert a == b  # seeded: same seed, same schedule
    for seed in range(50):
        sched = generate_serve_schedule(random.Random(seed), 2.0, 2)
        assert 1 <= len(sched) <= 2
        for spec in sched:
            kind, t, arg = parse_serve_spec(spec)
            assert kind in SERVE_MATRIX
            assert 0.0 < t <= 0.8 * 2.0  # inside the load window
            if SERVE_MATRIX[kind].get("arg") is not None:
                assert arg > 0
    with pytest.raises(ValueError, match="must be KIND@T"):
        parse_serve_spec("crash@3")  # training kinds don't parse here


def test_serve_directed_crash_absorbed(tmp_path):
    """Directed acceptance: a replica crash under live client load —
    composed with the always-on hot-reload — is fully absorbed: zero
    drops, monotone served steps, a clean drain, and a failover plus a
    supervised restart on the router's own counters."""
    from theanompi_tpu.tools.chaos import (
        check_serve_invariants,
        run_serve_schedule,
    )

    schedule = ["replica_crash@0.3"]
    # the default 2.0 s window: long enough that the mid-window
    # checkpoint commit reliably lands a hot-reload under this load
    res = run_serve_schedule(schedule, str(tmp_path), replicas=2,
                             duration=2.0, clients=3, seed=1)
    assert check_serve_invariants(schedule, res) == []
    assert res.router_stats["tmpi_router_dropped_total"] == 0.0
    assert res.router_stats["tmpi_router_restarts_total"] >= 1.0
    # hot-reload-under-load rode the schedule: the served step advanced
    steps = [e["step"] for ledger in res.ledgers for e in ledger
             if e["status"] == "served"]
    assert steps and max(steps) > min(steps)


def test_serve_mutation_drop_inflight_caught_and_shrunk(tmp_path):
    """The serving oracle's self-test: with the seeded drop_inflight
    mutation (the failover path drops the dying replica's in-flight
    request instead of re-admitting it) the no_drops invariant fires,
    and delta-debugging shrinks a 2-fault schedule to the single crash
    that triggers it — while the same schedule unmutated is absorbed
    (proved by test_serve_directed_crash_absorbed)."""
    from theanompi_tpu.tools.chaos import (
        check_serve_invariants,
        run_serve_schedule,
        shrink_serve_schedule,
    )

    # the stall parks in-flight work on one member (its batch sleeps
    # 0.45 s from t=0.2 while the closed-loop clients queue behind it)
    # and the crash at 0.4 targets the busiest healthy replica — so the
    # victim PROVABLY holds in-flight requests at kill time and the
    # mutation cannot dodge the oracle by scheduling luck, even on a
    # loaded box
    schedule = ["replica_stall@0.2:0.45", "replica_crash@0.4"]
    res = run_serve_schedule(schedule, str(tmp_path / "bad"),
                             replicas=2, duration=1.2, clients=3,
                             mutate="drop_inflight", seed=1)
    viol = check_serve_invariants(schedule, res)
    assert "no_drops" in viol, viol
    minimal, runs = shrink_serve_schedule(
        schedule, str(tmp_path / "shrink"), replicas=2, duration=1.2,
        clients=3, mutate="drop_inflight", seed=1, max_runs=6)
    # the crash is the trigger and always survives the shrink; whether
    # the stall is ALSO needed to reproduce depends on load timing, so
    # the minimal schedule is the crash alone or the pair — never empty
    # (the greedy shrinker only drops a fault after re-running the
    # remainder and seeing the violation again, so `minimal` is a
    # validated repro by construction)
    assert "replica_crash@0.4" in minimal
    assert len(minimal) <= 2
    assert runs >= 1


# --------------------------------------------------------------------------
# decode-fleet chaos (`tmpi chaos --serve --decode`, ISSUE 20): the
# DECODE_MATRIX generator, the directed kv_exhaust + long_prompt_burst
# composition over continuous-batching engines, and the kv_conserved
# oracle's self-test
# --------------------------------------------------------------------------


def test_generate_decode_schedule_uses_decode_matrix():
    import random

    from theanompi_tpu.tools.chaos import (
        DECODE_MATRIX,
        generate_serve_schedule,
        parse_serve_spec,
    )

    a = generate_serve_schedule(random.Random(7), 2.0, 2, DECODE_MATRIX)
    assert a == generate_serve_schedule(random.Random(7), 2.0, 2,
                                        DECODE_MATRIX)
    drawn: set = set()
    for seed in range(50):
        for spec in generate_serve_schedule(random.Random(seed), 2.0, 2,
                                            DECODE_MATRIX):
            kind, t, arg = parse_serve_spec(spec, DECODE_MATRIX)
            assert kind in DECODE_MATRIX
            assert 0.0 < t <= 0.8 * 2.0
            drawn.add(kind)
    # 50 seeds reliably draw the decode-only kinds at least once
    assert {"kv_exhaust", "long_prompt_burst"} <= drawn
    # default hold rides the matrix: kv_exhaust grabs pages for 0.5 s
    assert parse_serve_spec("kv_exhaust@0.4", DECODE_MATRIX)[2] == 0.5
    # decode-only kinds don't parse against the eval-serving matrix...
    with pytest.raises(ValueError, match="must be KIND@T"):
        parse_serve_spec("kv_exhaust@0.4")
    # ...and slow_replica is deliberately absent from the decode one
    with pytest.raises(ValueError, match="must be KIND@T"):
        parse_serve_spec("slow_replica@0.4:0.05", DECODE_MATRIX)


def test_decode_directed_kv_exhaust_and_burst_absorbed(tmp_path):
    """Directed acceptance for the decode fleet: KV-page exhaustion on
    one member composed with a worst-case long-prompt burst and the
    always-on hot-reload-mid-generation — absorbed with zero drops,
    generated tokens still flowing, and every member's KV free-list
    conserved after drain. Flipping the conservation bit proves the
    kv_conserved oracle actually fires (self-test)."""
    from theanompi_tpu.tools.chaos import (
        check_serve_invariants,
        run_serve_schedule,
    )

    schedule = ["kv_exhaust@0.3:0.4", "long_prompt_burst@0.5"]
    res = run_serve_schedule(schedule, str(tmp_path), replicas=2,
                             duration=1.5, clients=3, seed=1,
                             decode=True)
    assert check_serve_invariants(schedule, res) == []
    assert res.kv_conserved is True
    assert res.router_stats["tmpi_router_dropped_total"] == 0.0
    served = [e for ledger in res.ledgers for e in ledger
              if e["status"] == "served"]
    assert served
    # a leaked KV page (pages_out != pages_in after drain) must be a
    # violation, not a shrug — the page-table equivalent of no_drops
    res.kv_conserved = False
    assert "kv_conserved" in check_serve_invariants(schedule, res)
