"""Fault-tolerant run supervisor acceptance tests
(launch/supervisor.py + the worker's recovery paths).

The headline contract: an injected crash at step k under the supervisor
resumes from the newest VERIFIED checkpoint and finishes with params
BIT-IDENTICAL to an uninterrupted run at the same total step count —
for in-process crashes, for a SIGKILL'd subprocess (both checkpoint
formats), and through a truncated-newest-checkpoint walk-back."""

import json
import os
import signal
import subprocess
import sys

import jax
import numpy as np
import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.supervisor import supervise_training
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.utils.checkpoint import (
    checkpoint_step,
    latest_checkpoint,
    load_checkpoint,
    read_resumable_marker,
)
from theanompi_tpu.utils.faults import Preempted

_TINYMODEL_PY = os.path.join(os.path.dirname(__file__), "tinymodel.py")

_TINY = dict(
    rule="bsp",
    model_cls=TinyCNN,
    devices=8,
    recipe_overrides={"batch_size": 32, "input_shape": (16, 16, 3),
                      "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]}},
    dataset="synthetic",
    dataset_kwargs={"n_train": 64, "n_val": 32, "image_shape": (16, 16, 3)},
    print_freq=0,
    n_epochs=2,  # 2 steps/epoch -> 4 total steps
)


def _final_params(ckpt_dir):
    """Leaves of the newest verified checkpoint in ``ckpt_dir``."""
    path = latest_checkpoint(ckpt_dir, verify=True)
    assert path is not None, f"no verified checkpoint in {ckpt_dir}"
    model = TinyCNN(TinyCNN.default_recipe().replace(
        batch_size=32, input_shape=(16, 16, 3)))
    from theanompi_tpu.train import init_train_state

    template = init_train_state(model, jax.random.PRNGKey(0))
    restored, _ = load_checkpoint(path, template)
    return path, jax.tree_util.tree_leaves(restored)


def _assert_bit_identical(dir_a, dir_b):
    pa, la = _final_params(dir_a)
    pb, lb = _final_params(dir_b)
    assert checkpoint_step(pa) == checkpoint_step(pb)
    for a, b in zip(la, lb):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_supervisor_crash_resume_bit_identical(tmp_path):
    """Acceptance: injected crash at step k with max_retries 2 resumes
    and finishes with params bit-identical to an uninterrupted run."""
    clean = run_training(ckpt_dir=str(tmp_path / "clean"), **_TINY)
    sup = supervise_training(
        ckpt_dir=str(tmp_path / "sup"), obs_dir=str(tmp_path / "obs"),
        max_retries=2, backoff_base=0.0,
        inject_faults=["crash@3"], **_TINY,
    )
    assert sup["retries"] == 1 and sup["attempts"] == 2
    assert sup["steps"] == clean["steps"] == 4
    _assert_bit_identical(str(tmp_path / "clean"), str(tmp_path / "sup"))
    # per-attempt retry record + final snapshot, schema-valid
    from theanompi_tpu.tools.check_obs_schema import check_file

    sup_log = tmp_path / "obs" / "supervisor.jsonl"
    recs = [json.loads(l) for l in sup_log.read_text().splitlines()]
    assert [r["kind"] for r in recs] == ["retry"]
    assert recs[0]["attempt"] == 1 and recs[0]["error"].startswith("InjectedCrash")
    assert check_file(str(sup_log)) == []
    snaps = [json.loads(l)
             for l in (tmp_path / "obs" / "metrics.jsonl").read_text().splitlines()]
    assert snaps[-1]["source"] == "supervisor"
    assert snaps[-1]["metrics"]["tmpi_retries_total"] == 1.0


def test_supervisor_walks_back_past_truncated_checkpoint(tmp_path):
    """Acceptance: a truncated newest checkpoint is skipped for the
    previous verified one. Chain: epoch saves land at steps 2/4/6; the
    ckpt_truncate fault tears the step-4 file the moment it lands, the
    crash fires before step 5 — at that point step_count == 4 ==
    last_ckpt_step, so NO crash-path save re-covers step 4, and the
    retry MUST walk the keep-chain back to the verified step-2 file,
    then replay to a bit-identical finish."""
    clean = run_training(ckpt_dir=str(tmp_path / "clean"), n_epochs=3,
                         **{k: v for k, v in _TINY.items() if k != "n_epochs"})
    sup_dir = tmp_path / "sup"
    sup = supervise_training(
        ckpt_dir=str(sup_dir), obs_dir=str(tmp_path / "obs"),
        max_retries=2, backoff_base=0.0, n_epochs=3,
        inject_faults=["ckpt_truncate@4", "crash@5"],
        **{k: v for k, v in _TINY.items() if k != "n_epochs"},
    )
    assert sup["retries"] == 1
    assert sup["steps"] == clean["steps"] == 6
    _assert_bit_identical(str(tmp_path / "clean"), str(sup_dir))
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "supervisor.jsonl").read_text().splitlines()]
    assert recs[0]["kind"] == "retry"
    assert recs[0]["step"] == 2  # resumed from the VERIFIED step, not 4


def test_supervisor_elastic_same_mesh_resume_bit_identical(tmp_path):
    """Elastic mode must cost NOTHING when the topology does not
    change: a crash-retry under elastic=True on the same world loads
    the plain (non-reshard) path and stays bit-identical to an
    uninterrupted run — while supervisor.jsonl gains the topology
    records and the world-stamped retry."""
    clean = run_training(ckpt_dir=str(tmp_path / "clean"), **_TINY)
    sup = supervise_training(
        ckpt_dir=str(tmp_path / "sup"), obs_dir=str(tmp_path / "obs"),
        max_retries=2, backoff_base=0.0, elastic=True,
        inject_faults=["crash@3"], **_TINY,
    )
    assert sup["retries"] == 1 and sup["steps"] == clean["steps"] == 4
    assert "resharded_from_world" not in sup  # same mesh: no reshard
    _assert_bit_identical(str(tmp_path / "clean"), str(tmp_path / "sup"))
    from theanompi_tpu.tools.check_obs_schema import check_file

    sup_log = tmp_path / "obs" / "supervisor.jsonl"
    assert check_file(str(sup_log)) == []
    recs = [json.loads(l) for l in sup_log.read_text().splitlines()]
    topo = [r for r in recs if r["kind"] == "topology"]
    assert [t["world"] for t in topo] == [8, 8]  # one per attempt
    retry = [r for r in recs if r["kind"] == "retry"]
    assert retry[0]["world"] == 8
    # no reshard record: the same-mesh load is the bit-identical path
    mlog = tmp_path / "obs" / "metrics.jsonl"
    assert not any(
        json.loads(l).get("kind") == "reshard"
        for l in mlog.read_text().splitlines()
    )


def test_supervisor_exhausts_retries_and_raises(tmp_path):
    from theanompi_tpu.utils.faults import InjectedCrash

    with pytest.raises(InjectedCrash):
        supervise_training(
            ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
            max_retries=1, backoff_base=0.0,
            inject_faults=["crash@2", "crash@3"], **_TINY,
        )
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "supervisor.jsonl").read_text().splitlines()]
    assert len(recs) == 2  # one per failed attempt, incl. the last


def test_supervisor_requires_ckpt_dir():
    with pytest.raises(ValueError, match="requires ckpt_dir"):
        supervise_training(max_retries=1, **_TINY)


def test_supervisor_does_not_retry_halt(tmp_path):
    """--on-anomaly halt is a deliberate stop; the supervisor must not
    override it with a retry."""
    from theanompi_tpu.obs.numerics import NumericsAnomaly

    with pytest.raises(NumericsAnomaly):
        supervise_training(
            ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
            max_retries=3, backoff_base=0.0,
            numerics_freq=1, on_anomaly="halt",
            inject_faults=["nan_batch@3"], **_TINY,
        )
    assert not (tmp_path / "obs" / "supervisor.jsonl").exists()


def test_sigterm_grace_checkpoints_and_marks_resumable(tmp_path):
    """SIGTERM inside the grace window: checkpoint at the current step,
    drop the resumable marker, exit via Preempted; the NEXT supervisor
    invocation auto-resumes from the marker without resume=True."""
    ck = str(tmp_path / "ck")
    with pytest.raises(Preempted):
        supervise_training(
            ckpt_dir=ck, obs_dir=str(tmp_path / "obs"),
            max_retries=2, backoff_base=0.0, sigterm_grace=5.0,
            inject_faults=["sigterm@3"], **_TINY,
        )
    marker = read_resumable_marker(ck)
    assert marker and marker["reason"] == "sigterm"
    assert checkpoint_step(latest_checkpoint(ck, verify=True)) == marker["step"]
    # preempted attempt logged as resumable, backoff 0
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "supervisor.jsonl").read_text().splitlines()]
    assert recs[-1]["resumable"] is True and recs[-1]["backoff_s"] == 0.0
    # default SIGTERM disposition restored after the run
    assert signal.getsignal(signal.SIGTERM) in (
        signal.SIG_DFL, signal.default_int_handler)

    # an UNSUPERVISED resume must also consume the marker on success,
    # or a later supervised run would silently flip into resume mode
    # off the stale marker (review finding) — prove it on a copy
    import shutil

    ck2 = str(tmp_path / "ck2")
    shutil.copytree(ck, ck2)
    out_plain = run_training(ckpt_dir=ck2, resume=True, **_TINY)
    assert out_plain["steps"] == 4
    assert read_resumable_marker(ck2) is None

    out = supervise_training(ckpt_dir=ck, obs_dir=str(tmp_path / "obs"),
                             max_retries=2, backoff_base=0.0, **_TINY)
    assert out["preempt_resumes"] == 1
    assert out["steps"] == 4
    assert read_resumable_marker(ck) is None  # consumed on success
    # bit-identical to an uninterrupted run
    clean = run_training(ckpt_dir=str(tmp_path / "clean"), **_TINY)
    assert clean["steps"] == 4
    _assert_bit_identical(str(tmp_path / "clean"), ck)


def test_preemption_flush_anomaly_keeps_quarantine(tmp_path):
    """REGRESSION (review finding): with dispatch_depth>1 a NaN step's
    row can still be in flight when SIGTERM lands. The preemption
    handler's flush then makes the FIRST detection of the anomaly — the
    live state is poisoned, and the grace path must NOT persist it as
    the newest resumable checkpoint (it would pass CRC verification and
    poison every future resume). Timing is deterministic: sigterm@3
    fires before step 3 dispatches, nan_batch@3 poisons it, depth=2
    keeps its row undrained until the handler's flush."""
    import numpy as np

    with pytest.raises(Preempted):
        run_training(
            ckpt_dir=str(tmp_path / "ck"), dispatch_depth=2,
            numerics_freq=1, on_anomaly="halt", sigterm_grace=5.0,
            inject_faults=["sigterm@3", "nan_batch@3"], **_TINY,
        )
    # newest checkpoint is the PRE-anomaly epoch boundary, not step 3
    path = latest_checkpoint(str(tmp_path / "ck"), verify=True)
    assert checkpoint_step(path) == 2
    _, leaves = _final_params(str(tmp_path / "ck"))
    assert all(np.isfinite(np.asarray(l)).all() for l in leaves)
    # still marked resumable — from the last GOOD step
    marker = read_resumable_marker(str(tmp_path / "ck"))
    assert marker and marker["step"] == 2


def _tmpi_subprocess(args, allow_kill=False):
    """Run the tmpi CLI in a real subprocess on the 8-device virtual CPU
    platform (warm compile cache inherited from the session)."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    p = subprocess.run(
        [sys.executable, "-m", "theanompi_tpu.cli", *args],
        env=env, capture_output=True, text=True, timeout=420,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    if not allow_kill and p.returncode != 0:
        raise AssertionError(
            f"tmpi {args} rc={p.returncode}\n{p.stdout[-2000:]}\n{p.stderr[-2000:]}"
        )
    return p


@pytest.mark.parametrize("fmt", ["single", "sharded"])
def test_kill_and_resume_subprocess(tmp_path, fmt):
    """Acceptance (satellite): a subprocess run SIGKILL'd at injected
    step k — no finally, no grace — resumes under the supervisor and
    finishes with params bit-identical to an uninterrupted run, for
    both the single-file and --ckpt-sharded formats."""
    sharded = fmt == "sharded"
    base_args = [
        "BSP", "8", _TINYMODEL_PY, "TinyCNN",
        "--synthetic", "--epochs", "2", "--batch-size", "32",
        "--print-freq", "0",
        # sync checkpoints: the epoch-1 save must be DURABLE before the
        # SIGKILL lands (an async save still on the writer thread dies
        # with the process — exactly the loss mode reality has, but the
        # test needs a deterministic resume point)
        "--sync-ckpt",
        "--dataset-arg", "n_train=64", "--dataset-arg", "n_val=32",
        "--dataset-arg", "image_shape=[16,16,3]",
        "--recipe-arg", "input_shape=[16,16,3]",
        "--recipe-arg", 'sched_kwargs={"lr":0.05,"boundaries":[1000000000]}',
    ] + (["--ckpt-sharded"] if sharded else [])
    ck = str(tmp_path / "ck")
    p = _tmpi_subprocess(
        base_args + ["--ckpt-dir", ck, "--inject-fault", "sigkill@3"],
        allow_kill=True,
    )
    assert p.returncode == -signal.SIGKILL, (p.returncode, p.stderr[-800:])
    # the epoch-1 boundary checkpoint (step 2) survived the kill
    assert checkpoint_step(latest_checkpoint(ck, verify=True)) == 2
    # supervisor resumes (in-process: the checkpoint chain is just files)
    out = supervise_training(
        ckpt_dir=ck, max_retries=1, backoff_base=0.0, resume=True,
        sharded_ckpt=sharded, **_TINY,
    )
    assert out["resumed_from_step"] == 2 and out["steps"] == 4
    clean = run_training(ckpt_dir=str(tmp_path / "clean"),
                         sharded_ckpt=sharded, **_TINY)
    assert clean["steps"] == 4
    _assert_bit_identical(str(tmp_path / "clean"), ck)


def test_loader_stall_fault_trips_watchdog(tmp_path):
    """loader_stall@k:secs freezes step progress long enough for the
    stall watchdog to fire its report, and the run still completes."""
    out = run_training(
        ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
        stall_timeout=0.4, inject_faults=["loader_stall@3:1.2"], **_TINY,
    )
    assert out["steps"] == 4  # the stall is a pause, not a failure
    report = tmp_path / "obs" / "stall_rank0.json"
    assert report.exists()
    rec = json.loads(report.read_text())
    assert rec["kind"] == "stall" and rec["stall_s"] >= 0.4


# -- chaos-PR satellites: jitter, cause labels, ENOSPC sharded walk-back ---


def test_retry_jitter_deterministic_and_recorded(tmp_path):
    """--retry-jitter: the decorrelated-jitter backoff actually slept
    is recorded in the retry record, stays within [base, cap], and is
    DETERMINISTIC under the run's seed — two identical supervised runs
    draw the identical schedule (reproducibility), while a different
    seed de-phases (the anti-stampede property)."""

    def jittered_backoffs(root, seed):
        supervise_training(
            ckpt_dir=str(root / "ck"), obs_dir=str(root / "obs"),
            max_retries=2, backoff_base=0.01, retry_jitter=True,
            inject_faults=["crash@2", "crash@3"], seed=seed, **{
                k: v for k, v in _TINY.items() if k != "seed"},
        )
        recs = [json.loads(l) for l in
                (root / "obs" / "supervisor.jsonl").read_text().splitlines()]
        return [r["backoff_s"] for r in recs if r["kind"] == "retry"]

    a = jittered_backoffs(tmp_path / "a", seed=0)
    b = jittered_backoffs(tmp_path / "b", seed=0)
    c = jittered_backoffs(tmp_path / "c", seed=1)
    assert len(a) == 2
    assert a == b                     # seeded: reproducible schedule
    assert a != c                     # distinct seeds de-phase
    assert all(0.01 <= x <= 60.0 for x in a)


def test_retry_cause_classification_and_labels(tmp_path):
    """Retry records carry a cause label derived from the exception,
    and the final snapshot exports per-cause tmpi_retries_total series
    — crash for worker exceptions, storage for OSErrors (an injected
    ENOSPC on a SYNC save kills the attempt with the real OSError)."""
    sup = supervise_training(
        ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
        max_retries=3, backoff_base=0.0, async_checkpoint=False,
        inject_faults=["enospc@2", "crash@3"], **_TINY,
    )
    assert sup["steps"] == 4
    assert sup["retry_causes"] == {"storage": 1, "crash": 1}
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "supervisor.jsonl").read_text().splitlines()]
    causes = [r["cause"] for r in recs if r["kind"] == "retry"]
    assert causes == ["storage", "crash"]
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "obs" / "supervisor.jsonl")) == []
    snaps = [json.loads(l) for l in
             (tmp_path / "obs" / "metrics.jsonl").read_text().splitlines()
             if json.loads(l).get("source") == "supervisor"]
    m = snaps[-1]["metrics"]
    assert m["tmpi_retries_total"] == 2.0
    assert m['tmpi_retries_total{cause="storage"}'] == 1.0
    assert m['tmpi_retries_total{cause="crash"}'] == 1.0


def test_classify_retry_cause_mapping():
    from theanompi_tpu.launch.supervisor import classify_retry_cause
    from theanompi_tpu.obs.numerics import NumericsAnomaly
    from theanompi_tpu.utils.faults import InjectedCrash, TopologyChanged

    assert classify_retry_cause(Preempted(3)) == "preempt"
    assert classify_retry_cause(TopologyChanged("shrink", 2, 2)) == "topology"
    assert classify_retry_cause(OSError(28, "enospc")) == "storage"
    assert classify_retry_cause(NumericsAnomaly("x")) == "anomaly"
    assert classify_retry_cause(InjectedCrash("x")) == "crash"
    assert classify_retry_cause(RuntimeError("x")) == "crash"


def test_enospc_async_sharded_save_supervisor_resumes_prior_step(tmp_path):
    """Satellite acceptance: ENOSPC tears an async SHARDED save — the
    torn set reads as absent, latest_checkpoint(verify=True) walks back
    cleanly, and the supervised resume lands on the prior step,
    finishing bit-identical to an uninterrupted run. 3 epochs: saves at
    2/4/6; enospc@3 tears the step-4 set mid-write (the swallow keeps
    the attempt alive), crash@5 kills the attempt — the retry must
    resume from step 2."""
    tiny3 = {**{k: v for k, v in _TINY.items() if k != "n_epochs"},
             "n_epochs": 3}
    clean = run_training(ckpt_dir=str(tmp_path / "clean"),
                         sharded_ckpt=True, **tiny3)
    sup = supervise_training(
        ckpt_dir=str(tmp_path / "sup"), obs_dir=str(tmp_path / "obs"),
        max_retries=2, backoff_base=0.0, sharded_ckpt=True,
        inject_faults=["enospc@3", "crash@5"], **tiny3,
    )
    assert sup["retries"] == 1
    assert sup["steps"] == clean["steps"] == 6
    # the torn step-4 set never landed: nothing between 2 and 6
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "supervisor.jsonl").read_text().splitlines()]
    retry = [r for r in recs if r["kind"] == "retry"]
    assert retry[0]["step"] == 2 and retry[0]["cause"] == "crash"
    _assert_bit_identical(str(tmp_path / "clean"), str(tmp_path / "sup"))
    # no torn spill files either
    assert not [f for f in os.listdir(tmp_path / "sup")
                if f.endswith(".tmp")]


def test_worker_scrub_interval_quarantines_in_background(tmp_path):
    """--scrub-interval: the background scrubber quarantines a corrupt
    member DURING training and its kind=scrub record lands in
    metrics.jsonl."""
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    ck = tmp_path / "ck"
    # pre-seed the dir with a corrupt old checkpoint the run inherits
    p = save_checkpoint(str(ck), {"w": np.zeros(4, np.float32)}, 1)
    open(p, "r+b").truncate(os.path.getsize(p) // 2)
    out = run_training(ckpt_dir=str(ck), obs_dir=str(tmp_path / "obs"),
                       scrub_interval=0.1, **_TINY)
    assert out["steps"] == 4
    assert (ck / "quarantine" / "ckpt_1.npz").exists()
    mrecs = [json.loads(l) for l in
             (tmp_path / "obs" / "metrics.jsonl").read_text().splitlines()]
    scrubs = [r for r in mrecs if r.get("kind") == "scrub"]
    assert scrubs and any("ckpt_1.npz" in r["quarantined"] for r in scrubs)
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(tmp_path / "obs" / "metrics.jsonl")) == []
