"""--on-anomaly rollback acceptance tests (launch/worker.py +
obs facade): a confirmed anomaly restores the last VERIFIED checkpoint,
skips the offending step window, decrements the budget, and training
continues — the recovery-side extension of PR 3's flight recorder."""

import json
import math
import os

import pytest

from tinymodel import TinyCNN
from theanompi_tpu.launch.worker import run_training
from theanompi_tpu.obs.numerics import NumericsAnomaly, RollbackRequested

_TINY = dict(
    rule="bsp",
    model_cls=TinyCNN,
    devices=8,
    recipe_overrides={"batch_size": 32, "input_shape": (16, 16, 3),
                      "sched_kwargs": {"lr": 0.05, "boundaries": [10**9]}},
    dataset="synthetic",
    dataset_kwargs={"n_train": 64, "n_val": 32, "image_shape": (16, 16, 3)},
    print_freq=0,
    n_epochs=3,  # 2 steps/epoch, epoch checkpoints at steps 2/4/6
)


def test_rollback_survives_nan_step(tmp_path):
    """Acceptance: an injected NaN batch under --on-anomaly rollback
    restores the last good checkpoint, skips the poisoned batch on
    replay, and the run finishes with finite metrics within budget."""
    out = run_training(
        ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
        numerics_freq=1, on_anomaly="rollback",
        rollback_budget=1, rollback_skip=1,
        inject_faults=["nan_batch@4"], **_TINY,
    )
    assert out["rollbacks"] == 1
    assert out["skipped_steps"] == 1
    assert out["anomalies"] >= 1
    # one data batch was skipped, so the run lands one step short
    assert out["steps"] == 5
    assert all(math.isfinite(v) for v in out["val"].values()), out["val"]

    # rollback record next to the anomaly records, schema-valid
    nm_path = tmp_path / "obs" / "numerics_rank0.jsonl"
    recs = [json.loads(l) for l in nm_path.read_text().splitlines()]
    rb = [r for r in recs if r["kind"] == "rollback"]
    assert len(rb) == 1
    assert rb[0]["step"] == 4            # the anomalous step
    assert rb[0]["restore_step"] == 2    # the verified epoch-1 boundary
    assert rb[0]["budget_left"] == 0
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(nm_path)) == []

    # tmpi_rollbacks_total visible in the metrics snapshots (acceptance)
    snaps = [json.loads(l) for l in
             (tmp_path / "obs" / "metrics.jsonl").read_text().splitlines()]
    assert snaps[-1]["metrics"]["tmpi_rollbacks_total"] == 1.0
    assert snaps[-1]["metrics"]["tmpi_anomalies_total"] >= 1.0
    # the flight bundle landed too (rollback dumps like 'dump')
    assert (tmp_path / "obs" / "anomaly_rank0" / "report.json").exists()


def test_resume_after_rollback_skip_positions_by_batches_consumed(tmp_path):
    """REGRESSION (review finding): a rollback skip consumes a data
    batch without a training step, so step_count alone under-counts the
    loader position. The skipped count is persisted in checkpoint meta
    and a later resume must position by step + skipped — otherwise it
    re-feeds one already-trained batch (possibly the poisoned one) and
    shifts every subsequent step's data."""
    from theanompi_tpu.utils.checkpoint import (
        latest_checkpoint,
        read_checkpoint_meta,
    )

    kw = dict(ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
              numerics_freq=1, on_anomaly="rollback",
              rollback_budget=1, rollback_skip=1)
    out = run_training(inject_faults=["nan_batch@4"], **kw, **_TINY)
    assert out["steps"] == 5 and out["skipped_steps"] == 1
    newest = latest_checkpoint(str(tmp_path / "ck"), verify=True)
    assert read_checkpoint_meta(newest)["skipped_batches"] == 1
    # resume for one more epoch: batches consumed = 5 + 1 = 6 = three
    # full epochs, so the resumed run must start at epoch 3 and train
    # exactly 2 steps (without the meta correction it would recompute
    # 5 % 2 = 1 mid-epoch-2 and re-train an already-consumed batch)
    out2 = run_training(resume=True,
                        **{**kw, **_TINY, "n_epochs": 4})
    assert out2["resumed_from_step"] == 5
    assert out2["steps"] == 7
    assert out2["epochs"] == [3]
    assert out2["skipped_steps"] == 1  # inherited timeline total


def test_rollback_budget_exhausted_degrades_to_halt(tmp_path):
    """budget=0: the RollbackRequested escapes like a halt — and the
    crash-path checkpoint must NOT overwrite the chain with the
    poisoned state (the newest checkpoint stays the pre-anomaly one)."""
    from theanompi_tpu.utils.checkpoint import (
        checkpoint_step,
        latest_checkpoint,
    )

    with pytest.raises(RollbackRequested):
        run_training(
            ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
            numerics_freq=1, on_anomaly="rollback", rollback_budget=0,
            inject_faults=["nan_batch@4"], **_TINY,
        )
    newest = latest_checkpoint(str(tmp_path / "ck"), verify=True)
    assert checkpoint_step(newest) == 2  # pre-anomaly boundary, not 4


def test_rollback_without_ckpt_dir_raises():
    """No checkpoint to restore -> the anomaly propagates (after the
    record landed), rather than silently continuing on NaN params."""
    with pytest.raises(NumericsAnomaly):
        run_training(
            numerics_freq=1, on_anomaly="rollback", rollback_budget=2,
            inject_faults=["nan_batch@3"], **_TINY,
        )


def test_rollback_skip_zero_replays_everything(tmp_path):
    """rollback_skip=0: the transient injected fault does not refire on
    replay, so the full step count is reached with nothing skipped."""
    out = run_training(
        ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
        numerics_freq=1, on_anomaly="rollback",
        rollback_budget=1, rollback_skip=0,
        inject_faults=["nan_batch@4"], **_TINY,
    )
    assert out["rollbacks"] == 1
    assert out["skipped_steps"] == 0
    assert out["steps"] == 6
    assert all(math.isfinite(v) for v in out["val"].values())


def test_rollback_resets_detector_baselines(tmp_path):
    """After a restore the EWMA baselines must re-warm from clean
    values: the replayed steps (same magnitudes as before the anomaly)
    must not re-trigger spike detection against poisoned baselines —
    proven by the run completing with exactly one rollback."""
    out = run_training(
        ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
        numerics_freq=1, on_anomaly="rollback",
        rollback_budget=2, rollback_skip=1,
        inject_faults=["nan_batch@5"], **_TINY,
    )
    assert out["rollbacks"] == 1  # exactly one: replay stayed clean
    assert all(math.isfinite(v) for v in out["val"].values())


def test_cli_rollback_requires_ckpt_dir():
    from theanompi_tpu.cli import main as tmpi_main

    tiny = os.path.join(os.path.dirname(__file__), "tinymodel.py")
    with pytest.raises(SystemExit, match="rollback requires --ckpt-dir"):
        tmpi_main(["BSP", "8", tiny, "TinyCNN", "--synthetic",
                   "--on-anomaly", "rollback"])
    with pytest.raises(SystemExit, match="max-retries requires --ckpt-dir"):
        tmpi_main(["BSP", "8", tiny, "TinyCNN", "--synthetic",
                   "--max-retries", "2"])
    # without a ckpt dir the grace path would exit 75/"resumable" with
    # nothing saved — a lie to the scheduler (review finding)
    with pytest.raises(SystemExit, match="sigterm-grace requires --ckpt-dir"):
        tmpi_main(["BSP", "8", tiny, "TinyCNN", "--synthetic",
                   "--sigterm-grace", "10"])


# -- the anomaly paths with two steps in flight (ISSUE 31) -------------------
# 4 steps an epoch, the poisoned batch at step 6: step 7 follows it inside
# the epoch, so at the default depth its row drains with step 7 dispatched.
_MID = dict(_TINY, n_epochs=2,
            dataset_kwargs={**_TINY["dataset_kwargs"], "n_train": 128})


def _train_rows(save_dir):
    with open(os.path.join(save_dir, "run.jsonl")) as f:
        rows = [json.loads(line) for line in f]
    return [r["step"] for r in rows if r.get("kind") == "train"]


@pytest.mark.parametrize("depth,state_step", [(1, 6), (None, 7)],
                         ids=["depth1", "default_depth"])
def test_halt_mid_epoch_leaves_the_state_of_the_newest_dispatched_step(
        tmp_path, depth, state_step):
    """A halt names the anomalous step whatever the depth; the state it
    leaves (the triage bundle's capture) is that of the newest DISPATCHED
    step, one further at the default; no crash save makes it resumable."""
    from theanompi_tpu.utils.checkpoint import checkpoint_step, latest_checkpoint

    kw = {} if depth is None else {"dispatch_depth": depth}
    with pytest.raises(NumericsAnomaly):
        run_training(
            ckpt_dir=str(tmp_path / "ck"), obs_dir=str(tmp_path / "obs"),
            save_dir=str(tmp_path), run_name="run",
            numerics_freq=1, on_anomaly="halt",
            inject_faults=["nan_batch@6"], **kw, **_MID,
        )
    recs = [json.loads(l) for l in
            (tmp_path / "obs" / "numerics_rank0.jsonl").read_text().splitlines()]
    assert min(r["step"] for r in recs if r["kind"] == "anomaly") == 6
    # rows up to the newest dispatched step: the unwinding flush drains it
    assert _train_rows(str(tmp_path)) == list(range(1, state_step + 1))
    newest = latest_checkpoint(str(tmp_path / "ck"), verify=True)
    assert checkpoint_step(newest) == 4  # the boundary before the anomaly
    captured = latest_checkpoint(
        str(tmp_path / "obs" / "anomaly_rank0" / "state"), verify=True)
    assert checkpoint_step(captured) == state_step


def test_rollback_mid_epoch_lands_where_depth1_lands(tmp_path):
    """The rollback discards the step dispatched past the anomalous one:
    restore, skip and replay give depth 1's final parameters, bit for bit."""
    import numpy as np

    from theanompi_tpu.utils.checkpoint import latest_checkpoint

    finals = []
    for tag, kw in (("sync", {"dispatch_depth": 1}), ("default", {})):
        out = run_training(
            ckpt_dir=str(tmp_path / tag), obs_dir=str(tmp_path / tag / "obs"),
            numerics_freq=1, on_anomaly="rollback",
            rollback_budget=1, rollback_skip=1,
            inject_faults=["nan_batch@6"], **kw, **_MID,
        )
        assert (out["rollbacks"], out["skipped_steps"], out["steps"]) == (1, 1, 7)
        with np.load(latest_checkpoint(str(tmp_path / tag), verify=True)) as z:
            finals.append({k: z[k] for k in z.files if not k.startswith("__")})
    assert finals[0].keys() == finals[1].keys()
    for k in finals[0]:
        np.testing.assert_array_equal(finals[0][k], finals[1][k], err_msg=k)
