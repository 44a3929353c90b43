"""``tmpi serve --selftest`` serves a real checkpoint end-to-end from the
CLI."""

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
