"""``tmpi serve --decode --selftest`` serves generated tokens from a real
checkpoint end-to-end, from the CLI."""

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


def test_cli_serve_decode_selftest_roundtrip(tmp_path):
    """tmpi serve --decode over a checkpoint this test saves: reshard-
    aware load -> AOT warm (prefill buckets + ONE decode program) ->
    mixed-length selftest prompts -> schema-valid decode stats line."""
    from theanompi_tpu.models.zoo import zoo_entry
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint

    cls, _ = zoo_entry("transformer_lm")
    model = cls(cls.default_recipe().replace(
        input_shape=(64,), num_classes=32, d_model=32, n_heads=2,
        n_layers=2, d_ff=64, attn="ring", batch_size=4,
    ))
    state = init_train_state(model, jax.random.PRNGKey(0))
    save_checkpoint(str(tmp_path), state, 3, rng=jax.random.PRNGKey(1))

    obs = tmp_path / "obs"
    lines = _run([
        sys.executable, "-m", "theanompi_tpu.cli", "serve",
        "--ckpt-dir", str(tmp_path), "--model", "transformer_lm",
        "--recipe-arg", "input_shape=[64]",
        "--recipe-arg", "num_classes=32",
        "--recipe-arg", "d_model=32", "--recipe-arg", "n_heads=2",
        "--recipe-arg", "n_layers=2", "--recipe-arg", "d_ff=64",
        "--recipe-arg", 'attn="ring"', "--recipe-arg", "batch_size=4",
        "--decode", "--prefill-buckets", "4,8", "--kv-pages", "64",
        "--page-size", "4", "--max-seqs", "4", "--max-new-tokens", "4",
        "--selftest", "5", "--obs-dir", str(obs),
    ])
    stats = json.loads(lines[-1])
    assert stats["kind"] == "decode"
    assert stats["params_step"] == 3
    assert stats["metrics"]["tmpi_decode_served_total"] == 5.0
    assert stats["metrics"]["tmpi_decode_failed_total"] == 0.0
    # KV free-list conserved through the whole selftest
    assert (stats["metrics"]["tmpi_decode_kv_pages_out_total"]
            == stats["metrics"]["tmpi_decode_kv_pages_in_total"])
    assert validate_record(stats) == []
    from theanompi_tpu.tools.check_obs_schema import check_file

    assert check_file(str(obs / "decode.jsonl")) == []
