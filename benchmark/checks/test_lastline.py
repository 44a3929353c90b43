import json
import os
import subprocess
import sys

from harness import manifest

RUN = [sys.executable, os.path.join(manifest.BENCH_DIR, "run.py")]
ENV = {**os.environ, "JAX_PLATFORMS": "cpu", "BENCH_RUN": "ignored"}


def test_tiny_run_ends_in_the_contracts_line_and_is_never_correct():
    p = subprocess.run(RUN + ["--workload", "lm136m-bsp1-train", "--seed", "3000000019",
                              "--seconds", "1", "--trace", "0", "--tiny"],
                       env=ENV, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.rstrip("\n").rsplit("\n", 1)[-1])
    assert list(line) == ["correct", "attempted", "failed", "metrics", "device", "checks"]
    assert line["correct"] is False and line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"train_step_ms", "setup_s"}
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert p.stderr.rstrip("\n").rsplit("\n", 1)[-1] == "correct: False"
    for name, c in line["checks"].items():
        assert f"check {name}: value" in p.stderr


def test_refuses_to_measure_off_a_tpu():
    p = subprocess.run(RUN + ["--workload", "lm136m-bsp1-train", "--seed", "1",
                              "--seconds", "1", "--trace", "0"],
                       env=ENV, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not p.stdout.strip().startswith("{") and '"correct"' not in p.stdout
