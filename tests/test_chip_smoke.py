"""chip_smoke.py's contract, rehearsed on the CPU: whatever happens, the
LAST stdout line is the one JSON object the chip check reads, a CPU run
never says ``ok``, and a failed phase is reported as failed.

Each case runs the script as a user would, in a process of its own (the
parent must stay off JAX, and only a real process shows what is written
last)."""

import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _two_cores():
    # the rehearsal's children compile on every core they are given;
    # held to two, they cannot starve the timing-sensitive tests that
    # other xdist workers run meanwhile
    os.sched_setaffinity(0, sorted(os.sched_getaffinity(0))[:2])


def _run(argv, timeout=300):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(argv, cwd=REPO_ROOT, env=env, capture_output=True,
                       text=True, timeout=timeout, preexec_fn=_two_cores)
    lines = [l for l in p.stdout.splitlines() if l.strip()]
    return p.returncode, lines


def _contract_line(lines):
    last = json.loads(lines[-1])
    assert set(last) == {"ok", "device"}
    assert set(last["device"]) == {"platform", "kind", "count"}
    return last


def _phase_verdicts(lines):
    out = {}
    for l in lines:
        if l.startswith('{"phase"'):
            d = json.loads(l)
            out[d["phase"]] = d
    return out


def test_tiny_runs_every_phase_and_still_says_not_ok():
    rc, lines = _run([sys.executable, "chip_smoke.py", "--tiny"])
    last = _contract_line(lines)
    verdicts = _phase_verdicts(lines)
    assert set(verdicts) == {"train-lm", "serve-decode", "make-shards",
                             "train-alexnet"}
    assert all(v["passed"] for v in verdicts.values()), verdicts
    assert verdicts["train-lm"]["device_steps"] == verdicts["train-lm"]["steps"]
    assert verdicts["train-alexnet"]["host_loader"] is not None
    # a toy run on a CPU proves nothing about the chip
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert rc != 0


def test_refuses_the_cpu_at_full_width():
    rc, lines = _run([sys.executable, "chip_smoke.py"], timeout=60)
    last = _contract_line(lines)
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert rc != 0
    assert not _phase_verdicts(lines)  # refused before any phase


def test_failed_phase_is_reported_and_the_contract_line_still_comes_last():
    broken = (
        "import sys, chip_smoke\n"
        "def phases(tiny, out, device):\n"
        "    ps = chip_smoke.one_chip_phases(tiny, out, device)[:2]\n"
        "    i = ps[0].cmd.index('TransformerLM_136M')\n"
        "    ps[0].cmd[i] = 'NoSuchModel'\n"
        "    return ps\n"
        "sys.exit(chip_smoke.main(['--tiny'], phases_for=phases))\n"
    )
    rc, lines = _run([sys.executable, "-c", broken])
    last = _contract_line(lines)
    verdicts = _phase_verdicts(lines)
    assert verdicts["train-lm"]["passed"] is False
    assert verdicts["train-lm"]["rc"] != 0
    # its consumer is not run on a checkpoint that does not exist
    assert verdicts["serve-decode"]["passed"] is False
    assert "skipped" in verdicts["serve-decode"]
    assert last["ok"] is False
    assert rc != 0
