"""``tmpi chaos`` — seeded chaos campaigns over the full fault matrix.

Every recovery path in this framework (supervisor retry/backoff,
verified resume, anomaly rollback, SIGTERM grace, elastic reshard,
storage-fault walk-back, the scrubber) was proven by HAND-PICKED single
faults — ``--inject-fault sigkill@3`` — which is exactly how recovery
code rots: the combinations nobody wrote a test for are the ones
production hits. This module fuzzes the combinations. A campaign:

1. **generates randomized fault schedules** from a seeded RNG — kind x
   step x composition over the full matrix (process faults, data
   faults, and the storage kinds this PR adds: ``enospc`` /
   ``slow_write`` / ``bitrot`` / ``partial_set``), including
   back-to-back same-step pairs and fault-during-recovery timings (a
   second fault whose step lands inside the first fault's replay
   window);
2. **runs each schedule under** ``supervise_training`` — in-process
   when the schedule stays inside the process, in a subprocess sandbox
   (with relaunch-on-kill and a fired-fault ledger,
   ``utils/faults.FaultInjector(ledger=...)``) when it contains
   ``sigkill``;
3. **checks the invariant oracle** after every run (:data:`INVARIANTS`):
   the run completed to its target step with host/device step
   agreement, the newest VERIFIED checkpoint is restorable and finite
   (never poisoned), the final state is at parity with an uninterrupted
   baseline — bit-identical where the matrix says exact — the saved
   RNG stream position matches the baseline (an independent no-re-fed/
   no-skipped-batch detector: every consumed batch advances the key
   split stream), rc/resumable-marker semantics are honored, and every
   obs JSONL line is schema-clean;
4. **shrinks** a failing schedule to a minimal reproducer (greedy
   delta-debugging over the fault list) and emits it as a
   ready-to-paste ``--inject-fault`` command-line fragment plus a
   ``kind=chaos`` record in ``<out>/chaos.jsonl``.

The payoff is leverage: the same oracle runs over every engine x codec
x checkpoint-format combination (BSP and ZeRO-1, ``none`` and
``int8:ef``, single-file and sharded sets), so crash-safety of a new
knob is inherited by re-running the campaign, not re-deriving a test
matrix by hand.

Usage::

    tmpi chaos --seeds 25                  # full matrix, 4 configs
    tmpi chaos --smoke --seeds 5           # tier-1 CPU smoke (<120 s)
    tmpi chaos --schedule 'crash@5+bitrot@3'   # one directed schedule
    tmpi chaos --schedule crash@5 --mutate refeed   # oracle self-test

``--mutate refeed`` arms a deliberately seeded recovery bug (the worker
re-feeds one already-consumed batch on mid-epoch resume,
``TMPI_CHAOS_MUTATE``) — the campaign MUST catch and shrink it; that is
the proof the oracle is alive, the same way ``--inject-fault`` is the
proof the recovery paths are.

``--serve`` points the same machinery at the SERVING path instead of
training: seeded schedules over :data:`SERVE_MATRIX`
(``replica_crash@t`` / ``replica_stall@t:s`` / ``reload_corrupt@t`` /
``slow_replica@t:s``, t in seconds into the load window) fire at an
N-replica group (serve/router.py) under closed-loop client load, always
composed with a mid-window checkpoint hot-reload. The serving oracle
(:data:`SERVE_INVARIANTS`): zero dropped/failed requests while the
surviving capacity suffices, per-client served step monotone across
failover and reload, deadline semantics honored, schema-clean obs. The
same greedy shrink applies, and ``--mutate drop_inflight`` arms the
seeded router bug (an in-flight request on a dying replica is dropped
instead of re-admitted) the campaign must catch and shrink::

    tmpi chaos --serve --seeds 10
    tmpi chaos --serve --schedule replica_crash@0.4 --mutate drop_inflight

``--serve --decode`` points the serving campaign at a fleet of
continuous-batching DECODE engines (serve/decode/) instead of
eval-forward engines: clients stream mixed-length token prompts, and
the schedule draws from :data:`DECODE_MATRIX` — the shared kinds plus
``kv_exhaust@t:s`` (grab nearly every free KV page from inside a
member's decode loop and hold it for s seconds: admission must queue
on the free-list, never corrupt or crash) and ``long_prompt_burst@t``
(a concurrent burst of worst-case prompts with maximum output budgets
slamming the largest prefill bucket and the page reservation path).
The oracle gains ``kv_conserved``: after drain every member's KV
free-list must hold pages_out == pages_in with zero outstanding — a
leaked page is a silent capacity loss that compounds across requests::

    tmpi chaos --serve --decode --seeds 10
    tmpi chaos --serve --decode --schedule kv_exhaust@0.4:0.5
"""

from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import threading
import time
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

# ---------------------------------------------------------------------------
# fault matrix
# ---------------------------------------------------------------------------

# kind -> properties the scheduler/oracle need:
#   exact:      an injected fault of this kind must leave the final state
#               BIT-IDENTICAL to the uninterrupted baseline (the resume/
#               walk-back contract); inexact kinds (nan_batch's rollback
#               skips data batches by design) get the weaker oracle
#   arg:        spec arg appended as KIND@STEP:ARG (stall/slow seconds)
#   subprocess: the fault kills the process — needs the sandbox
#   sharded:    only meaningful for sharded checkpoint sets
#   rollback:   needs numerics sentinels + --on-anomaly rollback armed
#   elastic:    a topology fault — the run gets a 2-slice mesh and
#               elastic supervision (reshard-to-survivors); inexact by
#               nature (the survivor world re-partitions the batch, so
#               final state legitimately differs from the flat baseline)
MATRIX: dict[str, dict] = {
    "crash": {},
    "sigterm": {},
    "sigkill": {"subprocess": True},
    "ckpt_truncate": {},
    "loader_stall": {"arg": 0.2},
    "nan_batch": {"exact": False, "rollback": True},
    "enospc": {},
    "slow_write": {"arg": 0.2},
    "bitrot": {},
    "partial_set": {"sharded": True},
    "slice_down": {"exact": False, "elastic": True},
}

# the tier-1 smoke matrix: in-process, sleep-free, storage kinds included
# (slice_down rides tier-1 as a DIRECTED smoke schedule instead —
# tests/test_chaos.py — so the seeded fuzz draws stay stable)
SMOKE_KINDS = ("crash", "ckpt_truncate", "enospc", "bitrot")

INVARIANTS = (
    "completed",        # final summary reached the target step count
    "device_truth",     # host step ledger == device step counter
    "verified_chain",   # a VERIFIED checkpoint is restorable at the end
    "finite_state",     # ... and every array in it is finite
    "parity",           # exact schedules: bit-identical to the baseline
    "no_refeed",        # exact schedules: saved RNG stream position
                        # matches the baseline (re-fed/skipped batch
                        # detector independent of params)
    "rc_semantics",     # every launch exited 0 / rc-75 / injected kill;
                        # the final launch exited 0; marker consumed
    "schema",           # every obs JSONL line validates
)


@dataclass
class ChaosConfig:
    """One engine x codec x checkpoint-format cell of the campaign."""

    name: str
    zero: int = 0
    wire_codec: str = "none"
    sharded_ckpt: bool = False
    devices: int = 4
    batch: int = 32
    n_train: int = 96       # -> 3 steps/epoch: mid-epoch resumes happen
    n_epochs: int = 2

    @property
    def steps_per_epoch(self) -> int:
        return self.n_train // self.batch

    @property
    def total_steps(self) -> int:
        return self.steps_per_epoch * self.n_epochs


def default_configs(smoke: bool) -> list[ChaosConfig]:
    if smoke:
        return [ChaosConfig("bsp_none")]
    return [
        ChaosConfig("bsp_none"),
        ChaosConfig("bsp_int8ef", wire_codec="int8:ef"),
        ChaosConfig("zero1_none", zero=1, sharded_ckpt=True),
        ChaosConfig("zero1_int8ef", zero=1, wire_codec="int8:ef",
                    sharded_ckpt=True),
    ]


# ---------------------------------------------------------------------------
# schedule generation
# ---------------------------------------------------------------------------


def spec_kind(spec: str) -> str:
    return spec.partition("@")[0]


def usable_kinds(cfg: ChaosConfig, kinds: list[str]) -> list[str]:
    """The subset of ``kinds`` this config can actually draw:
    sharded-only kinds need a sharded config, and rollback kinds need a
    run long enough to hold a checkpoint to roll back TO (before the
    first epoch-boundary save the policy correctly degrades to halt —
    working-as-designed, not a schedule worth fuzzing)."""
    out = [k for k in kinds
           if not MATRIX[k].get("sharded") or cfg.sharded_ckpt]
    out = [k for k in out
           if not MATRIX[k].get("rollback")
           or cfg.steps_per_epoch + 1 <= cfg.total_steps]
    # elastic (topology) kinds run on a 2-slice mesh and reshard to
    # survivors: needs an even device count with at least one whole
    # slice left, and the plain-BSP replicated state (ZeRO's sharded
    # optimizer reshard across worlds is its own campaign)
    return [k for k in out
            if not MATRIX[k].get("elastic")
            or (cfg.devices >= 4 and cfg.devices % 2 == 0
                and not cfg.zero and not cfg.sharded_ckpt)]


def generate_schedule(rng: random.Random, cfg: ChaosConfig,
                      kinds: list[str], max_faults: int) -> list[str]:
    """One fuzzed schedule: 1..max_faults specs over the run's step
    range. Composition pressure is deliberate: with probability ~0.4 a
    fault reuses (or lands adjacent to) the previous fault's step —
    back-to-back faults and fault-during-recovery timings (the second
    fault fires inside the first one's replay) are where hand-written
    tests are thinnest."""
    usable = usable_kinds(cfg, kinds)
    if not usable:
        raise ValueError(
            f"no usable fault kinds for config {cfg.name!r}: {kinds} "
            "all filtered out (sharded-only kinds on a non-sharded "
            "config?) — pick --configs/--kinds that compose"
        )
    n = rng.randint(1, max_faults)
    schedule: list[str] = []
    prev_step: Optional[int] = None
    for _ in range(n):
        kind = rng.choice(usable)
        lo = (cfg.steps_per_epoch + 1 if MATRIX[kind].get("rollback")
              else 1)
        if prev_step is not None and rng.random() < 0.4:
            step = min(cfg.total_steps,
                       max(lo, prev_step + rng.choice((0, 1))))
        else:
            step = rng.randint(lo, cfg.total_steps)
        prev_step = step
        arg = MATRIX[kind].get("arg")
        schedule.append(f"{kind}@{step}" + (f":{arg}" if arg else ""))
    # at most one process-killer per schedule keeps the relaunch budget
    # small without losing composition coverage (two sigkills mostly
    # test the same path twice)
    killers = [s for s in schedule if spec_kind(s) == "sigkill"]
    for extra in killers[1:]:
        schedule.remove(extra)
    return schedule


# ---------------------------------------------------------------------------
# running one schedule
# ---------------------------------------------------------------------------


@dataclass
class RunResult:
    """Everything the oracle needs from one schedule's execution."""

    launches: list[str] = field(default_factory=list)  # per-launch outcome
    final_summary: Optional[dict] = None
    error: Optional[str] = None
    ckpt_dir: str = ""
    obs_dir: str = ""


def _base_run_kwargs(cfg: ChaosConfig, ckpt_dir: str, obs_dir: Optional[str],
                     schedule: list[str]) -> dict:
    from theanompi_tpu.models.mlp import MLP

    kw = dict(
        rule="bsp",
        model_cls=MLP,
        devices=cfg.devices,
        zero=cfg.zero,
        wire_codec=cfg.wire_codec,
        sharded_ckpt=cfg.sharded_ckpt,
        ckpt_dir=ckpt_dir,
        obs_dir=obs_dir,
        dataset="synthetic",
        dataset_kwargs={"n_train": cfg.n_train, "n_val": cfg.batch},
        recipe_overrides={"batch_size": cfg.batch},
        n_epochs=cfg.n_epochs,
        print_freq=0,
        seed=0,
    )
    if any(MATRIX[spec_kind(s)].get("rollback") for s in schedule):
        kw.update(numerics_freq=1, on_anomaly="rollback",
                  rollback_budget=len(schedule) + 1)
    if any(spec_kind(s) == "sigterm" for s in schedule):
        kw["sigterm_grace"] = 10.0
    if any(MATRIX[spec_kind(s)].get("elastic") for s in schedule):
        # whole-slice loss needs a slice to lose and a supervisor
        # allowed to reshard onto the survivors
        kw.update(n_slices=2, elastic=True)
    return kw


class BaselineCache:
    """Uninterrupted reference runs for parity checks, built lazily and
    cached per (config, step).

    The full-run baseline's keep-chain covers the epoch-boundary steps;
    a chaos run's newest verified checkpoint can also land MID-epoch
    (the crash-path and SIGTERM-grace saves checkpoint at the step they
    interrupt) — those anchors are produced on demand by a clean
    ``max_steps=step`` run, whose truncation save writes ``ckpt_step``
    with the exact state/rng an uninterrupted run holds after ``step``
    batches."""

    def __init__(self, out_dir: str):
        self.out_dir = out_dir
        self.seconds = 0.0
        self._full: dict[str, str] = {}
        self._at_step: dict[tuple, Optional[str]] = {}

    def full_dir(self, cfg: ChaosConfig) -> str:
        if cfg.name not in self._full:
            from theanompi_tpu.launch.worker import run_training

            t0 = time.perf_counter()
            ckpt_dir = os.path.join(self.out_dir,
                                    f"baseline_{cfg.name}", "ckpt")
            summary = run_training(**_base_run_kwargs(cfg, ckpt_dir,
                                                      None, []))
            self.seconds += time.perf_counter() - t0
            if summary["steps"] != cfg.total_steps:
                raise RuntimeError(
                    f"baseline for {cfg.name} stopped at step "
                    f"{summary['steps']}, expected {cfg.total_steps}"
                )
            self._full[cfg.name] = ckpt_dir
        return self._full[cfg.name]

    def at_step(self, cfg: ChaosConfig, step: int) -> Optional[str]:
        """A verified clean checkpoint of ``cfg`` at exactly ``step``
        (None only for step 0, which has no save to anchor on)."""
        key = (cfg.name, int(step))
        if key in self._at_step:
            return self._at_step[key]
        path = _chain_at_step(self.full_dir(cfg), step)
        if path is None and 0 < step <= cfg.total_steps:
            from theanompi_tpu.launch.worker import run_training

            t0 = time.perf_counter()
            ckpt_dir = os.path.join(self.out_dir, f"baseline_{cfg.name}",
                                    f"step{step}", "ckpt")
            run_training(max_steps=step,
                         **_base_run_kwargs(cfg, ckpt_dir, None, []))
            self.seconds += time.perf_counter() - t0
            path = _chain_at_step(ckpt_dir, step)
        self._at_step[key] = path
        return path


def _run_inprocess(cfg: ChaosConfig, schedule: list[str],
                   workdir: str) -> RunResult:
    """Run one schedule under supervise_training in THIS process: one
    FaultInjector threads through every supervisor attempt AND every
    rc-75-equivalent relaunch (Preempted re-raise -> marker resume), so
    each fault fires exactly once per schedule."""
    from theanompi_tpu.launch.supervisor import supervise_training
    from theanompi_tpu.utils.faults import FaultInjector, Preempted

    res = RunResult(ckpt_dir=os.path.join(workdir, "ckpt"),
                    obs_dir=os.path.join(workdir, "obs"))
    injector = FaultInjector(schedule)
    kw = _base_run_kwargs(cfg, res.ckpt_dir, res.obs_dir, schedule)
    resume = False
    budget = len(schedule) + 3
    for _ in range(budget):
        try:
            summary = supervise_training(
                max_retries=len(schedule) + 2, backoff_base=0.0,
                inject_faults=injector, resume=resume, **kw,
            )
            res.launches.append("ok")
            res.final_summary = summary
            return res
        except Preempted:
            # the marker the grace path dropped drives the next
            # launch's auto-resume — exactly the scheduler-requeue
            # contract rc 75 promises
            res.launches.append("preempted")
            continue
        except Exception as e:  # noqa: BLE001 — the oracle's evidence
            res.launches.append(f"error:{type(e).__name__}")
            res.error = repr(e)
            return res
    res.error = f"relaunch budget ({budget}) exhausted"
    return res


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _subprocess_env(mutate: Optional[str]) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()
    if mutate:
        env["TMPI_CHAOS_MUTATE"] = mutate
    else:
        env.pop("TMPI_CHAOS_MUTATE", None)
    return env


def _run_subprocess(cfg: ChaosConfig, schedule: list[str], workdir: str,
                    mutate: Optional[str], timeout: float) -> RunResult:
    """Run one schedule in a subprocess sandbox — required whenever the
    schedule kills the process (sigkill has no in-process recovery).
    The chaos runner is the outer scheduler: it relaunches a killed/
    preempted run with ``--resume``, and the fired-fault LEDGER
    (``--fault-ledger``) carries once-only semantics across the process
    boundary — without it every relaunch would replay the kill forever."""
    import signal as _signal

    res = RunResult(ckpt_dir=os.path.join(workdir, "ckpt"),
                    obs_dir=os.path.join(workdir, "obs"))
    ledger = os.path.join(workdir, "fault_ledger.txt")
    args = [
        "BSP", str(cfg.devices), "theanompi_tpu.models.mlp", "MLP",
        "--synthetic", "--epochs", str(cfg.n_epochs),
        "--batch-size", str(cfg.batch), "--print-freq", "0",
        "--dataset-arg", f"n_train={cfg.n_train}",
        "--dataset-arg", f"n_val={cfg.batch}",
        "--ckpt-dir", res.ckpt_dir, "--obs-dir", res.obs_dir,
        "--max-retries", str(len(schedule) + 2), "--retry-backoff", "0",
        "--fault-ledger", ledger,
        "--wire-codec", cfg.wire_codec,
    ]
    if cfg.zero:
        args += ["--zero", str(cfg.zero)]
    if cfg.sharded_ckpt:
        args += ["--ckpt-sharded"]
    if any(MATRIX[spec_kind(s)].get("rollback") for s in schedule):
        args += ["--numerics-freq", "1", "--on-anomaly", "rollback",
                 "--rollback-budget", str(len(schedule) + 1)]
    if any(spec_kind(s) == "sigterm" for s in schedule):
        args += ["--sigterm-grace", "10"]
    if any(MATRIX[spec_kind(s)].get("elastic") for s in schedule):
        args += ["--slices", "2", "--elastic"]
    for s in schedule:
        args += ["--inject-fault", s]
    env = _subprocess_env(mutate)
    budget = len(schedule) + 3
    resume: list[str] = []
    for _ in range(budget):
        try:
            p = subprocess.run(
                [sys.executable, "-m", "theanompi_tpu.cli", *args, *resume],
                env=env, capture_output=True, text=True, timeout=timeout,
                cwd=_repo_root(),
            )
        except subprocess.TimeoutExpired as e:
            # a hung launch is a FINDING for this schedule (exactly the
            # class of bug a chaos tool exists to surface), not a
            # campaign-aborting runner error — record it and let the
            # oracle fail/shrink the schedule like any other violation
            res.launches.append("timeout")
            res.error = (f"launch exceeded {timeout:.0f}s "
                         f"({e.cmd[-3:]}...)")
            return res
        if p.returncode == 0:
            res.launches.append("ok")
            for line in reversed(p.stdout.strip().splitlines()):
                try:
                    res.final_summary = json.loads(line)
                    break
                except json.JSONDecodeError:
                    continue
            return res
        if p.returncode == 75:
            res.launches.append("preempted")
            resume = ["--resume"]
            continue
        if p.returncode in (-_signal.SIGKILL, -_signal.SIGTERM):
            res.launches.append(f"killed:{p.returncode}")
            resume = ["--resume"]
            continue
        res.launches.append(f"rc:{p.returncode}")
        res.error = (f"rc {p.returncode}\n{p.stdout[-1500:]}\n"
                     f"{p.stderr[-1500:]}")
        return res
    res.error = f"relaunch budget ({budget}) exhausted"
    return res


def run_schedule(cfg: ChaosConfig, schedule: list[str], workdir: str, *,
                 mutate: Optional[str] = None,
                 timeout: float = 300.0) -> RunResult:
    os.makedirs(workdir, exist_ok=True)
    if any(MATRIX[spec_kind(s)].get("subprocess") for s in schedule):
        return _run_subprocess(cfg, schedule, workdir, mutate, timeout)
    if mutate:
        os.environ["TMPI_CHAOS_MUTATE"] = mutate
    try:
        return _run_inprocess(cfg, schedule, workdir)
    finally:
        if mutate:
            os.environ.pop("TMPI_CHAOS_MUTATE", None)


# ---------------------------------------------------------------------------
# the invariant oracle
# ---------------------------------------------------------------------------


def _ckpt_arrays(path: str) -> dict[str, np.ndarray]:
    """The comparable content of one checkpoint: every saved array,
    minus the JSON sidecars whose text may legitimately differ across
    recovery histories (__usermeta__ records rollback skips;
    __integrity__ re-derives from the arrays; __meta__/__topology__
    describe layout, which shape checks already pin)."""
    data = np.load(path)
    skip = ("__integrity__", "__usermeta__", "__meta__", "__topology__",
            "__rng_impl__")
    return {k: data[k] for k in data.files if k not in skip}


def _sharded_member_paths(path: str) -> list[str]:
    from theanompi_tpu.utils.checkpoint import _SHARD_RE, _sharded_sets

    m = _SHARD_RE.search(os.path.basename(path))
    if not m:
        return [path]
    return _sharded_sets(os.path.dirname(path) or ".")[int(m.group(1))]


def _final_verified(ckpt_dir: str):
    from theanompi_tpu.utils.checkpoint import (
        checkpoint_step, latest_checkpoint,
    )

    path = latest_checkpoint(ckpt_dir, verify=True)
    return path, (checkpoint_step(path) if path else -1)


# fault kinds that can destroy a COMMITTED or in-flight save: a
# schedule made of these may legitimately leave ZERO verified
# checkpoints (every save torn/rotted/dropped) — an empty chain is only
# a violation when nothing in the schedule could have caused it
_SAVE_DESTROYING = ("ckpt_truncate", "bitrot", "partial_set", "enospc")


def check_invariants(cfg: ChaosConfig, schedule: list[str], res: RunResult,
                     baseline: BaselineCache) -> list[str]:
    """The oracle: the names of every violated invariant (empty = the
    schedule was absorbed correctly). See :data:`INVARIANTS`."""
    from theanompi_tpu.utils.checkpoint import read_resumable_marker

    viol: list[str] = []
    exact = all(MATRIX[spec_kind(s)].get("exact", True) for s in schedule)

    # a schedule can compose a rollback-policy fault with enough
    # save-destroyers that NOTHING verified remains when the rollback
    # needs it — the policy then degrades to halt (a DELIBERATE stop,
    # the documented PR-4 semantics, and the supervisor rightly never
    # retries it). That terminal state is legitimate: the oracle keeps
    # enforcing the quarantine invariant (no poisoned verified
    # checkpoint) and schema/marker hygiene, but not completion.
    _halt_names = ("RollbackRequested", "NumericsAnomaly")
    anomaly_halt = (
        any(MATRIX[spec_kind(f)].get("rollback") for f in schedule)
        and any(spec_kind(f) in _SAVE_DESTROYING for f in schedule)
        and res.error is not None
        and any(n in res.error for n in _halt_names)
    )

    s = res.final_summary
    # batches-consumed accounting: an anomaly rollback SKIPS data
    # batches by design (each skip consumes a batch without a training
    # step), so completion is judged on steps + skipped_steps — the
    # same ledger the resume-positioning contract uses
    consumed = (int(s.get("steps", -1)) + int(s.get("skipped_steps", 0))
                if s else -1)
    if not anomaly_halt and (
            res.error is not None or s is None
            or consumed != cfg.total_steps):
        viol.append("completed")
    if s is not None and s.get("device_steps") is not None and (
            s.get("device_steps") != s.get("steps")):
        viol.append("device_truth")

    path, step = _final_verified(res.ckpt_dir)
    if path is None:
        if not any(spec_kind(f) in _SAVE_DESTROYING for f in schedule):
            viol.append("verified_chain")
    else:
        arrays = _ckpt_arrays(path)
        member_arrays = [
            _ckpt_arrays(p) for p in _sharded_member_paths(path)
        ]
        if not all(
            np.isfinite(a).all()
            for ma in member_arrays
            for a in ma.values()
            if np.issubdtype(a.dtype, np.floating)
        ):
            viol.append("finite_state")
        if exact and step > 0:
            # parity against a CLEAN run's checkpoint at the SAME step
            # (a tail-of-run storage fault legitimately walks the chain
            # back, so the anchor is whatever IS restorable; step 0 has
            # no save to anchor on and is skipped)
            bpath = baseline.at_step(cfg, step)
            if bpath is None:
                viol.append("parity")
            else:
                barrays = _ckpt_arrays(bpath)
                if set(arrays) != set(barrays) or any(
                    not np.array_equal(arrays[k], barrays[k])
                    for k in arrays if k != "__rng__"
                ):
                    viol.append("parity")
                if "__rng__" in arrays and not np.array_equal(
                        arrays.get("__rng__"), barrays.get("__rng__")):
                    viol.append("no_refeed")

    if anomaly_halt:
        # the halt must still be CLEAN: no stale resumable marker
        # promising a scheduler an auto-resume into a halted policy
        if read_resumable_marker(res.ckpt_dir) is not None:
            viol.append("rc_semantics")
    else:
        bad_launch = [
            l for l in res.launches
            if l not in ("ok", "preempted") and not l.startswith("killed:")
        ]
        if (not res.launches or res.launches[-1] != "ok" or bad_launch
                or read_resumable_marker(res.ckpt_dir) is not None):
            viol.append("rc_semantics")

    viol.extend(_schema_violations(res.obs_dir))
    return viol


def _chain_at_step(ckpt_dir: str, step: int) -> Optional[str]:
    from theanompi_tpu.utils.checkpoint import _keep_chain, verify_checkpoint

    for s, _, path in _keep_chain(ckpt_dir):
        if s == step and verify_checkpoint(path):
            return path
    return None


def _schema_violations(obs_dir: str) -> list[str]:
    from theanompi_tpu.tools.check_obs_schema import check_file, discover

    if not obs_dir or not os.path.isdir(obs_dir):
        return []
    try:
        files = discover([obs_dir])
    except FileNotFoundError:
        return []
    errs: list[str] = []
    for f in files:
        errs += check_file(f)
    return ["schema"] if errs else []


# ---------------------------------------------------------------------------
# shrinking
# ---------------------------------------------------------------------------


def shrink_schedule(cfg: ChaosConfig, schedule: list[str],
                    baseline: BaselineCache, workdir: str, *,
                    mutate: Optional[str] = None, timeout: float = 300.0,
                    max_runs: int = 24) -> tuple[list[str], int]:
    """Greedy delta-debugging: drop one fault at a time while the
    reduced schedule still violates ANY invariant; fixed point = the
    minimal reproducer. Returns (minimal schedule, shrink runs spent)."""
    current = list(schedule)
    runs = 0
    changed = True
    while changed and len(current) > 1 and runs < max_runs:
        changed = False
        for i in range(len(current)):
            cand = current[:i] + current[i + 1:]
            wd = os.path.join(workdir, f"shrink{runs}")
            runs += 1
            res = run_schedule(cfg, cand, wd, mutate=mutate, timeout=timeout)
            if check_invariants(cfg, cand, res, baseline):
                current = cand
                changed = True
                break
            if runs >= max_runs:
                break
    return current, runs


def repro_line(schedule: list[str]) -> str:
    return " ".join(f"--inject-fault {s}" for s in schedule)


# ---------------------------------------------------------------------------
# the serving campaign (`tmpi chaos --serve`)
# ---------------------------------------------------------------------------

# serving fault kinds: spec is KIND@T[:ARG] with T seconds into the
# load window (floats, unlike the training matrix's step numbers).
#   replica_crash   hard-kill one healthy member (router.kill_replica:
#                   queued AND in-flight requests must fail over)
#   replica_stall   freeze one member's batcher for ARG seconds, once —
#                   the router's least-loaded scoring must steer around
#                   the growing queue, not blackhole behind it
#   reload_corrupt  commit a NEWER checkpoint then bit-rot it: the
#                   central reloader's verified keep-chain walk must
#                   skip it and keep serving the previous step
#   slow_replica    ARG seconds of extra latency per batch for the rest
#                   of the run (a degraded-not-dead member: EWMA-based
#                   routing shifts load, health checks keep it green)
SERVE_MATRIX: dict[str, dict] = {
    "replica_crash": {},
    "replica_stall": {"arg": 0.3},
    "reload_corrupt": {},
    "slow_replica": {"arg": 0.05},
}

# the decode fleet's matrix (``--serve --decode``): the engine-agnostic
# kinds, plus
#   kv_exhaust       from inside one member's decode loop, alloc all
#                    but one free KV page and hold them ARG seconds —
#                    admission must back up on the free-list (FIFO
#                    queueing, typed KVExhausted internally) and
#                    resume when the pages return; never a crash, a
#                    drop, or a corrupted page table
#   long_prompt_burst  a concurrent burst of worst-case-length prompts
#                    with maximum output budgets — slams the largest
#                    prefill bucket, the worst-case page reservation,
#                    and slot contention all at once
# (slow_replica is omitted: per-batch latency injection wraps the
# eval engine's _serve_batch; the decode equivalent of a persistently
# slow member is kv_exhaust's page pressure)
DECODE_MATRIX: dict[str, dict] = {
    "replica_crash": {},
    "replica_stall": {"arg": 0.3},
    "reload_corrupt": {},
    "kv_exhaust": {"arg": 0.5},
    "long_prompt_burst": {},
}

SERVE_INVARIANTS = (
    "no_drops",        # zero dropped/failed requests while the
                       # surviving capacity sufficed (every request
                       # terminally served/expired/rejected-with-
                       # retry — never silently lost)
    "step_monotone",   # per-client served params_step never moves
                       # backward across failover or hot-reload
    "deadline",        # DeadlineExceeded only after the deadline
                       # actually passed; no zombie expiries
    "completed",       # clients all ran, traffic was served, the
                       # router drained cleanly
    "schema",          # every obs JSONL line validates (router.jsonl,
                       # serve_r<id>.jsonl included)
    "kv_conserved",    # decode fleets only: after drain, every
                       # member's KV free-list is whole (pages_out ==
                       # pages_in, zero outstanding) — a leaked page
                       # is silent capacity loss
)


def parse_serve_spec(spec: str, matrix: Optional[dict] = None) -> tuple:
    """``KIND@T[:ARG]`` -> (kind, t_seconds, arg)."""
    matrix = SERVE_MATRIX if matrix is None else matrix
    kind, sep, rest = spec.partition("@")
    if not sep or kind not in matrix:
        raise ValueError(
            f"serve fault spec {spec!r} must be KIND@T with kind in "
            f"{sorted(matrix)}"
        )
    t_s, sep2, arg_s = rest.partition(":")
    arg = float(arg_s) if sep2 else matrix[kind].get("arg")
    return kind, float(t_s), arg


def generate_serve_schedule(rng: random.Random, duration: float,
                            max_faults: int,
                            matrix: Optional[dict] = None) -> list[str]:
    """One fuzzed serving schedule: 1..max_faults specs inside the load
    window, with the training generator's composition pressure (~0.4
    probability a fault lands on/next to the previous one's time — a
    crash DURING a stall, a second crash inside the first restart's
    backoff window)."""
    matrix = SERVE_MATRIX if matrix is None else matrix
    n = rng.randint(1, max_faults)
    schedule: list[str] = []
    prev_t: Optional[float] = None
    for _ in range(n):
        kind = rng.choice(sorted(matrix))
        if prev_t is not None and rng.random() < 0.4:
            t = min(0.8 * duration, prev_t + rng.choice((0.0, 0.1)))
        else:
            t = rng.uniform(0.15 * duration, 0.7 * duration)
        t = round(t, 2)
        prev_t = t
        arg = matrix[kind].get("arg")
        schedule.append(f"{kind}@{t}" + (f":{arg}" if arg is not None
                                         else ""))
    return schedule


@dataclass
class ServeRunResult:
    """Everything the serving oracle needs from one schedule's run."""

    ledgers: list = field(default_factory=list)  # per-client entry dicts
    router_stats: dict = field(default_factory=dict)
    drained: bool = False
    error: Optional[str] = None
    obs_dir: str = ""
    # decode fleets only: every member's KV free-list whole after
    # drain (None = not a decode run, invariant not applicable)
    kv_conserved: Optional[bool] = None


def _serve_model():
    from theanompi_tpu.models.mlp import MLP

    return MLP(MLP.default_recipe().replace(
        input_shape=(8, 8, 3), batch_size=8))


def _decode_model():
    from theanompi_tpu.models.zoo import zoo_entry

    cls, _ = zoo_entry("transformer_lm")
    return cls(cls.default_recipe().replace(
        input_shape=(64,), num_classes=32, d_model=32, n_heads=2,
        n_layers=2, d_ff=64, attn="ring", batch_size=4))


def _degrade_engine(eng, seconds: float, once: bool) -> None:
    """Wrap one engine's batch path with injected latency — the
    chaos-side stand-in for a GC pause / noisy neighbor (`once`) or a
    persistently slow host (not `once`). The eval engine's unit of
    work is ``_serve_batch``; the decode engine's is ``_iteration``."""
    if hasattr(eng, "_serve_batch"):
        orig = eng._serve_batch

        def stalled(reqs):
            if once:
                eng._serve_batch = orig
            time.sleep(seconds)
            orig(reqs)

        eng._serve_batch = stalled
    else:
        orig = eng._iteration

        def stalled_iter():
            if once:
                eng._iteration = orig
            time.sleep(seconds)
            orig()

        eng._iteration = stalled_iter


def _exhaust_engine(eng, hold_s: float, held: list) -> None:
    """kv_exhaust: from INSIDE the decode loop (the free-list is
    single-owner — foreign-thread allocs would race admission), grab
    all but one free KV page on the next iteration and hold them for
    ``hold_s`` seconds. Admission must back up on the free-list and
    resume when the pages return. ``held`` collects the grab so the
    runner can return pages that are still out when the window closes
    (after drain, once the batcher thread is gone)."""
    orig = eng._iteration
    grab: dict = {"fl": eng._cache.free_list, "pages": None, "t0": None}
    held.append(grab)

    def exhausted_iter():
        fl = grab["fl"]
        now = time.perf_counter()
        if grab["pages"] is None:
            n = max(0, fl.n_free - 1)
            grab["pages"] = fl.alloc(n) if n else []
            grab["t0"] = now
        elif grab["pages"] and now - grab["t0"] >= hold_s:
            fl.free(grab["pages"])
            grab["pages"] = []
            eng._iteration = orig
        orig()

    eng._iteration = exhausted_iter


def run_serve_schedule(schedule: list[str], workdir: str, *,
                       replicas: int = 2, duration: float = 2.0,
                       clients: int = 4, mutate: Optional[str] = None,
                       seed: int = 0,
                       decode: bool = False) -> ServeRunResult:
    """Run one serving schedule in-process: an N-replica Router under
    closed-loop client load, the fault controller firing the schedule
    at its T marks, and ALWAYS a good checkpoint committed mid-window
    (hot-reload under load rides every schedule). ``decode=True``
    swaps the fleet members for continuous-batching decode engines
    (clients stream mixed-length token prompts; the Router is
    UNCHANGED — that composition is the point)."""
    import jax

    from theanompi_tpu.serve.engine import (
        DeadlineExceeded, Rejected, ServeEngine,
    )
    from theanompi_tpu.serve.reload import CheckpointReloader
    from theanompi_tpu.serve.router import RequestDropped, Router
    from theanompi_tpu.train import init_train_state
    from theanompi_tpu.utils.checkpoint import save_checkpoint
    from theanompi_tpu.utils.faults import FaultInjector

    os.makedirs(workdir, exist_ok=True)
    res = ServeRunResult(obs_dir=os.path.join(workdir, "obs"))
    ckpt_dir = os.path.join(workdir, "ckpt")
    os.makedirs(ckpt_dir, exist_ok=True)

    model = _decode_model() if decode else _serve_model()
    state = init_train_state(model, jax.random.PRNGKey(0))
    ckpt_step = [1]

    def _commit(corrupt: bool = False) -> None:
        # step-dependent params so every swap is visible in served steps
        ckpt_step[0] += 1
        step = ckpt_step[0]
        bumped = state._replace(params=jax.tree_util.tree_map(
            lambda p: p + 0.01 * step, state.params))
        save_checkpoint(ckpt_dir, bumped, step,
                        rng=jax.random.PRNGKey(step), keep=10)
        if corrupt:
            FaultInjector.bitrot_newest(ckpt_dir)

    save_checkpoint(ckpt_dir, state, 1, rng=jax.random.PRNGKey(1), keep=10)

    def _member(rid):
        if decode:
            from theanompi_tpu.serve.decode import DecodeEngine

            eng = DecodeEngine(
                model, prefill_buckets=(4, 8), page_size=4,
                kv_pages=48, max_seqs=4, max_new_tokens=6,
                max_queue=256, obs_dir=res.obs_dir,
                replica_id=rid, sink_name=f"decode_r{rid}.jsonl",
            )
        else:
            eng = ServeEngine(
                model, buckets=(1, 4), max_queue=256, obs_dir=res.obs_dir,
                replica_id=rid, sink_name=f"serve_r{rid}.jsonl",
            )
        eng.load_initial(ckpt_dir)
        eng.warmup()
        eng.start()
        return eng

    router = Router(
        _member, replicas, obs_dir=res.obs_dir, health_interval=0.05,
        restart_base_s=0.05, restart_cap_s=0.4, seed=seed, mutate=mutate,
    )
    router.start()
    reloader = CheckpointReloader(router, ckpt_dir, interval=0.1)

    stop = threading.Event()
    ledgers: list[list] = [[] for _ in range(clients)]

    vocab = int(getattr(model.recipe, "num_classes", 0) or 0)

    def _client(idx: int) -> None:
        r = np.random.RandomState(1000 + idx)
        if not decode:
            shape = tuple(model.recipe.input_shape)
            x = r.randn(*shape).astype(np.float32)
        i = 0
        while not stop.is_set():
            if decode:
                # mixed-length token prompts spanning every prefill
                # bucket plus the prefill-free single-token path
                x = r.randint(0, vocab, size=r.randint(1, 10),
                              dtype=np.int32)
            # every 4th request carries a (generous) deadline so the
            # deadline invariant exercises the expiry path under faults
            deadline = 2000.0 if i % 4 == 0 else None
            entry: dict = {"deadline_ms": deadline}
            t0 = time.perf_counter()
            try:
                out = router.infer(x, deadline_ms=deadline, timeout=30.0)
                entry.update(status="served", step=int(out.step))
            except DeadlineExceeded:
                entry["status"] = "expired"
            except RequestDropped as e:
                entry.update(status="dropped", error=repr(e))
            except Rejected as e:
                entry.update(status="rejected",
                             error=type(e).__name__)
            except Exception as e:  # noqa: BLE001 — oracle evidence
                entry.update(status="failed", error=repr(e))
            entry["ms"] = round(1000.0 * (time.perf_counter() - t0), 3)
            ledgers[idx].append(entry)
            i += 1
            if entry["status"] == "rejected":
                time.sleep(0.01)  # honor retry-after in spirit

    def _fire(kind: str, arg: Optional[float]) -> None:
        if kind == "replica_crash":
            # kill the BUSIEST healthy member (deepest queue, ties to
            # the lowest id): the harshest realistic crash — it is the
            # replica actually holding in-flight work, so the failover
            # re-admission path is exercised every time instead of by
            # scheduling luck
            healthy = [rep for rep in router._replicas
                       if rep.state == "healthy" and rep.engine is not None]
            if healthy:
                victim = max(healthy,
                             key=lambda rep: (rep.engine.queue_depth,
                                              -rep.replica_id))
                router.kill_replica(victim.replica_id)
        elif kind in ("replica_stall", "slow_replica"):
            rep = next((rep for rep in router._replicas
                        if rep.state == "healthy"
                        and rep.engine is not None), None)
            if rep is not None:
                _degrade_engine(rep.engine,
                                arg or SERVE_MATRIX[kind]["arg"],
                                once=(kind == "replica_stall"))
        elif kind == "kv_exhaust":
            rep = next((rep for rep in router._replicas
                        if rep.state == "healthy"
                        and rep.engine is not None), None)
            if rep is not None:
                _exhaust_engine(rep.engine,
                                arg or DECODE_MATRIX[kind]["arg"], held)
        elif kind == "long_prompt_burst":
            # worst-case prompts (largest bucket + 1) with maximum
            # output budgets, submitted concurrently through the
            # router; outcomes land in their own ledger so the oracle
            # scores them like any client's
            top = 9  # the decode members' largest prefill bucket + 1
            prompts = [burst_rng.randint(0, max(vocab, 2), size=top,
                                         dtype=np.int32)
                       for _ in range(2 * replicas + 2)]

            def _burst_wait(p):
                entry: dict = {"deadline_ms": None}
                t0 = time.perf_counter()
                try:
                    out = router.infer(p, timeout=30.0)
                    entry.update(status="served", step=int(out.step))
                except RequestDropped as e:
                    entry.update(status="dropped", error=repr(e))
                except Rejected as e:
                    entry.update(status="rejected", error=type(e).__name__)
                except Exception as e:  # noqa: BLE001 — oracle evidence
                    entry.update(status="failed", error=repr(e))
                entry["ms"] = round(1000.0 * (time.perf_counter() - t0), 3)
                burst_ledger.append(entry)

            for p in prompts:
                threading.Thread(target=_burst_wait, args=(p,),
                                 daemon=True).start()
        elif kind == "reload_corrupt":
            _commit(corrupt=True)
            reloader.poll_once()  # force the load attempt NOW (it is
            # absorbed — serving keeps the current params); waiting on
            # the background poller leaves the exercise to timing luck
        elif kind == "good_reload":
            _commit(corrupt=False)
            # land the swap at the event mark: this IS the
            # reload-under-load composition, deterministically timed —
            # the background poller still runs for extra churn, but on
            # a loaded box its first poll can start after the window
            reloader.poll_once()

    events = [parse_serve_spec(s, DECODE_MATRIX if decode else None)
              for s in schedule]
    # hot-reload-under-load rides EVERY schedule: a good checkpoint
    # lands mid-window, so faults compose with a live swap (for a
    # decode fleet this IS hot-reload mid-generation: in-flight
    # sequences keep generating across the fleet-wide param swap)
    events.append(("good_reload", round(duration * 0.5, 2), None))
    events.sort(key=lambda e: e[1])
    held: list = []            # kv_exhaust grabs (returned post-drain)
    burst_ledger: list = []    # long_prompt_burst outcomes
    burst_rng = np.random.RandomState(seed * 7 + 3)

    def _controller() -> None:
        t_start = time.perf_counter()
        for kind, t, arg in events:
            wait = t - (time.perf_counter() - t_start)
            if wait > 0 and stop.wait(wait):
                return
            try:
                _fire(kind, arg)
            except Exception as e:  # noqa: BLE001 — runner bug, not
                # a finding: surface it as a run error
                res.error = f"fault controller: {e!r}"
                return

    threads = [threading.Thread(target=_client, args=(i,), daemon=True)
               for i in range(clients)]
    ctrl = threading.Thread(target=_controller, daemon=True)
    threads.append(ctrl)
    try:
        reloader.start()
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        # the window closes `duration` after start OR 0.3 s after the
        # LAST scheduled event fired, whichever is later: on a loaded
        # box the controller's event marks slip, and closing on wall
        # time alone can cut the window before the composed
        # reload-under-load ever gets a post-swap request
        ctrl.join(timeout=2.0 * duration + 30.0)
        time.sleep(max(duration - (time.perf_counter() - t0), 0.3))
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=60.0)
        if any(t.is_alive() for t in threads):
            res.error = res.error or "client/controller thread hung"
        reloader.stop()
        res.drained = router.drain(timeout=30.0)
    if decode:
        # return any kv_exhaust pages still out when the window closed
        # (safe now: drain stopped the batcher threads that own the
        # free-lists), then assert conservation over every member that
        # is still attached — crashed members were failed-over and
        # their replacement engines are the ones in rotation
        for grab in held:
            if grab["pages"]:
                grab["fl"].free(grab["pages"])
                grab["pages"] = []
        res.kv_conserved = all(
            rep.engine._cache.free_list.conserved()
            for rep in router._replicas if rep.engine is not None
        )
    res.router_stats = router.stats()
    res.ledgers = ledgers + ([burst_ledger] if burst_ledger else [])
    return res


def check_serve_invariants(schedule: list[str],
                           res: ServeRunResult) -> list[str]:
    """The serving oracle: names of every violated invariant (empty =
    the schedule was absorbed). See :data:`SERVE_INVARIANTS`."""
    viol: list[str] = []
    entries = [e for ledger in res.ledgers for e in ledger]
    served = [e for e in entries if e["status"] == "served"]

    if (res.error is not None or not res.drained or not served
            or any(not ledger for ledger in res.ledgers)):
        viol.append("completed")

    # zero silent loss while capacity sufficed: the schedules this
    # campaign generates always leave the supervisor able to restore
    # capacity (factory restarts succeed), so ANY dropped/failed
    # request is a violation — counted both from the client ledgers
    # and the router's own counter (they must agree in kind)
    dropped = res.router_stats.get("tmpi_router_dropped_total", 0.0)
    if dropped > 0 or any(e["status"] in ("dropped", "failed")
                          for e in entries):
        viol.append("no_drops")

    for ledger in res.ledgers:
        steps = [e["step"] for e in ledger if e["status"] == "served"]
        if any(b < a for a, b in zip(steps, steps[1:])):
            viol.append("step_monotone")
            break

    for e in entries:
        d = e.get("deadline_ms")
        if e["status"] == "expired" and (d is None or e["ms"] < d - 50.0):
            viol.append("deadline")  # expired before its deadline
            break
        if e["status"] == "served" and d is not None and e["ms"] > d + 1500.0:
            viol.append("deadline")  # served long past its deadline
            break

    if res.kv_conserved is False:  # decode fleets only (None = N/A)
        viol.append("kv_conserved")

    viol.extend(_schema_violations(res.obs_dir))
    return viol


def shrink_serve_schedule(schedule: list[str], workdir: str, *,
                          replicas: int, duration: float, clients: int,
                          mutate: Optional[str], seed: int,
                          max_runs: int = 16,
                          decode: bool = False) -> tuple[list[str], int]:
    """Greedy delta-debugging over a failing serving schedule — same
    fixed-point loop as the training shrink."""
    current = list(schedule)
    runs = 0
    changed = True
    while changed and len(current) > 1 and runs < max_runs:
        changed = False
        for i in range(len(current)):
            cand = current[:i] + current[i + 1:]
            wd = os.path.join(workdir, f"shrink{runs}")
            runs += 1
            r = run_serve_schedule(cand, wd, replicas=replicas,
                                   duration=duration, clients=clients,
                                   mutate=mutate, seed=seed,
                                   decode=decode)
            if check_serve_invariants(cand, r):
                current = cand
                changed = True
                break
            if runs >= max_runs:
                break
    return current, runs


def run_serve_campaign(args: argparse.Namespace) -> dict:
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    chaos_log = os.path.join(out_dir, "chaos.jsonl")
    decode = bool(getattr(args, "decode", False))
    matrix = DECODE_MATRIX if decode else SERVE_MATRIX
    kind_name = "decode" if decode else "serve"
    config_name = f"{kind_name}_{args.replicas}r"

    if args.schedule:
        for s in args.schedule.split("+"):
            parse_serve_spec(s, matrix)  # fail fast on a bad spec
        plans = [(args.seed, args.schedule.split("+"))]
    else:
        plans = []
        for i in range(args.seeds):
            seed = args.seed + i
            rng = random.Random(seed * 100003 + 29)
            plans.append((seed, generate_serve_schedule(
                rng, args.serve_duration, args.max_faults, matrix)))

    t_start = time.perf_counter()
    # no parity baseline on the serving path; the bucket stays for the
    # summary line's shared format
    timings = {"baseline": 0.0, "runs": 0.0, "shrink": 0.0}
    results = []
    n_bad = 0
    with open(chaos_log, "a") as log_f:
        for seed, schedule in plans:
            wd = os.path.join(out_dir, f"{kind_name}_seed{seed}")
            t0 = time.perf_counter()
            res = run_serve_schedule(
                schedule, wd, replicas=args.replicas,
                duration=args.serve_duration, clients=args.serve_clients,
                mutate=args.mutate, seed=seed, decode=decode)
            viol = check_serve_invariants(schedule, res)
            timings["runs"] += time.perf_counter() - t0
            rec = {
                "kind": "chaos", "t": time.time(), "seed": int(seed),
                "config": config_name, "schedule": "+".join(schedule),
                "ok": not viol, "violations": ",".join(viol),
                "runs": 1,
                "seconds": round(time.perf_counter() - t0, 3),
            }
            if viol:
                n_bad += 1
                t0 = time.perf_counter()
                minimal, shrink_runs = shrink_serve_schedule(
                    schedule, wd, replicas=args.replicas,
                    duration=args.serve_duration,
                    clients=args.serve_clients, mutate=args.mutate,
                    seed=seed, decode=decode)
                timings["shrink"] += time.perf_counter() - t0
                rec["shrunk_schedule"] = "+".join(minimal)
                rec["repro"] = (f"--serve {'--decode ' if decode else ''}"
                                f"--schedule {'+'.join(minimal)}")
                rec["runs"] = rec["runs"] + shrink_runs
                print(f"[chaos] {kind_name} seed {seed} VIOLATED {viol} "
                      f"by {'+'.join(schedule)}; minimal repro: "
                      f"{rec['repro']}", flush=True)
                if res.error:
                    print(f"[chaos]   run error: {res.error[:400]}",
                          flush=True)
            else:
                n_served = sum(
                    1 for ledger in res.ledgers for e in ledger
                    if e["status"] == "served")
                print(f"[chaos] {kind_name} seed {seed} ok: "
                      f"{'+'.join(schedule)} absorbed "
                      f"({n_served} served, "
                      f"{int(res.router_stats.get('tmpi_router_failovers_total', 0))}"
                      f" failovers)", flush=True)
            log_f.write(json.dumps(rec) + "\n")
            log_f.flush()
            results.append(rec)

    timings["total"] = time.perf_counter() - t_start
    report = {
        "schedules": len(results),
        "ok": len(results) - n_bad,
        "violated": n_bad,
        "kinds": sorted(matrix),
        "configs": [config_name],
        "mutate": args.mutate,
        "results": results,
        "timings_s": {k: round(v, 3) for k, v in timings.items()},
        "out": out_dir,
    }
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
    return report


# ---------------------------------------------------------------------------
# campaign driver
# ---------------------------------------------------------------------------


def run_campaign(args: argparse.Namespace) -> dict:
    out_dir = os.path.abspath(args.out)
    os.makedirs(out_dir, exist_ok=True)
    chaos_log = os.path.join(out_dir, "chaos.jsonl")
    kinds = list(SMOKE_KINDS if args.smoke else
                 (args.kinds.split(",") if args.kinds else MATRIX))
    for k in kinds:
        if k not in MATRIX:
            raise SystemExit(f"unknown fault kind {k!r}; matrix: "
                             f"{sorted(MATRIX)}")
    configs = default_configs(args.smoke)
    if args.configs:
        want = args.configs.split(",")
        configs = [c for c in default_configs(False) if c.name in want]
        if not configs:
            raise SystemExit(f"no config matches {args.configs!r}")

    t_start = time.perf_counter()
    timings = {"baseline": 0.0, "runs": 0.0, "shrink": 0.0}
    baseline = BaselineCache(out_dir)

    # directed mode: one explicit schedule instead of fuzzing
    if args.schedule:
        plans = [(0, configs[0], args.schedule.split("+"))]
    else:
        for cfg in configs:
            # refuse up front with an actionable message rather than an
            # IndexError mid-campaign
            if not usable_kinds(cfg, kinds):
                raise SystemExit(
                    f"config {cfg.name!r} has no usable fault kinds in "
                    f"{kinds} (sharded-only kinds on a non-sharded "
                    "config?) — adjust --kinds/--configs"
                )
        plans = []
        for i in range(args.seeds):
            seed = args.seed + i
            cfg = configs[i % len(configs)]
            rng = random.Random(seed * 100003 + 17)
            plans.append((seed, cfg,
                          generate_schedule(rng, cfg, kinds,
                                            args.max_faults)))

    results = []
    n_bad = 0
    with open(chaos_log, "a") as log_f:
        for seed, cfg, schedule in plans:
            baseline.full_dir(cfg)  # build the reference run up front
            wd = os.path.join(out_dir, f"seed{seed}_{cfg.name}")
            t0 = time.perf_counter()
            res = run_schedule(cfg, schedule, wd, mutate=args.mutate,
                               timeout=args.run_timeout)
            viol = check_invariants(cfg, schedule, res, baseline)
            timings["runs"] += time.perf_counter() - t0
            rec = {
                "kind": "chaos", "t": time.time(), "seed": int(seed),
                "config": cfg.name, "schedule": "+".join(schedule),
                "ok": not viol, "violations": ",".join(viol),
                "runs": len(res.launches),
                "seconds": round(time.perf_counter() - t0, 3),
            }
            if viol:
                n_bad += 1
                t0 = time.perf_counter()
                minimal, shrink_runs = shrink_schedule(
                    cfg, schedule, baseline, wd, mutate=args.mutate,
                    timeout=args.run_timeout)
                timings["shrink"] += time.perf_counter() - t0
                rec["shrunk_schedule"] = "+".join(minimal)
                rec["repro"] = repro_line(minimal)
                rec["runs"] = rec["runs"] + shrink_runs
                print(f"[chaos] seed {seed} ({cfg.name}) VIOLATED "
                      f"{viol} by {'+'.join(schedule)}; minimal repro: "
                      f"{rec['repro']}", flush=True)
                if res.error:
                    print(f"[chaos]   run error: {res.error[:400]}",
                          flush=True)
            else:
                print(f"[chaos] seed {seed} ({cfg.name}) ok: "
                      f"{'+'.join(schedule)} absorbed "
                      f"({len(res.launches)} launch(es))", flush=True)
            log_f.write(json.dumps(rec) + "\n")
            log_f.flush()
            results.append(rec)

    # baseline wall time is attributed wherever it was lazily paid
    # (up-front full runs + on-demand mid-epoch anchors inside the
    # oracle); the dedicated bucket reports the true total
    timings["baseline"] = baseline.seconds
    timings["total"] = time.perf_counter() - t_start
    report = {
        "schedules": len(results),
        "ok": len(results) - n_bad,
        "violated": n_bad,
        "kinds": kinds,
        "configs": [c.name for c in configs],
        "mutate": args.mutate,
        "results": results,
        "timings_s": {k: round(v, 3) for k, v in timings.items()},
        "out": out_dir,
    }
    with open(os.path.join(out_dir, "report.json"), "w") as f:
        json.dump(report, f, indent=2)
    return report


def chaos_main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="tmpi chaos", description=__doc__.splitlines()[0])
    ap.add_argument("--seeds", type=int, default=25,
                    help="fuzzed schedules to run (one seed each)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base seed: schedule i uses seed+i")
    ap.add_argument("--smoke", action="store_true",
                    help="tier-1 CPU smoke: bsp/none config, in-process "
                         "sleep-free kinds only (crash/ckpt_truncate/"
                         "enospc/bitrot) — the <120 s CI mode")
    ap.add_argument("--schedule", default=None, metavar="K@S[+K@S...]",
                    help="run ONE directed schedule instead of fuzzing "
                         "(e.g. 'crash@5+bitrot@3')")
    ap.add_argument("--kinds", default=None,
                    help="comma-joined fault-kind subset of the matrix")
    ap.add_argument("--configs", default=None,
                    help="comma-joined config subset "
                         "(bsp_none,bsp_int8ef,zero1_none,zero1_int8ef)")
    ap.add_argument("--max-faults", type=int, default=3,
                    help="max faults per fuzzed schedule")
    ap.add_argument("--mutate", choices=["refeed", "drop_inflight"],
                    default=None,
                    help="arm a deliberately seeded recovery bug "
                         "(oracle self-test): 'refeed' re-feeds one "
                         "consumed batch on mid-epoch resume; "
                         "'drop_inflight' (--serve only) drops an "
                         "in-flight request on replica death instead "
                         "of re-admitting it — the campaign must "
                         "catch and shrink it")
    ap.add_argument("--serve", action="store_true",
                    help="chaos the SERVING path instead of training: "
                         "fuzzed SERVE_MATRIX schedules against an "
                         "N-replica router under client load")
    ap.add_argument("--decode", action="store_true",
                    help="with --serve: fleet of continuous-batching "
                         "decode engines; schedules draw from "
                         "DECODE_MATRIX (adds kv_exhaust/"
                         "long_prompt_burst) and the oracle adds "
                         "kv_conserved")
    ap.add_argument("--replicas", type=int, default=2, metavar="N",
                    help="--serve: replica-group size")
    ap.add_argument("--serve-duration", type=float, default=2.0,
                    help="--serve: load-window seconds per schedule")
    ap.add_argument("--serve-clients", type=int, default=4,
                    help="--serve: closed-loop client threads")
    ap.add_argument("--out", default="chaos_out",
                    help="campaign output dir (chaos.jsonl, report.json, "
                         "per-seed work dirs)")
    ap.add_argument("--run-timeout", type=float, default=300.0,
                    help="per-subprocess-launch timeout seconds")
    ap.add_argument("--json", action="store_true",
                    help="print the full JSON report to stdout")
    args = ap.parse_args(argv)

    if args.decode and not args.serve:
        raise SystemExit("--decode modifies the serving campaign; "
                         "pass --serve --decode")
    if args.mutate == "drop_inflight" and not args.serve:
        raise SystemExit("--mutate drop_inflight needs --serve (it is "
                         "a router bug, not a training one)")
    if args.mutate == "refeed" and args.serve:
        raise SystemExit("--mutate refeed is a training-resume bug; "
                         "--serve wants drop_inflight")

    from theanompi_tpu.tools.lint import _ensure_virtual_devices

    _ensure_virtual_devices()

    try:
        report = run_serve_campaign(args) if args.serve \
            else run_campaign(args)
    except SystemExit:
        raise
    except Exception as e:  # noqa: BLE001 — rc 2 = runner bug, not a finding
        print(f"tmpi chaos: internal error: {e!r}", file=sys.stderr)
        import traceback

        traceback.print_exc()
        return 2
    if args.json:
        print(json.dumps(report, indent=2))
    else:
        t = report["timings_s"]
        print(
            f"chaos: {report['ok']}/{report['schedules']} schedules "
            f"absorbed ({report['violated']} violated) over configs "
            f"{report['configs']} | timings_s baseline={t['baseline']} "
            f"runs={t['runs']} shrink={t['shrink']} total={t['total']}"
        )
        for r in report["results"]:
            if not r["ok"]:
                print(f"  seed {r['seed']} {r['config']}: "
                      f"{r['violations']} <- {r['schedule']} | repro: "
                      f"{r.get('repro', '')}")
    return 1 if report["violated"] else 0


if __name__ == "__main__":
    sys.exit(chaos_main())
