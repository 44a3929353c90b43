"""Multi-controller process launcher — the ``mpirun`` equivalent.

Reference: the launcher built an ``mpirun -n N python worker.py`` command
line with per-rank device env (``lib/base.py`` + rule ``init()``;
SURVEY.md §3.1). On TPU pods each HOST already runs one controller
process (started by the pod runtime / GKE / SLURM, picked up via
``TMPI_AUTO_INIT=1``), so a production launcher is usually unnecessary.
This module provides the same capability for the cases that need it:

- **Local simulation**: N controller processes on one machine, each
  owning a slice of virtual CPU devices — the multi-host integration
  test bed (``--xla_force_host_platform_device_count``), usable by any
  developer without a pod.
- **Ad-hoc clusters**: print/spawn the env each host needs.

``spawn_local(n_proc, argv)`` forks this Python interpreter N times with
``TMPI_*`` env set; rank 0's output streams through; returns exit codes.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys
from typing import Optional, Sequence


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def controller_env(
    process_id: int,
    num_processes: int,
    coordinator: str,
    devices_per_proc: Optional[int] = None,
    platform: Optional[str] = None,
) -> dict:
    """The env one controller process needs to join the world."""
    env = {
        "TMPI_COORDINATOR": coordinator,
        "TMPI_NUM_PROCESSES": str(num_processes),
        "TMPI_PROCESS_ID": str(process_id),
    }
    if devices_per_proc is not None:
        flags = os.environ.get("XLA_FLAGS", "")
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices_per_proc}"
        ).strip()
    if platform is not None:
        env["JAX_PLATFORMS"] = platform
    return env


def spawn_local(
    n_proc: int,
    argv: Sequence[str],
    devices_per_proc: Optional[int] = None,
    coordinator: Optional[str] = None,
    timeout: Optional[float] = None,
    failure_grace: float = 15.0,
) -> list[int]:
    """Run ``python -m/argv`` as ``n_proc`` cooperating controller
    processes on this machine (CPU simulation of a multi-host pod).
    Streams rank-0 output; captures other ranks to buffers printed on
    failure. Returns the per-rank exit codes.

    Supervision: children are POLLED, not waited-on in rank order — if
    any rank dies non-zero while the others block in a collective, the
    survivors get ``failure_grace`` seconds to exit on their own, then
    are killed, and the failed rank's buffered output is printed.
    ``timeout`` (None = unbounded, the default: training runs are long)
    caps total wall clock and raises ``TimeoutExpired``.
    """
    import threading
    import time as _time

    coordinator = coordinator or f"127.0.0.1:{_free_port()}"
    procs = []
    for pid in range(n_proc):
        env = dict(os.environ)
        env.update(
            controller_env(
                pid, n_proc, coordinator,
                devices_per_proc=devices_per_proc,
                platform="cpu" if devices_per_proc is not None else None,
            )
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, *argv],
                env=env,
                stdout=None if pid == 0 else subprocess.PIPE,
                stderr=None if pid == 0 else subprocess.STDOUT,
                text=pid != 0,
            )
        )

    # drain non-rank-0 pipes concurrently (a full pipe buffer would
    # deadlock the child)
    outputs: dict[int, str] = {}
    drains = []
    for pid, p in enumerate(procs):
        if p.stdout is not None:
            t = threading.Thread(
                target=lambda pid=pid, p=p: outputs.__setitem__(pid, p.stdout.read()),
                name=f"tmpi-mh-drain-p{pid}", daemon=True,
            )
            t.start()
            drains.append(t)

    deadline = (_time.monotonic() + timeout) if timeout else None

    def _kill_survivors():
        for p in procs:
            if p.poll() is None:
                p.kill()

    while True:
        rcs = [p.poll() for p in procs]
        if all(rc is not None for rc in rcs):
            break
        if any(rc not in (None, 0) for rc in rcs):
            grace_end = _time.monotonic() + failure_grace
            while any(p.poll() is None for p in procs) and _time.monotonic() < grace_end:
                _time.sleep(0.2)
            _kill_survivors()
            break
        if deadline is not None and _time.monotonic() > deadline:
            _kill_survivors()
            for p in procs:  # reap — no zombie children on the timeout path
                p.wait()
            for t in drains:
                t.join(timeout=5)
            raise subprocess.TimeoutExpired([sys.executable, *argv], timeout)
        _time.sleep(0.2)

    for p in procs:
        p.wait()
    for t in drains:
        t.join(timeout=5)
    codes = [p.returncode for p in procs]
    for pid, rc in enumerate(codes):
        if rc != 0 and outputs.get(pid):
            sys.stderr.write(f"--- rank {pid} (exit {rc}) output ---\n{outputs[pid]}\n")
    return codes
