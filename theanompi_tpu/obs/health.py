"""Per-host heartbeat + multihost stall watchdog.

A multihost run that hangs in a collective today freezes SILENTLY: the
gang-scheduled XLA program blocks every controller, no Python line is
"slow", and the only symptom is a JSONL stream that stops growing. The
reference never faced this (blocking MPI calls fail loudly); the TPU
equivalent needs an out-of-band health layer that distinguishes *slow*
from *stuck*:

- :class:`Heartbeat` — a daemon thread that atomically rewrites
  ``heartbeat_rank{r}.json`` every ``interval`` seconds with the wall
  time, pid, and last completed global step. An external supervisor (or
  another host) reads file mtime + step to tell a live-but-slow rank
  from a dead one.
- :class:`StallWatchdog` — a daemon thread fed ``notify_step(step)``
  after every completed step. When the step stops advancing for
  ``timeout`` seconds it fires ONCE per stall: dumps every Python
  thread's stack (the driver's frame shows WHICH dispatch blocks) to
  ``stall_rank{r}.json`` + a human-readable ``.txt``, then arms a
  ``jax.profiler`` trace into ``postmortem_rank{r}/`` for a short
  window so the device timeline around the hang is preserved for
  tensorboard/xprof. Re-arms automatically when steps resume. The
  clock runs from CONSTRUCTION, not the first step: a run that wedges
  in its very first collective — the canonical multihost hang this
  layer exists to diagnose — reports ``step: -1`` (nothing completed
  yet). The cost of that coverage: a first-epoch compile longer than
  the timeout also reads as a stall, so size the timeout above the
  worst expected compile/eval pause.

Each host watches only its own step counter — a hung collective stalls
every participant, so every rank produces its own post-mortem, and a
SINGLE slow host is identifiable as the one whose heartbeat still
advances while the others' step counters froze.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import traceback
from typing import Callable, Optional

from theanompi_tpu.obs.metrics import atomic_write_text


def _atomic_write_json(path: str, obj: dict) -> None:
    atomic_write_text(path, json.dumps(obj))


def arm_profiler_capture(trace_dir: str, capture_s: float = 2.0,
                         rank: int = 0, wait_at_exit: bool = False) -> str:
    """Best-effort ``jax.profiler`` capture of a ``capture_s`` window on
    a daemon thread — armed-and-forgotten, shared by the stall watchdog
    and the flight recorder (obs/flight.py). start/stop can themselves
    BLOCK on a wedged runtime (observed: stop_trace hangs on the CPU
    backend mid-stall), so nothing waits on the thread; any failure
    (already tracing, wedged runtime) is swallowed. Returns the target
    directory immediately.

    ``wait_at_exit``: run the capture on a NON-daemon thread so a
    process that exits right after arming (the ``--on-anomaly halt``
    path) lets the capture finish instead of tearing the interpreter
    down mid-trace (measured: a daemon capture killed at finalization
    segfaults the CPU backend — an atexit join does NOT save it, the
    thread never gets scheduled again once shutdown starts). Callers
    must only set this when the runtime is known-alive (an anomaly dump
    just drained a row from it); stall dumps keep the daemon default —
    their runtime is presumed wedged and a hung stop_trace must never
    block exit."""

    def capture():
        try:
            import jax

            os.makedirs(trace_dir, exist_ok=True)
            jax.profiler.start_trace(trace_dir)
            try:
                time.sleep(capture_s)
            finally:
                jax.profiler.stop_trace()
        except Exception as e:  # noqa: BLE001 — an armed Recorder
            # trace (already tracing) or a wedged runtime must not
            # surface as a crash from a diagnostics thread
            print(f"[rank {rank}] post-mortem trace capture "
                  f"failed: {e!r}", file=sys.stderr, flush=True)

    threading.Thread(
        target=capture, name=f"tmpi-postmortem-r{rank}",
        daemon=not wait_at_exit,
    ).start()
    return trace_dir


def thread_stacks() -> dict[str, list[str]]:
    """``{thread_name: [formatted frames...]}`` for every live Python
    thread (the stall report payload). Ordered for triage: the main
    thread first (the driver's frame shows which dispatch blocks),
    then the framework's stable ``tmpi-<role>`` threads sorted by role
    so repeated dumps group attributably, then everything else — the
    same names the thread-model inventory
    (tools/analyze/concurrency.thread_inventory) and the stress
    harness report."""
    names = {t.ident: t.name for t in threading.enumerate()}

    def rank(item):
        name = item[0]
        if name.startswith("MainThread"):
            return (0, name)
        if name.startswith("tmpi-"):
            return (1, name)
        return (2, name)

    stacks = {}
    for ident, frame in sys._current_frames().items():
        name = names.get(ident, f"thread-{ident}")
        stacks[f"{name} ({ident})"] = [
            line.rstrip("\n")
            for line in traceback.format_stack(frame)
        ]
    return dict(sorted(stacks.items(), key=rank))


class Heartbeat:
    def __init__(self, obs_dir: str, rank: int = 0, interval: float = 5.0):
        self.path = os.path.join(obs_dir, f"heartbeat_rank{rank}.json")
        self.rank = rank
        self.interval = max(0.2, float(interval))
        self._step = 0
        self._extra: Optional[Callable[[], dict]] = None
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"tmpi-heartbeat-r{rank}", daemon=True
        )
        self._thread.start()

    def set_step(self, step: int) -> None:
        self._step = int(step)

    def set_extra(self, provider: Optional[Callable[[], dict]]) -> None:
        """Install a provider whose dict merges into every beat — the
        driver wires the dispatch pipeline's ``dispatch_in_flight`` /
        ``last_drained_step`` here, so a stall report reader can tell a
        wedged DEVICE program (step advances, drains stop: in-flight
        pinned at depth) from a stalled HOST driver (dispatches stop:
        in-flight falls to 0 and stays)."""
        self._extra = provider

    def _beat(self) -> None:
        payload = {
            "kind": "heartbeat",
            "rank": self.rank,
            "t": time.time(),
            "step": self._step,
            "pid": os.getpid(),
        }
        provider = self._extra
        if provider is not None:
            try:
                payload.update(provider())
            except Exception:  # noqa: BLE001 — liveness must not die
                pass           # because a telemetry getter raced a close
        _atomic_write_json(self.path, payload)

    def _run(self) -> None:
        while not self._stop.is_set():
            try:
                self._beat()
            except OSError:
                pass  # a full disk must not kill the heartbeat thread
            self._stop.wait(self.interval)

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
        try:
            self._beat()  # final state on disk: last step before exit
        except OSError:
            pass


class StallWatchdog:
    """Fires ``on_stall`` (default: stack dump + profiler arm) when the
    step counter stops advancing for ``timeout`` seconds."""

    def __init__(
        self,
        timeout: float,
        obs_dir: str,
        rank: int = 0,
        arm_profiler: bool = True,
        capture_s: float = 2.0,
        on_stall: Optional[Callable[[dict], None]] = None,
    ):
        if timeout <= 0:
            raise ValueError(f"stall timeout must be > 0, got {timeout}")
        self.timeout = float(timeout)
        self.obs_dir = obs_dir
        self.rank = rank
        self.arm_profiler = arm_profiler
        self.capture_s = capture_s
        self.report_path = os.path.join(obs_dir, f"stall_rank{rank}.json")
        self._on_stall = on_stall
        self._lock = threading.Lock()
        self._last_step = -1
        self._last_advance = time.monotonic()
        self._fired_at_step: Optional[int] = None
        self.stall_count = 0
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._run, name=f"tmpi-stall-watchdog-r{rank}", daemon=True
        )
        self._thread.start()

    def notify_step(self, step: int) -> None:
        with self._lock:
            if step != self._last_step:
                self._last_step = step
                self._last_advance = time.monotonic()
                self._fired_at_step = None  # re-arm after progress

    def _run(self) -> None:
        poll = min(self.timeout / 4.0, 1.0)
        while not self._stop.wait(poll):
            with self._lock:
                stalled_s = time.monotonic() - self._last_advance
                step = self._last_step
                # step == -1: nothing completed yet — a first-dispatch
                # hang still fires (the clock runs from construction)
                should_fire = (
                    stalled_s > self.timeout
                    and self._fired_at_step != step
                )
                if should_fire:
                    self._fired_at_step = step
            if should_fire:
                try:
                    self._fire(step, stalled_s)
                except Exception as e:  # noqa: BLE001 — diagnostics only:
                    # the watchdog must never take down a live run
                    print(f"[rank {self.rank}] stall watchdog report "
                          f"failed: {e!r}", file=sys.stderr, flush=True)

    def _fire(self, step: int, stalled_s: float) -> None:
        self.stall_count += 1
        report = {
            "kind": "stall",
            "rank": self.rank,
            "t": time.time(),
            "step": step,
            "stall_s": stalled_s,
            "timeout_s": self.timeout,
            "stacks": thread_stacks(),
        }
        print(
            f"[rank {self.rank}] STALL WATCHDOG: global step stuck at "
            f"{step} for {stalled_s:.1f}s (> {self.timeout:.1f}s) — "
            f"dumping thread stacks to {self.report_path}",
            file=sys.stderr, flush=True,
        )
        # report FIRST (the stacks are the critical payload), THEN arm
        # the device capture: profiler start/stop can block indefinitely
        # on a wedged runtime — exactly the situation being diagnosed
        postmortem = self._arm_postmortem()
        if postmortem:
            report["postmortem_trace"] = postmortem
        _atomic_write_json(self.report_path, report)
        txt = self.report_path[:-5] + ".txt"
        with open(txt, "w") as f:
            f.write(
                f"STALL at step {step}: no progress for {stalled_s:.1f}s "
                f"(timeout {self.timeout:.1f}s), rank {self.rank}\n\n"
            )
            for name, frames in report["stacks"].items():
                f.write(f"--- {name} ---\n")
                f.write("\n".join(frames) + "\n\n")
            if postmortem:
                f.write(
                    f"device post-mortem trace: {postmortem}\n"
                    "view: tensorboard --logdir <dir> (xprof trace viewer)\n"
                )
        if self._on_stall is not None:
            self._on_stall(report)

    def _arm_postmortem(self) -> Optional[str]:
        """Capture a ``capture_s`` device-trace window DURING the stall
        (shared :func:`arm_profiler_capture` machinery): if the device
        is actually executing (slow collective, DCN congestion) the
        trace shows it."""
        if not self.arm_profiler:
            return None
        return arm_profiler_capture(
            os.path.join(self.obs_dir, f"postmortem_rank{self.rank}"),
            capture_s=self.capture_s, rank=self.rank,
        )

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=2.0)
