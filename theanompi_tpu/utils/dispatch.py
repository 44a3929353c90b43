"""Asynchronous dispatch pipeline: deferred step metrics + amortized timing.

The reference hid host work behind device compute on the INPUT side
(``lib/proc_load_mpi.py`` double-buffering; our ``data/loader.py``
PrefetchLoader) — and then the per-step driver threw the win away on the
OUTPUT side: ``rec.end("step", sync=metrics["loss"])`` forced a full
host<->device round trip per step, so the host could not enqueue step
N+1 until step N's loss had been materialized — it serializes dispatch.

:class:`MetricsDispatcher` removes the per-step sync. The driver pushes
each step's DEVICE-RESIDENT metric pytree into a ring buffer of
``depth`` in-flight entries; pushing entry N drains entry N-depth+1 —
whose D2H fetch blocks only if the device has not yet finished a step
that is ``depth-1`` dispatches old (in steady state: never). The drain
is the ONLY host<->device sync in the train loop
(``tools/check_hot_loop.py`` lints that it stays that way).

Timing semantics (amortized spaced syncs): each drain IS a spaced sync,
and the per-step wall time attributed to the drained step is the
interval between consecutive drain returns minus the data-wait time the
driver reported via :meth:`note_wait` in that interval. In steady state
the device completes exactly one step per drain interval, so the
attributed time converges to the true device step time whether the
device or the host is the bottleneck. ``flush()`` (epoch / exchange /
checkpoint boundaries) blocks once on the newest in-flight step and
attributes the remaining window evenly across the drained entries.

**Depth 2 is the driver's default** (``run_training``, ``tmpi``; PR 31):
the driver dispatches step N, refills the key stream, and ``push(N)``
drains step N-1 — its blocking D2H returns with N already queued, so the
device goes from N-1's last operation to N's first without the host, and
the drain's wake, the row, the fetch and the next dispatch run under
step N. What lags by one step: step N-1's recorder row, ``on_row`` (the
flight ring, anomaly detection and with it ``--on-anomaly`` halt and
rollback, which then act with step N already dispatched),
``last_drained_step`` in the heartbeat, and the amortized step seconds.
What does not: the state, the step count and the key carry (a
checkpoint pairs the three as of the newest DISPATCHED step), the
watchdog's ``on_step``, and every boundary (epoch end, EASGD exchange,
validation, checkpoint, preemption), each of which ``flush()``es first.
:meth:`MetricsDispatcher.note_dispatch` counts how often the pipeline was
in fact ahead of the device (``summary["dispatch_ahead_share"]``).

With ``depth=1`` every push drains immediately — the attributed time is
dispatch + block, exactly what the old ``end("step", sync=...)`` bracket
measured, and rows are emitted at the same points in the JSONL stream.
Deeper pipelines emit the SAME rows (same steps, same values, same
n_images attribution), just later — tests/test_dispatch.py proves the
streams bit-identical modulo the wall-clock ``images_per_sec`` field.

``host_blocked_s`` accumulates the time the host actually spent blocked
inside drains — ``host_blocked_frac`` in the run summary
is this over the train-loop wall time, the direct measurement of the
per-step host tax this module exists to remove. It is the sum of the
recorder's ``drain`` brackets, not a timer of its own.

Spans (utils/recorder.py ``SpanRing``): the blocking D2H is the
``drain`` bracket and the row's emission (recorder row, ``on_row``, the
print) the ``emit`` bracket, each under the number of the step it
drains — at depth 2 the step before the one just dispatched. The
amortized windows close on the drain bracket's own end stamp.

:class:`KeyStream` is the pipeline's other half on the way IN: each
step's random key is split off the carry one dispatch unit ahead, by
one jitted program queued behind the step that is running, so the host
has the next key in hand when the drain returns.
"""

from __future__ import annotations

from collections import deque
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np


def _block_on(metrics: dict) -> None:
    """Block until the step that produced ``metrics`` has executed
    (device arrays expose ``block_until_ready``; host values no-op)."""
    for v in metrics.values():
        block = getattr(v, "block_until_ready", None)
        if block is not None:
            block()  # one leaf suffices: all values share the program
            return


def _still_running(metrics: dict) -> bool:
    """Whether the step that produced ``metrics`` has NOT yet executed,
    asked without blocking (``jax.Array.is_ready``; host values: done)."""
    for v in metrics.values():
        is_ready = getattr(v, "is_ready", None)
        if is_ready is not None:
            return not is_ready()  # one leaf, as in ``_block_on``
    return False


class MetricsDispatcher:
    """Ring buffer of in-flight step metrics (see module docstring).

    ``recorder``: the run's :class:`~theanompi_tpu.utils.recorder.Recorder`
    — drains call ``recorder.note_time("step", dt)`` then
    ``recorder.train_metrics(...)``, so rows carry the amortized
    per-step throughput exactly like sync-mode rows carry the bracketed
    one. ``on_step_seconds``: optional callback receiving the amortized
    per-substep seconds at each sync point (the driver wires
    ``Observability.note_step_seconds`` so the comm-GB/s gauge stays
    live under deferred timing).
    """

    def __init__(
        self,
        recorder,
        depth: int = 1,
        on_step_seconds: Optional[Callable[[float], None]] = None,
        on_row: Optional[Callable[[int, dict, dict], None]] = None,
    ):
        self.rec = recorder
        self.depth = max(1, int(depth))
        self._buf: deque = deque()
        self._t_mark: Optional[int] = None  # ns on the recorder's clock
        self._wait_s = 0.0
        self._on_step_seconds = on_step_seconds
        # per-emitted-row hook ``(step, metrics, numerics)`` — the obs
        # facade's flight-ring/anomaly entry point (obs/numerics.py).
        # Called AFTER the recorder row lands, with host floats from the
        # SAME D2H fetch the row came from: numerics detection adds no
        # sync of its own, it rides the drain.
        self._on_row = on_row
        # time the host spent actually blocked inside drains (the tax)
        self.host_blocked_s = 0.0
        self.n_syncs = 0
        # newest step whose row has been emitted (heartbeat telemetry:
        # in_flight + this distinguish a wedged device program from a
        # stalled host driver)
        self.last_drained_step = -1
        # amortized per-substep seconds of the most recent sync; None
        # while steps are in flight without a completed sync
        self.last_step_seconds: Optional[float] = None
        # step dispatches seen by note_dispatch, and those of them made
        # while the step before was still running on the device
        self.dispatches = 0
        self.dispatches_ahead = 0

    @property
    def in_flight(self) -> int:
        """Entries pushed but not yet drained."""
        return len(self._buf)

    @property
    def ahead_share(self) -> Optional[float]:
        """Dispatches made while the step before was still running, over
        dispatches (depth 2, device the pace: 1 less the first after each
        flush; depth 1: 0; falling = the host has become the pace)."""
        return self.dispatches_ahead / self.dispatches if self.dispatches else None

    # -- driver hooks --------------------------------------------------------
    def note_dispatch(self) -> None:
        """Call right before a step program's dispatch: counts it, and
        whether the newest in-flight step is still running (no blocking:
        one ``is_ready`` on one leaf; nothing in flight = not ahead)."""
        self.dispatches += 1
        if self._buf and _still_running(self._buf[-1][1]):
            self.dispatches_ahead += 1

    def note_wait(self, dt: float) -> None:
        """Report data-wait time (the recorder's ``wait`` bracket) so the
        amortized step attribution excludes it — keeping the wait/step
        split's meaning identical to sync mode."""
        self._wait_s += float(dt)

    def push(self, step: int, metrics: dict, n_images: int = 0,
             substeps: int = 1) -> None:
        """Enqueue one dispatched step (or fused group of ``substeps``)
        whose ``metrics`` are still device-resident futures. Drains the
        oldest entry once ``depth`` entries are in flight."""
        if self._t_mark is None:
            # window opens at the first in-flight push; waits before it
            # (epoch-boundary eval/checkpoint, first batch load) are not
            # part of any step's attribution
            self._t_mark = self.rec.clock_ns()
            self._wait_s = 0.0
        self._buf.append((int(step), metrics, int(n_images), max(1, int(substeps))))
        while len(self._buf) >= self.depth:
            self._drain_one()

    def flush(self) -> None:
        """Drain every in-flight entry: ONE block on the newest step
        (which implies all older steps finished), remaining window time
        attributed evenly. Call at epoch ends, before an engine
        exchange, and before checkpoints — the recorder stream then
        holds exactly the rows sync mode would hold at the same point."""
        if not self._buf:
            # close the timing window even with nothing in flight: with
            # depth=1 the buffer is ALWAYS empty here (push drains
            # immediately), and a stale _t_mark would hand the whole
            # boundary's wall time (eval/val/checkpoint, or an EASGD
            # exchange) to the first step drained after it
            self._t_mark = None
            self._wait_s = 0.0
            return
        entries = list(self._buf)
        self._buf.clear()
        self.rec.start("drain")
        err: Optional[Exception] = None
        try:
            _block_on(entries[-1][1])
        except Exception as e:  # noqa: BLE001
            # a buffered step's program faulted (OOM, NaN check, ...) —
            # the newest entry's sync surfaces it, but OLDER steps may
            # have completed fine; persist their rows (exactly what
            # depth=1 would already have written) before re-raising
            err = e
        # one block for all the entries: under the newest step's number
        self.host_blocked_s += self.rec.end("drain", step=entries[-1][0])
        self.n_syncs += 1
        total = max(
            0.0, (self.rec.t_end_ns - self._t_mark) * 1e-9 - self._wait_s)
        per_entry = total / len(entries)
        self._t_mark = None
        self._wait_s = 0.0
        for step, metrics, n_images, substeps in entries:
            if err is not None:
                # oldest-first salvage: materializing the first poisoned
                # entry re-raises; everything older is already emitted
                try:
                    metrics = {k: np.asarray(v) for k, v in metrics.items()}
                except Exception:  # noqa: BLE001
                    raise err
            self.last_step_seconds = per_entry / substeps
            self.rec.note_time("step", per_entry, step=step)
            self._emit_rows(step, metrics, n_images, substeps)
        if err is not None:
            raise err
        if self._on_step_seconds is not None and entries:
            self._on_step_seconds(self.last_step_seconds)

    def discard(self) -> None:
        """Drop every in-flight entry WITHOUT draining and close the
        timing window. The anomaly-rollback path (launch/worker.py)
        uses this: the buffered entries belong to steps the restore is
        about to erase, and draining them would re-run anomaly
        detection on the very rows that triggered the rollback."""
        self._buf.clear()
        self._t_mark = None
        self._wait_s = 0.0

    # -- internals -----------------------------------------------------------
    def _drain_one(self) -> None:
        step, metrics, n_images, substeps = self._buf.popleft()
        self.rec.start("drain")
        host = {k: np.asarray(v) for k, v in metrics.items()}  # D2H sync
        self.host_blocked_s += self.rec.end("drain", step=step)
        now = self.rec.t_end_ns
        self.n_syncs += 1
        dt = max(0.0, (now - self._t_mark) * 1e-9 - self._wait_s)
        self._t_mark = now
        self._wait_s = 0.0
        self.last_step_seconds = dt / substeps
        self.rec.note_time("step", dt, step=step)
        self._emit_rows(step, host, n_images, substeps)
        if self._on_step_seconds is not None:
            self._on_step_seconds(self.last_step_seconds)

    def _emit_rows(self, step: int, metrics: dict, n_images: int,
                   substeps: int) -> None:
        """The drained entry's rows, under one ``emit`` bracket (closed
        also when ``on_row`` raises an anomaly halt)."""
        self.rec.start("emit")
        try:
            self._rows(step, metrics, n_images, substeps)
        finally:
            self.rec.end("emit", step=step)

    def _rows(self, step: int, metrics: dict, n_images: int,
              substeps: int) -> None:
        from theanompi_tpu.obs.numerics import split_numerics

        if substeps == 1:
            plain, nm = split_numerics(metrics)
            self.rec.train_metrics(step, plain, n_images=n_images)
            self.last_drained_step = step
            if self._on_row is not None:
                # row first, hook second: an --on-anomaly halt raised
                # here still leaves the anomalous step's row persisted
                self._on_row(
                    step,
                    {k: float(v) for k, v in plain.items()},
                    {k: float(v) for k, v in nm.items()},
                )
            return
        # fused group: one JSONL row PER SUBSTEP from the stacked
        # metrics (same-resolution loss/LR curves as per-step runs);
        # the group's throughput is attributed to its final row
        host = {k: np.asarray(v) for k, v in metrics.items()}
        for i in range(substeps):
            sub = {k: a[i] for k, a in host.items()}
            plain, nm = split_numerics(sub)
            sub_step = step - substeps + i + 1
            self.rec.train_metrics(
                sub_step, plain,
                n_images=n_images if i == substeps - 1 else 0,
            )
            self.last_drained_step = sub_step
            if self._on_row is not None:
                self._on_row(
                    sub_step,
                    {k: float(v) for k, v in plain.items()},
                    {k: float(v) for k, v in nm.items()},
                )


@jax.jit
def _split(carry):
    """``carry, sub = jax.random.split(carry)`` as ONE program. Key
    derivation is integer arithmetic: the traced split gives the eager
    one's bits, whatever implementation ``carry`` has (a raw key under
    the default, or a typed key a checkpoint wrapped)."""
    pair = jax.random.split(carry)
    return pair[0], pair[1]


class KeyStream:
    """The driver's per-step random keys — the chain ``carry, sub =
    jax.random.split(carry)``, one split a step — made ahead of the
    step that uses them.

    ``carry`` is the COMMITTED carry: the key after the splits of every
    step taken so far, what a checkpoint saves as ``rng=``. Ahead of it
    sits a queue of ready ``(carry_after, sub)`` pairs that
    :meth:`refill` tops up to ``ahead`` (the dispatch unit: 1, or
    ``steps_per_dispatch``). The driver refills right after a step
    program's dispatch returns: the split's host work and its tiny
    device program then run under that step, not in the bare gap
    between two steps. A key that is not ready when :meth:`take` wants
    it (first step, after :meth:`reset`, a group larger than the queue)
    is made on the spot — late, never wrong.
    """

    def __init__(self, carry, ahead: int = 1):
        self.carry = carry
        self.ahead = ahead
        self._ready: deque = deque()
        self.taken = 0
        self.taken_ready = 0  # of those, the keys that were waiting

    def take(self, n: int = 1, stacked: bool = False):
        """The next ``n`` keys (``stacked``: one ``[n, ...]`` array for a
        fused group; else ``n`` must be 1: the key itself), committing
        the carry past them."""
        subs = []
        for _ in range(n):
            if self._ready:
                self.taken_ready += 1
            else:
                self._make()
            self.carry, sub = self._ready.popleft()
            subs.append(sub)
        self.taken += n
        return jnp.stack(subs) if stacked else subs[0]

    def refill(self) -> None:
        """Top the ready queue up to ``ahead`` keys."""
        while len(self._ready) < self.ahead:
            self._make()

    def reset(self, carry) -> None:
        """Continue from a restored carry; what was ready belonged to
        the timeline the restore erased."""
        self.carry = carry
        self._ready.clear()

    @property
    def ready_share(self) -> Optional[float]:
        """Keys taken from the ready queue over keys taken (steady
        state: 1 less the first unit; falling = refill not ahead)."""
        return self.taken_ready / self.taken if self.taken else None

    def _make(self) -> None:
        tip = self._ready[-1][0] if self._ready else self.carry
        self._ready.append(_split(tip))
