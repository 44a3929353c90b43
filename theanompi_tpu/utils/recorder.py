"""Recorder: per-iteration timing split + train/val metric history.

Rebuild of the reference's observability layer (reference:
``lib/recorder.py`` — ``Recorder`` with ``start()``/``end('calc'|'comm')``
wall-clock brackets, train cost/error accumulation, val cost/error/top-5,
periodic console prints, pickled history; SURVEY.md §5.1, §5.5). The API
is kept because it was good; additions over the reference:

- JSONL event log (machine-readable) instead of pickle-only;
- images/sec and cumulative epoch timing (the BASELINE.json metric);
- correct device-timing semantics for XLA: an async dispatch means
  host-side brackets measure nothing unless the caller synchronizes —
  ``end()`` optionally blocks on a ``jax.Array`` for honest splits;
- optional delegation to the obs subsystem (ISSUE 1): pass ``registry``
  (obs/metrics.py) and the brackets feed timing histograms + last-value
  gauges; pass ``spans`` (obs/spans.py) and every ``start``/``end``
  bracket ALSO opens/closes a trace span (wait -> ``data_wait``,
  comm -> ``grad_sync``, others by name) — the Recorder stays the
  single emission point, the obs files the machine-readable sinks;
- ONE span store (ISSUE 26; its own class since ISSUE 37, so that the
  serving loop brackets through the same code): :class:`SpanStore`, which
  the Recorder is. A bracket closed with a step number keeps
  its start and duration in :class:`SpanRing`, in memory, always on.
  Stamps are integer nanoseconds of ``time.time_ns()``, the clock the
  profiler writes a ``*.xplane.pb`` in (an event's ``start_ns`` there
  counts from the ``profile_start_time`` of the ``Task Environment``
  plane), so a reader lays the spans over a device trace that holds no
  host events. A bracket reads the clock twice: the span sink takes
  the same two stamps, and the dispatcher's ``host_blocked_s`` is the
  sum of the ``drain`` brackets. Each bracket also opens a
  ``jax.profiler.TraceAnnotation`` of its name: an operator's trace
  with the host tracer on shows the phases above the device's row.

Note on calc/comm split: in the reference these were separate host
phases (Theano call, then MPI). Here the collective is fused INSIDE the
compiled step, so per-phase brackets cannot separate them; the honest
equivalents are ``step`` (whole-iteration device time) plus
``jax.profiler`` traces for the in-step breakdown. The bracket API
remains for the host-visible phases (data wait / step / eval).
"""

from __future__ import annotations

import json
import os
import pickle
import time
from collections import defaultdict
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

SPAN_RING_STEPS = 65536
# how long an armed capture waits for a profiler session another holds
PROFILE_BUSY_WAIT_S = 10.0


class SpanRing:
    """One bracket category's spans of the last ``capacity`` steps:
    start and duration in integer nanoseconds, addressed by step number
    (slot ``step % capacity``; an older step's slot is overwritten, so a
    long run holds constant memory). ``cause``: the number of the span
    that caused this one (a request's span names the loop iteration that
    admitted or answered it), -1 where none."""

    __slots__ = ("capacity", "steps", "t0_ns", "dur_ns", "cause")

    def __init__(self, capacity: int = SPAN_RING_STEPS):
        self.capacity = int(capacity)
        self.steps = np.full(self.capacity, -1, np.int64)
        self.t0_ns = np.zeros(self.capacity, np.int64)
        self.dur_ns = np.zeros(self.capacity, np.int64)
        self.cause = np.full(self.capacity, -1, np.int64)

    def put(self, step: int, t0_ns: int, dur_ns: int, cause: int = -1) -> None:
        i = step % self.capacity
        self.steps[i] = step
        self.t0_ns[i] = t0_ns
        self.dur_ns[i] = dur_ns
        self.cause[i] = cause

    def get(self, step: int) -> Optional[tuple[int, int]]:
        """``(t0_ns, dur_ns)`` of ``step``'s span, or None where the ring
        never held it or has since overwritten it."""
        i = step % self.capacity
        if self.steps[i] != step:
            return None
        return int(self.t0_ns[i]), int(self.dur_ns[i])

    def held(self) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(steps, t0_ns, dur_ns)`` of every span held, by step (their
        causes: ``cause[steps % capacity]``)."""
        idx = np.flatnonzero(self.steps >= 0)
        idx = idx[np.argsort(self.steps[idx], kind="stable")]
        return self.steps[idx], self.t0_ns[idx], self.dur_ns[idx]

    def durations(self, lo: int, hi: int) -> np.ndarray:
        """``dur_ns`` of the spans numbered ``lo`` to ``hi - 1`` that are
        still held (a reader's window, without a pass over the ring)."""
        numbers = np.arange(max(0, lo), hi)
        at = numbers % self.capacity
        return self.dur_ns[at][self.steps[at] == numbers]


class CountRing:
    """A whole number a step (how many prefill calls an iteration made),
    kept beside the spans and addressed as :class:`SpanRing` is."""

    __slots__ = ("capacity", "steps", "values")

    def __init__(self, capacity: int = SPAN_RING_STEPS):
        self.capacity = int(capacity)
        self.steps = np.full(self.capacity, -1, np.int64)
        self.values = np.zeros(self.capacity, np.int64)

    def put(self, step: int, value: int) -> None:
        i = step % self.capacity
        self.steps[i] = step
        self.values[i] = value

    def get(self, step: int) -> Optional[int]:
        i = step % self.capacity
        return int(self.values[i]) if self.steps[i] == step else None


class SpanStore:
    """Brackets of one thread, kept by number: the one span
    implementation of the package. ``enter(category)`` reads
    ``clock_ns`` and opens a ``TraceAnnotation`` of the category's name;
    ``leave(category, number)`` reads the clock again and keeps start and
    duration in the category's :class:`SpanRing` under ``number`` (a
    training step, a serving iteration). Rings are made on first use
    and stay at ``SPAN_RING_STEPS``; no lock, no I/O, always on.
    ``put`` keeps a span measured elsewhere (a request's, from stamps
    it already carries), ``count`` a whole number beside a span's.

    A store made with a ``name`` is found again by :func:`span_store`
    after its owner has gone: a reader outside the program (the
    benchmark's per-layer metrics) asks by name."""

    clock_ns = staticmethod(time.time_ns)  # the profiler's clock

    def __init__(self, name: Optional[str] = None):
        # open brackets: category -> (t0_ns, trace annotation)
        self._open: dict[str, tuple] = {}
        self.span_rings: dict[str, SpanRing] = {}
        self.counts: dict[str, CountRing] = {}
        self.t_end_ns = 0  # the stamp that closed the newest bracket
        if name is not None:
            _STORES[name] = self

    def enter(self, category: str) -> int:
        # with no profiler session, entering one costs an atomic read
        ann = TraceAnnotation(category)
        ann.__enter__()
        t0 = self.clock_ns()
        self._open[category] = (t0, ann)
        return t0

    def leave(self, category: str,
              number: Optional[int] = None) -> Optional[tuple[int, int]]:
        """Close ``category``'s bracket -> ``(t0_ns, t1_ns)``, or None
        where none is open."""
        t1 = self.clock_ns()
        opened = self._open.pop(category, None)
        if opened is None:
            return None
        t0, ann = opened
        ann.__exit__(None, None, None)
        self.t_end_ns = t1
        if number is not None:
            # the wall clock may be set back
            self.put(category, number, t0, max(0, t1 - t0))
        return t0, t1

    def abandon(self) -> None:
        """Close every open bracket and keep none (an iteration that
        raised between an ``enter`` and its ``leave``)."""
        while self._open:
            _, (_, ann) = self._open.popitem()
            ann.__exit__(None, None, None)

    def put(self, category: str, number: int, t0_ns: int, dur_ns: int,
            cause: int = -1) -> None:
        ring = self.span_rings.get(category)
        if ring is None:
            ring = self.span_rings[category] = SpanRing()
        ring.put(number, t0_ns, dur_ns, cause)

    def count(self, name: str, number: int, value: int) -> None:
        """Keep the whole number ``value`` under ``number``."""
        ring = self.counts.get(name)
        if ring is None:
            ring = self.counts[name] = CountRing()
        ring.put(number, value)

    def counted(self, name: str, number: int) -> Optional[int]:
        ring = self.counts.get(name)
        return None if ring is None else ring.get(number)

    def span(self, category: str, step: int) -> Optional[tuple[int, int]]:
        """``(t0_ns, dur_ns)`` of ``category``'s bracket of ``step``, on
        the ``clock_ns`` clock; None where none is held."""
        ring = self.span_rings.get(category)
        return None if ring is None else ring.get(step)


_STORES: dict[str, SpanStore] = {}


def span_store(name: str) -> Optional[SpanStore]:
    """The newest :class:`SpanStore` made under ``name`` in this process
    (``"decode"``: the serving loop's; a router's replicas are
    ``"decode/<replica_id>"``), or None."""
    return _STORES.get(name)


class Recorder(SpanStore):
    # bracket category -> obs span kind (obs/spans.py SPAN_KINDS); the
    # reference's 'comm' bracket is the gradient exchange, hence grad_sync
    SPAN_NAMES = {"wait": "data_wait", "comm": "grad_sync"}
    # the driver's phases inside a step's amortized window: in the span
    # sink they are children (depth 1) of the ``step`` span, which keeps
    # span_summary's top-level fractions disjoint (``wait`` stays beside
    # it at depth 0: the amortized step already excludes the waits)
    STEP_CHILDREN = frozenset({"key_split", "dispatch", "drain", "emit"})

    def __init__(
        self,
        rank: int = 0,
        print_freq: int = 40,
        save_dir: Optional[str] = None,
        run_name: str = "run",
        tensorboard: bool = False,
        registry=None,
        spans=None,
    ):
        self.rank = rank
        self.print_freq = print_freq
        self.save_dir = save_dir
        self.run_name = run_name
        self.registry = registry  # obs.MetricsRegistry or None
        self.spans = spans  # obs.SpanRecorder or None
        SpanStore.__init__(self)
        self._tokens: dict[str, dict] = {}  # open brackets' sink tokens
        self.timings: dict[str, list[float]] = defaultdict(list)
        self.history: dict[str, list] = defaultdict(list)
        self.epoch_start: Optional[float] = None
        self._jsonl = None
        self._tb = None
        if save_dir:
            os.makedirs(save_dir, exist_ok=True)
            self._jsonl = open(os.path.join(save_dir, f"{run_name}.jsonl"), "a")
        if tensorboard and save_dir:
            # optional TensorBoard scalars (SURVEY.md §5.5 "TPU
            # equivalent": JSONL + optional TensorBoard) — soft
            # dependency, JSONL remains the source of truth
            try:
                from tensorboardX import SummaryWriter

                self._tb = SummaryWriter(
                    os.path.join(save_dir, "tb", f"{run_name}_rank{rank}")
                )
            except Exception as e:  # broken installs raise beyond ImportError
                print(
                    f"[rank {rank}] tensorboard=True but tensorboardX is "
                    f"unavailable ({type(e).__name__}: {e}) — JSONL/pickle "
                    "history only",
                    flush=True,
                )

    # -- XLA trace capture ---------------------------------------------------
    # The reference's calc/comm split came from host brackets around
    # separate Theano/MPI phases (lib/recorder.py). Here the collective
    # is fused inside one XLA program, so the in-step breakdown comes
    # from a jax.profiler device trace instead (SURVEY.md §5.1 "TPU
    # equivalent"): view with tensorboard/xprof to read the comm vs
    # compute fraction of each step.
    def enable_profile(
        self, profile_dir: str, start_offset: int = 2, n_steps: int = 4
    ) -> None:
        """Arm a ``jax.profiler`` trace capture of ``n_steps`` steps,
        starting ``start_offset`` steps after the FIRST
        :meth:`profile_tick` (relative, so resumed runs still skip the
        recompile/warmup steps)."""
        self._prof = {
            "dir": profile_dir,
            "offset": int(start_offset),
            "n": int(n_steps),
            "state": "armed",
            "base": None,
            "started_at": None,
        }

    def profile_tick(self, step: int) -> None:
        """Start/stop the armed trace based on the global step count.
        Call once per training step, before dispatching it."""
        p = getattr(self, "_prof", None)
        if p is None or p["state"] == "done":
            return
        if p["state"] == "armed":
            if p["base"] is None:
                p["base"] = step
            if step >= p["base"] + p["offset"]:
                import jax

                os.makedirs(p["dir"], exist_ok=True)
                try:
                    jax.profiler.start_trace(p["dir"])
                except RuntimeError as e:
                    # the process has ONE profiler session and another
                    # capture holds it (a post-mortem trace the watchdog
                    # or the flight recorder armed: obs/health.py, 2 s):
                    # stay armed and try again at the next step, for
                    # PROFILE_BUSY_WAIT_S; any other failure, or a
                    # session held longer, is the operator's to see
                    now = time.monotonic()
                    since = p.setdefault("busy_since", now)
                    if ("already been started" not in str(e)
                            or now - since > PROFILE_BUSY_WAIT_S):
                        p["state"] = "done"
                        raise
                    if since == now:
                        print(f"[rank {self.rank}] profile capture waits: "
                              f"{e}", flush=True)
                    return
                p["state"] = "tracing"
                p["started_at"] = step
        elif p["state"] == "tracing" and step >= p["started_at"] + p["n"]:
            self._profile_stop()

    def _profile_stop(self, reason: str = "") -> None:
        p = self._prof
        import jax

        try:
            jax.profiler.stop_trace()
        finally:  # whatever stop_trace raised, this capture is over
            p["state"] = "done"
        print(
            f"[rank {self.rank}] wrote XLA trace to {p['dir']}"
            + (f" ({reason})" if reason else "")
            + " (view: tensorboard --logdir)",
            flush=True,
        )

    # -- timing brackets (reference API) ------------------------------------
    def start(self, category: str = "calc") -> None:
        t0 = self.enter(category)
        if self.spans is not None:
            self._tokens[category] = self.spans.begin(
                self.SPAN_NAMES.get(category, category), t0_ns=t0,
                under=1 if category in self.STEP_CHILDREN else 0,
            )

    def end(self, category: str = "calc", sync=None,
            step: Optional[int] = None) -> float:
        """Close a bracket. Pass a ``jax.Array`` (e.g. the loss) as
        ``sync`` to block until the device work really finished —
        without it the bracket only measures dispatch. Pass the number
        of the step the bracket belongs to as ``step`` and the span is
        kept in the category's :class:`SpanRing` (``span(category,
        step)`` reads it back).

        An ``end`` without a matching ``start`` warns (naming the
        category) and returns 0.0 instead of raising — an accounting
        slip must not kill a training run."""
        if sync is not None:
            try:
                sync.block_until_ready()
            except AttributeError:
                pass
        closed = self.leave(category, step)
        if closed is None:
            import warnings

            warnings.warn(
                f"Recorder.end({category!r}) without a matching "
                f"start({category!r}); returning 0.0",
                RuntimeWarning, stacklevel=2,
            )
            return 0.0
        t0, t1 = closed
        dt = max(0, t1 - t0) * 1e-9
        self.timings[category].append(dt)
        token = self._tokens.pop(category, None)
        if token is not None and self.spans is not None:
            self.spans.finish(token, t1_ns=t1, step=step)
        if self.registry is not None:
            name = self.SPAN_NAMES.get(category, category)
            self.registry.histogram(
                f"tmpi_{name}_seconds",
                help=f"Recorder '{category}' bracket wall time",
            ).observe(dt)
        return dt

    def note_time(self, category: str, dt: float,
                  step: Optional[int] = None) -> float:
        """Record an externally measured bracket duration without a
        ``start``/``end`` pair — the dispatch pipeline's amortized
        spaced-sync timing (utils/dispatch.py), whose window closes with
        the bracket closed last (``t_end_ns``: no clock is read here).
        Feeds the same sinks a bracket would: the timings list, the obs
        histogram, and an ``amortized``-flagged span line (the duration
        must already EXCLUDE overlapping owner-thread spans, e.g. data
        waits, so the span summary's fraction invariant holds)."""
        dt = float(dt)
        self.timings[category].append(dt)
        name = self.SPAN_NAMES.get(category, category)
        if self.spans is not None:
            self.spans.note(
                name, dt, step=step,
                t0_wall=self.t_end_ns * 1e-9 - dt if self.t_end_ns else None,
            )
        if self.registry is not None:
            self.registry.histogram(
                f"tmpi_{name}_seconds",
                help=f"Recorder '{category}' bracket wall time",
            ).observe(dt)
        return dt

    # -- metric accumulation -------------------------------------------------
    def train_metrics(self, step: int, metrics: dict, n_images: int = 0) -> None:
        rec = {k: float(v) for k, v in metrics.items()}
        rec["step"] = int(step)
        if n_images and self.registry is not None:
            self.registry.counter(
                "tmpi_images_total", help="training examples consumed"
            ).inc(n_images)
        if n_images and self.timings.get("step"):
            rec["images_per_sec"] = n_images / self.timings["step"][-1]
        self.history["train"].append(rec)
        self._emit("train", rec)
        if self.print_freq and len(self.history["train"]) % self.print_freq == 0:
            self._print_train(rec)

    def val_metrics(self, epoch: int, metrics: dict) -> None:
        rec = {k: float(v) for k, v in metrics.items()}
        rec["epoch"] = int(epoch)
        self.history["val"].append(rec)
        self._emit("val", rec)
        loss = rec.get("loss", float("nan"))
        msg = f"[rank {self.rank}] epoch {epoch} val: loss={loss:.4f}"
        # print only the metrics the engine produced (LM engines report
        # loss only; classifiers add error/top5)
        if "error" in rec:
            msg += f" err={rec['error']:.4f}"
        if "top5_error" in rec:
            msg += f" top5_err={rec['top5_error']:.4f}"
        print(msg, flush=True)

    # -- epoch accounting ----------------------------------------------------
    def start_epoch(self) -> None:
        self.epoch_start = time.perf_counter()

    def end_epoch(self, epoch: int, n_images: int = 0) -> float:
        p = getattr(self, "_prof", None)
        if p is not None and p["state"] == "tracing":
            # never let the trace run through validation/checkpoint I/O —
            # it exists to read the train-step comm/compute split
            self._profile_stop("stopped at epoch end")
        dt = time.perf_counter() - (self.epoch_start or time.perf_counter())
        rec = {"epoch": int(epoch), "seconds": dt}
        if n_images:
            rec["images_per_sec"] = n_images / dt
        self.history["epoch"].append(rec)
        self._emit("epoch", rec)
        print(
            f"[rank {self.rank}] epoch {epoch} done in {dt:.1f}s"
            + (f" ({rec['images_per_sec']:.0f} img/s)" if n_images else ""),
            flush=True,
        )
        return dt

    # -- summaries -----------------------------------------------------------
    def mean_time(self, category: str, last_n: Optional[int] = None) -> float:
        ts = self.timings.get(category, [])
        if not ts:
            return 0.0
        return float(np.mean(ts[-last_n:] if last_n else ts))

    def _print_train(self, rec: dict) -> None:
        parts = [f"step {rec['step']}"]
        for k in ("loss", "error", "lr"):
            if k in rec:
                parts.append(f"{k}={rec[k]:.4f}")
        for cat in ("wait", "step"):
            if self.timings.get(cat):
                parts.append(f"{cat}={1000*self.mean_time(cat, self.print_freq):.1f}ms")
        if "images_per_sec" in rec:
            parts.append(f"{rec['images_per_sec']:.0f} img/s")
        print(f"[rank {self.rank}] " + " ".join(parts), flush=True)

    def _emit(self, kind: str, rec: dict) -> None:
        if self._jsonl:
            self._jsonl.write(json.dumps({"kind": kind, **rec}) + "\n")
            self._jsonl.flush()
        if self.registry is not None:
            # last-value gauges per metric (tmpi_train_loss, tmpi_val_error,
            # tmpi_epoch_seconds, ...) so obs snapshots carry the training
            # curve's current point next to the comm/health telemetry;
            # images ride a counter (throughput = rate(tmpi_images_total))
            for k, v in rec.items():
                if k in ("step", "epoch") or not isinstance(v, float):
                    continue
                if k == "images_per_sec":
                    self.registry.gauge(
                        "tmpi_images_per_sec", help="recent throughput"
                    ).set(v)
                else:
                    self.registry.gauge(f"tmpi_{kind}_{k}").set(v)
        if self._tb is not None:
            x = rec.get("step", rec.get("epoch", 0))
            for k, v in rec.items():
                if k not in ("step", "epoch") and isinstance(v, float):
                    self._tb.add_scalar(f"{kind}/{k}", v, int(x))

    def save(self, path: Optional[str] = None) -> None:
        """Pickle the full history (reference: ``Recorder.save`` pickled
        its lists for offline plotting)."""
        if path is None:
            if not self.save_dir:
                return
            path = os.path.join(self.save_dir, f"{self.run_name}_history.pkl")
        with open(path, "wb") as f:
            pickle.dump(
                {"history": dict(self.history), "timings": dict(self.timings)}, f
            )

    @staticmethod
    def load_history(path: str) -> dict:
        with open(path, "rb") as f:
            return pickle.load(f)

    def close(self) -> None:
        p = getattr(self, "_prof", None)
        if p is not None and p["state"] == "tracing":  # run ended mid-capture
            self._profile_stop("run ended mid-capture")
        elif p is not None and p["state"] == "armed":
            print(
                f"[rank {self.rank}] WARNING: profile was armed but the run "
                f"ended before the capture window opened — no trace in "
                f"{p['dir']} (need > {p['offset']} steps)",
                flush=True,
            )
            p["state"] = "done"
        if self._jsonl:
            self._jsonl.close()
            self._jsonl = None
        if self._tb is not None:
            self._tb.close()
            self._tb = None
