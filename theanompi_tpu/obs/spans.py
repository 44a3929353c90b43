"""Nestable trace spans with a per-rank JSONL span log.

The reference's whole trace story was the recorder's flat calc/comm
wall-clock brackets (reference: ``lib/recorder.py``; SURVEY.md §5.1).
Spans generalize that to a NESTABLE, named tree — ``checkpoint`` inside
``step``-adjacent driver code, ``h2d`` inside the prefetch producer
thread — written one JSON object per line as each span closes, plus a
run-end ``span_summary`` line with per-kind time fractions.

Span kinds used by the training stack (callers may add their own):
``data_wait``, ``h2d``, ``step``, ``grad_sync``, ``eval``,
``checkpoint`` — plus the nested ``checkpoint_gather`` /
``checkpoint_write`` sub-spans utils/checkpoint.py opens inside a save
(named apart so a synchronous save does not count the same wall time
twice under one kind), and the driver's phases inside a step's
amortized window, in a step's order ``dispatch``, ``key_split`` (the
NEXT unit's keys, split under the step just dispatched), ``drain`` and
``emit`` (depth 1: children of ``step``, so they stay out of the
fractions). The serving loop (serve/decode/engine.py) keeps its own
kinds in memory and writes them as ``span`` lines when it drains: an
iteration's ``queue``, ``admit``, ``prefill``, ``upload``, ``dispatch``,
``drain``, ``harvest`` (each with its ``iteration``) and a request's
``queue_wait`` and ``first_token`` (each with its ``request`` and the
``cause`` iteration).
Schema: tools/check_obs_schema.py.

Clock: ``t0`` is seconds of ``time.time_ns()``, the clock a profiler
trace is written in, and ``dur`` the difference of two reads of it. The
Recorder's brackets (utils/recorder.py) hand their own two stamps to
``begin``/``finish``, so a bracket reads the clock twice whether this
sink is attached or not; a span of the training loop carries the
``step`` it belongs to.

Fraction semantics: the summary's ``fractions`` divide per-kind
EXCLUSIVE top-level time by the recorder's open→close wall clock, and
count only spans opened on the OWNER thread (the driver). Owner-thread
depth-0 spans are sequential by construction, so the fractions sum to
<= 1.0 — the acceptance invariant a concurrent accounting (e.g. adding
the producer thread's overlapping ``h2d`` spans) could not honor.
Spans from other threads still appear as ``span`` lines and in
``totals_s``/``counts``; they are simply excluded from ``fractions``.

A module-level *current recorder* lets deep layers (utils/checkpoint.py,
data/loader.py) open spans without threading a handle through every
signature: ``with obs_span("checkpoint"): ...`` is a no-op unless the
driver installed a recorder.
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager
from typing import Optional

SPAN_KINDS = ("data_wait", "h2d", "step", "grad_sync", "eval", "checkpoint",
              "key_split", "dispatch", "drain", "emit",
              # the serving loop's (serve/decode/engine.py LOOP_SPANS,
              # REQUEST_SPANS; ``dispatch`` and ``drain`` are above)
              "queue", "admit", "prefill", "upload", "harvest",
              "queue_wait", "first_token")


class SpanRecorder:
    def __init__(self, path: str, rank: int = 0):
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        self.path = path
        self.rank = rank
        self._f = open(path, "a")
        self._wlock = threading.Lock()
        self._stacks = threading.local()  # per-thread open-span stack
        self._owner = threading.get_ident()
        self._t_open_ns = time.time_ns()
        # totals over ALL spans / owner-thread depth-0 spans respectively
        self._totals: dict[str, float] = {}
        self._counts: dict[str, int] = {}
        self._owner_top: dict[str, float] = {}
        self._closed = False

    def _stack(self) -> list:
        if not hasattr(self._stacks, "s"):
            self._stacks.s = []
        return self._stacks.s

    # -- explicit begin/finish (the Recorder bracket bridge) ----------------
    def begin(self, name: str, t0_ns: Optional[int] = None,
              under: int = 0) -> dict:
        """Open a span. ``t0_ns``: the caller's own ``time.time_ns()``
        stamp (the Recorder's bracket), else the clock is read here.
        ``under``: levels of parents that are not on the stack (the
        amortized ``step`` a driver phase belongs to)."""
        stack = self._stack()
        token = {
            "name": str(name),
            "t0_ns": time.time_ns() if t0_ns is None else t0_ns,
            "depth": len(stack) + under,
            "thread": threading.get_ident(),
        }
        stack.append(token)
        return token

    def finish(self, token: dict, t1_ns: Optional[int] = None,
               step: Optional[int] = None) -> float:
        stack = self._stack()
        if any(t is token for t in stack):
            # tolerate out-of-order finishes (a bracket leaked across an
            # exception): drop everything opened above the token too
            while stack[-1] is not token:
                stack.pop()
            stack.pop()
        # a token not on the stack (double finish / cross-thread) still
        # records its span but must not disturb other threads' nesting
        t1_ns = time.time_ns() if t1_ns is None else t1_ns
        dur = max(0, t1_ns - token["t0_ns"]) * 1e-9
        name = token["name"]
        rec = {
            "kind": "span",
            "name": name,
            "rank": self.rank,
            "t0": token["t0_ns"] * 1e-9,
            "dur": dur,
            "depth": token["depth"],
        }
        if step is not None:
            rec["step"] = int(step)
        with self._wlock:
            if not self._closed:
                self._f.write(json.dumps(rec) + "\n")
            self._totals[name] = self._totals.get(name, 0.0) + dur
            self._counts[name] = self._counts.get(name, 0) + 1
            if token["depth"] == 0 and token["thread"] == self._owner:
                self._owner_top[name] = self._owner_top.get(name, 0.0) + dur
        return dur

    def note(self, name: str, dur: float, t0_wall: Optional[float] = None,
             step: Optional[int] = None) -> None:
        """Record a span measured EXTERNALLY (no begin/finish pair) —
        the dispatch pipeline's amortized step windows
        (utils/dispatch.py). Attributed to the calling thread at depth
        0, so when the caller is the driver the duration lands in the
        summary ``fractions``; the caller must therefore pass exclusive
        time (overlapping spans like data waits already subtracted) to
        preserve the fractions-sum<=1 invariant. The emitted line is
        flagged ``amortized`` so trace readers can tell attributed time
        from bracketed time (schema: tools/check_obs_schema.py)."""
        dur = float(dur)
        name = str(name)
        rec = {
            "kind": "span",
            "name": name,
            "rank": self.rank,
            "t0": (time.time_ns() * 1e-9 - dur) if t0_wall is None else t0_wall,
            "dur": dur,
            "depth": 0,
            "amortized": True,
        }
        if step is not None:
            rec["step"] = int(step)
        with self._wlock:
            if not self._closed:
                self._f.write(json.dumps(rec) + "\n")
            self._totals[name] = self._totals.get(name, 0.0) + dur
            self._counts[name] = self._counts.get(name, 0) + 1
            if threading.get_ident() == self._owner:
                self._owner_top[name] = self._owner_top.get(name, 0.0) + dur

    @contextmanager
    def span(self, name: str):
        token = self.begin(name)
        try:
            yield token
        finally:
            self.finish(token)

    # -- run-end summary ----------------------------------------------------
    def summary(self) -> dict:
        wall = max((time.time_ns() - self._t_open_ns) * 1e-9, 1e-9)
        with self._wlock:
            fractions = {
                k: min(v / wall, 1.0) for k, v in sorted(self._owner_top.items())
            }
            rec = {
                "kind": "span_summary",
                "rank": self.rank,
                "t0": self._t_open_ns * 1e-9,
                "wall_s": wall,
                "fractions": fractions,
                "totals_s": dict(sorted(self._totals.items())),
                "counts": dict(sorted(self._counts.items())),
            }
        return rec

    def close(self) -> Optional[dict]:
        """Write the summary line and close the file. Idempotent."""
        rec = None
        if not self._closed:
            rec = self.summary()
            with self._wlock:
                self._closed = True
                self._f.write(json.dumps(rec) + "\n")
                self._f.close()
        return rec


# -- module-level current recorder (deep-layer span hook) -------------------

_current: Optional[SpanRecorder] = None


def set_current(rec: Optional[SpanRecorder]) -> None:
    global _current
    _current = rec


def current() -> Optional[SpanRecorder]:
    return _current


@contextmanager
def obs_span(name: str):
    """Open ``name`` on the installed current recorder; no-op (zero
    overhead beyond one global read) when observability is off."""
    rec = _current
    if rec is None:
        yield None
        return
    with rec.span(name) as token:
        yield token
