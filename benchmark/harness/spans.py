"""The program's spans laid over the device trace: what the host was doing
while the chip waited between two runs of the step program.

The program keeps its driver-thread brackets (``wait``, ``key_split``,
``dispatch``, ``drain``, ``emit``) in ``Recorder.span_rings``: start and
duration in nanoseconds of ``time.time_ns()``, by step number. A
``*.xplane.pb`` counts an event's ``start_ns`` from the
``profile_start_time`` (the same clock) of its ``Task Environment`` plane,
so the two lie on one clock with no host event in the trace. Where that
cannot be shown (no such plane, or the step programs do not follow their
``dispatch`` spans), a constant offset is estimated instead, and nothing is
attributed where the programs' lags behind their ``dispatch`` spans scatter by
more than ``MAX_SCATTER_NS`` (interquartile, over the traced steps).

A program without the rings (any commit before they were added) gives the
scalar from the trace alone and no attribution; nothing here raises for it.
"""

import glob
import os
import statistics

import numpy as np

from harness import manifest, trace

DRIVER_SPANS = ("drain", "emit", "wait", "key_split", "dispatch")
# the lag from a dispatch span's opening to its program's start scatters
# (interquartile) 0.12-0.19 ms in a fast process and 0.26 in a slow one, 0.87 in
# the one trace whose clocks did not hold together (my chip runs, PR 26)
MAX_SCATTER_NS = 400_000
SLACK_NS = 1_000_000


def trace_file(cell_name):
    """The traced run's ``*.xplane.pb``, by the driver's own rule."""
    files = sorted(glob.glob(os.path.join(manifest.ROOT, ".bench_work", cell_name, "trace",
                                          "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def read_planes(path):
    """-> (ops, modules, origin_ns) of the first chip: events as
    ``trace._events`` gives them, and the clock reading that their
    ``start_ns`` count from (None where the trace does not say)."""
    from jax.profiler import ProfileData

    ops = modules = origin = None
    for plane in ProfileData.from_file(path).planes:
        if ops is None and trace.DEVICE_PLANE.match(plane.name):
            ops = trace._events(plane, trace.OPS_LINE)
            modules = trace._events(plane, trace.MODULES_LINE)
        elif plane.name == "Task Environment":
            origin = dict(plane.stats).get("profile_start_time")
    return ops or [], modules or [], None if origin is None else int(origin)


def step_intervals(ops, modules):
    """-> (starts, intervals): the starts of the runs of the step program
    (the module with the most device time), and one entry per pair of
    consecutive runs: the idle ``segments`` [s, e) between the earlier run's
    last operation and the later run's first, other programs' operations
    cut out, and their sum ``idle_ns``."""
    total = {}
    for n, _, d in modules:
        total[n] = total.get(n, 0.0) + d
    if not total:
        return [], []
    step = max(total, key=total.get)
    runs = sorted((s, s + d) for n, s, d in modules if n == step)
    op_s = np.array([s for _, s, _ in ops], np.float64)
    op_e = op_s + np.array([d for _, _, d in ops], np.float64)
    first, last = [], []
    for lo, hi in runs:
        inside = (op_s >= lo) & (op_e <= hi)
        first.append(op_s[inside].min() if inside.any() else lo)
        last.append(op_e[inside].max() if inside.any() else hi)
    out = []
    for i in range(len(runs) - 1):
        a, b = float(last[i]), float(first[i + 1])
        between = (op_e > a) & (op_s < b)  # the small programs' operations
        busy = trace._union([(max(s, a), min(e, b)) for s, e in zip(op_s[between], op_e[between])])
        edges = [a] + [x for iv in busy for x in iv] + [b]
        segments = [(s, e) for s, e in zip(edges[::2], edges[1::2]) if e > s]
        out.append({"segments": segments, "idle_ns": sum(e - s for s, e in segments)})
    return [lo for lo, _ in runs], out


def ring(recorder, name):
    """-> (steps, t0_ns, dur_ns) arrays of one span name, by step; None where
    the program keeps no such spans."""
    r = getattr(recorder, "span_rings", {}).get(name)
    return None if r is None else r.held()


def _iqr(values):
    if len(values) < 2:
        return 0.0
    q = statistics.quantiles(values, n=4)
    return q[2] - q[0]


def clock_offset(starts, dispatch_t0, same_origin):
    """What to add to a trace time to get the time of the spans.

    ``starts``: the traced step programs' starts (trace time);
    ``dispatch_t0``: every ``dispatch`` span's opening, in step order, less
    the trace's origin where ``same_origin`` (else less any constant).
    -> (offset_ns, scatter_ns, how) or None. The programs are laid against a
    run of consecutive dispatches: on the trace's own origin the run that
    opened last before each program started (``SLACK_NS`` allowed: the
    profiler sets the device's clock against the host's anew in every
    session, to some tenths of a millisecond); without one, the run whose
    lags scatter least. A program cannot start before its dispatch span
    opens: where one seems to, the offset moves the trace by that much (all
    of the smallest lag, without an origin). Nothing where the lags' scatter
    (interquartile) passes ``MAX_SCATTER_NS``."""
    starts, d = np.asarray(starts, np.float64), np.asarray(dispatch_t0, np.float64)
    n = len(starts)
    if n < 2 or len(d) < n:
        return None

    def lags_of(k):
        lags = starts - d[k:k + n]
        return _iqr(list(lags)), lags

    if same_origin:
        k = int(np.median(np.searchsorted(d, starts + SLACK_NS, side="right") - 1 - np.arange(n)))
        if 0 <= k <= len(d) - n:
            scatter, lags = lags_of(k)
            if scatter <= MAX_SCATTER_NS and lags.min() >= -SLACK_NS:
                if lags.min() >= 0:
                    return 0.0, scatter, "the trace's own origin"
                return (float(-lags.min()), scatter,
                        "the trace's own origin, moved so that no program starts before its dispatch span opens")
    scatter, lags = min((lags_of(k) for k in range(len(d) - n + 1)), key=lambda found: found[0])
    if scatter > MAX_SCATTER_NS:
        return None
    return float(-lags.min()), scatter, "estimated from the step programs' starts"


def attribute(intervals, spans, offset_ns):
    """Nanoseconds of the intervals' idle time under each span name, and
    under none. ``spans``: {name: (t0_ns, dur_ns) arrays} on the spans' clock."""
    under = dict.fromkeys(spans, 0.0)
    idle = 0.0
    for iv in intervals:
        for s, e in iv["segments"]:
            s, e = s + offset_ns, e + offset_ns
            idle += e - s
            for name, (t0, dur) in spans.items():
                under[name] += float(np.clip(np.minimum(t0 + dur, e) - np.maximum(t0, s), 0, None).sum())
    under["no span"] = idle - sum(under.values())
    return under


def step_gaps(ctx):
    """The reduction behind ``step_gap_ms.train``, made once a run:
    ``gap_ms`` (mean idle time between two runs of the step program),
    ``steps`` (pairs of runs), and, where the program keeps spans and the
    clocks can be laid together, ``under_ms`` (ms a step by span name) with
    ``offset_ms`` (from the trace's own origin, where it has one),
    ``scatter_ms`` and ``how``."""
    if "_step_gaps" in ctx:
        return ctx["_step_gaps"]
    out = None
    path = trace_file(ctx["cell"]["name"]) if ctx.get("trace") else None
    if path:
        ops, modules, origin = read_planes(path)
        starts, intervals = step_intervals(ops, modules)
        if intervals:
            n = len(intervals)
            out = {"steps": n, "gap_ms": 1e-6 * sum(iv["idle_ns"] for iv in intervals) / n}
            out.update(laid_over(starts, intervals, ctx["recorder"], origin) or {})
    ctx["_step_gaps"] = out
    return out


def laid_over(starts, intervals, recorder, origin_ns):
    """The attribution's part of ``step_gaps``, or None."""
    held = {name: ring(recorder, name) for name in DRIVER_SPANS}
    if held["dispatch"] is None or not len(held["dispatch"][1]):
        return None
    # whole nanoseconds less a base first: a float64 holds no 1.8e18 exactly
    base = int(held["dispatch"][1][0]) if origin_ns is None else origin_ns
    spans = {name: ((h[1] - base).astype(np.float64), h[2].astype(np.float64))
             for name, h in held.items() if h is not None}
    found = clock_offset(starts, spans["dispatch"][0], origin_ns is not None)
    if not found:
        return None
    offset, scatter, how = found
    n = len(intervals)
    return {"under_ms": {k: 1e-6 * v / n for k, v in attribute(intervals, spans, offset).items()},
            "offset_ms": 1e-6 * offset, "scatter_ms": 1e-6 * scatter, "how": how}
