"""Reduction of a JAX profiler trace (``*.xplane.pb``) to the numbers the
per-layer metrics read. Nothing but ``jax.profiler.ProfileData``.

On a TPU each chip is a plane ``/device:TPU:<n>`` whose line ``XLA Ops`` holds
one event per device operation (fusions, custom calls, copies) and whose line
``XLA Modules`` holds one event per executed program. The steady window is
cut to whole steps: from the start of the first traced run of the step
program (the module with the most device time) to the start of its last run,
so that busy and idle shares are over whole periods of the pipeline.
"""

import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
OPCODE = re.compile(r"\s([a-z][a-z0-9\-]*)\(")


def short_name(op):
    """The trace prints an op as its whole HLO line, ``%name = shape
    opcode(operands), ...``: keep ``%name opcode``."""
    name, _, rest = op.partition(" = ")
    m = OPCODE.search(" " + rest)
    return f"{name} {m.group(1)}" if m else name


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            return [(e.name, float(e.start_ns), float(e.duration_ns)) for e in line.events]
    return []


def _union(intervals):
    """Merged, sorted [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(events, lo, hi):
    return [(n, max(s, lo), min(s + d, hi) - max(s, lo)) for n, s, d in events
            if s + d > lo and s < hi]


def reduce_plane(ops, modules):
    """One chip's reduction over whole steps; None where no step repeats."""
    per_module = defaultdict(float)
    for n, _, d in modules:
        per_module[n] += d
    if not per_module:
        return None
    step_module = max(per_module, key=per_module.get)
    starts = sorted(s for n, s, _ in modules if n == step_module)
    if len(starts) < 2:
        return None
    lo, hi = starts[0], starts[-1]
    ops = _clip(ops, lo, hi)
    busy = _union([(s, s + d) for _, s, d in ops])
    by_name = defaultdict(float)
    for n, _, d in ops:
        by_name[short_name(n)] += d
    gaps = []
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    bounds = set(starts)
    for a, b in zip(edges[::2], edges[1::2]):
        if b > a:
            # a gap that holds a step program's start is between two steps:
            # the host is fetching, dispatching or draining; any other gap is
            # inside one program
            between = any(a <= s <= b for s in bounds)
            gaps.append(("between steps (host: fetch, dispatch, drain)" if between
                         else "inside the step program", b - a))
    return {
        "step_module": step_module, "steps": len(starts) - 1,
        "window_s": (hi - lo) * 1e-9, "busy_s": sum(e - s for s, e in busy) * 1e-9,
        "op_s": {k: v * 1e-9 for k, v in by_name.items()},
        "gaps": [(n, d * 1e-9) for n, d in gaps],
    }


def reduce(path, chips=1):
    """-> the reduction averaged over the chips used, or None where the trace
    holds no device plane with a repeating step."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            r = reduce_plane(_events(plane, OPS_LINE), _events(plane, MODULES_LINE))
            if r is not None:
                planes.append(r)
    if not planes:
        return None
    planes = planes[:chips]
    n = len(planes)
    out = dict(planes[0])
    out["busy_s"] = sum(p["busy_s"] for p in planes) / n
    out["window_s"] = sum(p["window_s"] for p in planes) / n
    merged = defaultdict(float)
    for p in planes:
        for k, v in p["op_s"].items():
            merged[k] += v / n
    out["op_s"] = dict(merged)
    return out


def breakdown(reduced):
    """The contract's ``breakdown``: the device operations that took most
    time, and the idle gaps by what the host was doing, ten of each at most,
    in seconds over the traced window."""
    ops = sorted(reduced["op_s"].items(), key=lambda kv: -kv[1])[:10]
    by_name = defaultdict(float)
    for name, d in reduced["gaps"]:
        by_name[name] += d
    longest = sorted(reduced["gaps"], key=lambda g: -g[1])[:8]
    gaps = [[f"all: {k}", v] for k, v in sorted(by_name.items(), key=lambda kv: -kv[1])[:2]]
    gaps += [[f"longest: {n}", d] for n, d in longest]
    return {"device_ops": [[k, v] for k, v in ops], "idle_gaps": gaps[:10]}
