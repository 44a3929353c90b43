"""The contract's last line, and the only way out of a run.

``emit`` prints the one JSON object as the final act, flushes, and leaves
through ``os._exit`` so that no atexit handler or daemon thread (loader,
profiler, obs sinks) can write after it.
"""

import json
import os
import sys


def _finite(v):
    """JSON has no NaN or Infinity: a number that is neither reads 1e300."""
    return v if isinstance(v, int) or abs(v) < 1e300 else 1e300


def build(correct, attempted, failed, metrics, device, checks, breakdown=None):
    """``metrics``: name -> (value, unit). ``checks``: name -> (value, limit);
    it comes last in the line, each number beside its limit."""
    line = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {k: {"value": float(v), "unit": u} for k, (v, u) in metrics.items()},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {k: {"value": _finite(v), "limit": lim} for k, (v, lim) in checks.items()}
    return line


def emit(line, rc=0):
    checks = line.get("checks", {})
    for name, c in checks.items():
        print(f"check {name}: value {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(f"correct: {line['correct']}", file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.write(json.dumps(line) + "\n")
    sys.stdout.flush()
    os._exit(rc)


def refuse(message, rc=2):
    """Leave without a result line."""
    print(f"benchmark: {message}", file=sys.stderr)
    sys.stderr.flush()
    sys.stdout.flush()
    os._exit(rc)
