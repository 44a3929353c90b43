"""The driver thread's time a step outside the wait for the device: the
window's seconds less the ``drain`` spans of the window's steps (the blocking
D2H of each step's metrics), over the steps. Fetch, key split, dispatch, row
and loop overhead are what is left.

The spans are addressed by step number, the window by position in
``timings[...]``: position + 1 in a run that did not resume. The reader
holds the ring's ``wait`` durations against ``timings["wait"]`` over the
window and reports nothing where they differ."""

import numpy as np

from harness import spans


def read(ctx):
    rec = ctx["recorder"]
    drain, wait = spans.ring(rec, "drain"), spans.ring(rec, "wait")
    if drain is None or wait is None or not ctx["steps"]:
        return None
    numbers = np.arange(ctx["first_step"] + 1, ctx["last_step"] + 2)
    timed = np.asarray(rec.timings["wait"][ctx["first_step"]:ctx["last_step"] + 1])
    at = np.searchsorted(wait[0], numbers)
    if (len(timed) != len(numbers) or at[-1] >= len(wait[0]) or not np.array_equal(wait[0][at], numbers)
            or not np.allclose(1e-9 * wait[2][at], timed, rtol=0, atol=1e-9)):
        return None
    at = np.searchsorted(drain[0], numbers)
    if at[-1] >= len(drain[0]) or not np.array_equal(drain[0][at], numbers):
        return None
    return 1e3 * (ctx["seconds"] - 1e-9 * float(drain[2][at].sum())) / ctx["steps"]
