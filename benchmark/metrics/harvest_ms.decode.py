"""The loop thread's ``harvest`` span (tokens appended, futures resolved,
evictions, gauges, the periodic record), mean over the window's iterations.
The program's own span (``harness/loop_spans.py``): nothing where it keeps none
or where its iteration numbers do not map onto the probe's rows."""

from harness import loop_spans


def read(ctx):
    return loop_spans.mean_of(ctx, ("harvest",))
