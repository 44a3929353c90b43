"""The loop thread's ``queue`` + ``admit`` spans, mean over the window's
iterations: the lock, the condition wait and the queue's hand-over between two
iterations, then the admission pass and the expired requests' rejections. The
program's own spans (``harness/loop_spans.py``); nothing where it keeps none."""

from harness import loop_spans


def read(ctx):
    return loop_spans.mean_of(ctx, ("queue", "admit"))
