"""The loop thread's ``prefill`` + ``upload`` + ``dispatch`` spans, mean over
the window's iterations: what handing inputs and programs to the runtime costs
the host (the admitted prompts' prefill calls with their uploads, the decode
step's five host arrays and their H2D, the decode call until it returns). The
program's own spans (``harness/loop_spans.py``); nothing where it keeps none."""

from harness import loop_spans


def read(ctx):
    return loop_spans.mean_of(ctx, ("prefill", "upload", "dispatch"))
