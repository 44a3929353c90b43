"""Median ``queue_wait`` span (``submit()`` to the admission that gave the
request a slot) of the requests whose first token came in one of the window's
iterations: the part of a first token that is neither its prefill nor its
step. The program's own spans (``harness/loop_spans.py``); nothing where it
keeps none.

Prints one earlier line: the median ``first_token`` span beside it, their sum
and the median of the requests' own sums (which is the window's
``ttft_p50_ms``: the same requests, the same stamps), the share admitted at
their first chance, and where in the loop the submissions landed."""

import numpy as np

from harness import loop_spans


def read(ctx):
    r = loop_spans.requests(ctx)
    if r is None:
        return None
    wait, first = float(np.median(r["wait_ms"])), float(np.median(r["first_ms"]))
    chance = "not known" if r["first_chance"] is None else f"{100 * r['first_chance']:.1f} %"
    print(f"[bench] first tokens in the window: {len(r['wait_ms'])} requests; queue_wait median {wait:.3f} ms "
          f"(p95 {np.percentile(r['wait_ms'], 95):.3f}), first_token median "
          f"{first:.3f} ms, together {wait + first:.3f}; the requests' own sums: median "
          f"{np.median(r['ttft_ms']):.3f} ms (the window's ttft_p50_ms); admitted by the first iteration "
          f"whose queue span closed after their submission: {chance}; the loop thread was, at the submission, in: "
          + ", ".join(f"{k} {100 * v:.1f} %" for k, v in r["landed"].items() if v), flush=True)
    return wait
