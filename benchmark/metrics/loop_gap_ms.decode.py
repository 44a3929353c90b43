"""Mean time an iteration the chip is idle between the decode program's last
operation and the first operation of the next program (decode or prefill),
other programs' operations cut out, over the traced iterations: the serving
loop's bare path between two programs, as the device saw it. It must agree
with the sum of ``trace_programs``' gaps "after <decode program>, before ..."
over the same trace, or nothing is reported.

Prints two earlier lines: that gap by the span the loop thread was in (the
program's own spans, ``harness/loop_spans.py``: the ``drain`` span's tail,
``harvest``, ``queue``, ``admit``, ``prefill``, ``upload``, ``dispatch`` up to
the program's start, no span) with the clock's offset and scatter; and the
window's four longest iterations, each with the span that held it (the one
furthest over its own median): the pause record of this layer."""

import numpy as np

from harness import loop_spans


def read(ctx):
    t = loop_spans.traced(ctx)
    if t is None:
        return None
    total = 1e-3 * t["gap_ms"] * t["runs"]
    line = (f"[bench] after a decode program the chip is idle {t['gap_ms']:.3f} ms an iteration over {t['runs']} "
            f"traced iterations ({total:.4f} s; trace_programs' gaps after the decode program: "
            f"{t['programs_gap_s']:.4f} s)")
    if "under_ms" in t:
        named = 1 - t["under_ms"]["no span"] / t["gap_ms"] if t["gap_ms"] else 0.0
        line += ("; of it under " + ", ".join(f"{k} {v:.3f}" for k, v in t["under_ms"].items())
                 + f" ms ({100 * named:.1f} % under a span); span clock: {t['how']}, offset {t['offset_ms']:.3f} ms, "
                 f"program starts scatter {t['scatter_ms']:.3f} ms after their dispatch spans open"
                 + ("" if t["exact"] else " (the offset takes the smallest dispatch-to-start lag as nothing: drain is "
                                          "an upper bound, dispatch a lower one, their sum stands)"))
    else:
        line += f"; no attribution: {t['why']}"
    print(line, flush=True)
    long_ = loop_spans.longest(ctx)
    if long_:
        w = loop_spans.window(ctx)
        print(f"[bench] the window's longest iterations (median period {np.median(w['period']) / 1e6:.3f} ms): "
              + "; ".join(f"{p:.1f} ms at iteration {it} ({calls} prefill calls), {name} {ms:.1f} ms over its median"
                          for p, it, calls, name, ms in long_),
              flush=True)
    if abs(total - t["programs_gap_s"]) > loop_spans.MAX_GAP_REL * t["programs_gap_s"]:
        print("[bench] loop_gap_ms.decode: not reported: the two sums differ by more than 2 %", flush=True)
        return None
    return t["gap_ms"]
