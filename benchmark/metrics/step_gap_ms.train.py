"""Mean time a step the chip is idle between the last operation of one run
of the step program and the first operation of its next run, over the traced
whole steps (the device time of the small programs in between taken out):
the host's bare path between two steps, as the device saw it.

Prints one earlier line a run: that idle time by what the driver thread was
doing in it (the program's spans, ``harness/spans.py``), and how it sits
against the whole traced idle time that ``device_idle.train`` reports."""

from harness import spans


def read(ctx):
    g = spans.step_gaps(ctx)
    if g is None:
        return None
    t = ctx["trace"]
    line = (f"[bench] between two runs of the step program the chip is idle {g['gap_ms']:.3f} ms a step "
            f"over {g['steps']} traced steps ({1e-3 * g['gap_ms'] * g['steps']:.4f} s of "
            f"{t['window_s'] - t['busy_s']:.4f} s idle in the traced window)")
    if "under_ms" in g:
        line += ("; of it under " + ", ".join(f"{k} {v:.3f}" for k, v in g["under_ms"].items())
                 + f" ms; span clock: {g['how']}, offset {g['offset_ms']:.3f} ms, "
                 f"program starts scatter {g['scatter_ms']:.3f} ms after their dispatch spans open")
    else:
        line += "; no attribution: the program keeps no spans, or they cannot be laid over this trace"
    print(line, flush=True)
    return g["gap_ms"]
