"""EVA's decode attention's share of its roofline: the least time the chip
needs to read what the traced decode steps' queries READ (``flops/<config>.py
eva_decode_least_seconds``: K and V of the cached ring rows below each
slot's ``t % 2048`` and of the compressed rows of its earlier blocks, from
the program's own counts an iteration, ``eva_window_positions`` and
``eva_compressed_rows`` in the serving loop's span store; the larger of
16,384 operations a row a layer over 197e12 and 16 KB over 819e9: bytes
bound it, 1 operation a byte) over the device seconds of the operations
whose name holds ``eva_decode`` (the kernel's ``pallas_call`` name) in the
traced window. Nothing (never 0) where no operation's name matches or the
program keeps no such counts (any commit before them)."""

from harness import loop_spans, trace_programs

KERNEL = "eva_decode"
COUNTS = ("eva_window_positions", "eva_compressed_rows")


def traced_rows(ctx):
    """The probe's rows of the traced iterations: the window holds the traced
    iterations' decode runs but the last, in order."""
    _, runs = trace_programs.seconds_of(ctx["trace"], ctx["cell"]["programs"]["decode"])
    return [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs)]


def counts(ctx, rows):
    """``{name: [value an iteration]}`` of the program's counts over ``rows``
    (row ``k`` of the probe is the engine's iteration ``k``,
    ``harness/loop_spans.py``), or None where the store lacks any."""
    store = loop_spans.find_store()
    numbers = loop_spans.numbers_of(rows, ctx["all_iterations"])
    if store is None or not len(numbers):
        return None
    out = {}
    for name in COUNTS:
        got = [store.counted(name, int(n)) for n in numbers]
        if any(v is None for v in got):
            return None
        out[name] = got
    return out


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "eva_decode_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    rows = traced_rows(ctx)
    got = counts(ctx, rows) if seconds and rows else None
    if got is None:
        return None
    least = sum(f.eva_decode_least_seconds(ctx["config"], ctx["peaks"], r[6], w, c)
                for r, w, c in zip(rows, *(got[n] for n in COUNTS)))
    print(f"[bench] eva_decode: {seconds / len(rows) * 1e3:.3f} ms a step over {len(rows)} traced steps; needed "
          f"{least / len(rows) * 1e3:.3f} ms a step reading {sum(got[COUNTS[0]]) / len(rows):.0f} window positions "
          f"and {sum(got[COUNTS[1]]) / len(rows):.0f} compressed rows a step", flush=True)
    return 100.0 * least / seconds
