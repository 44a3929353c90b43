"""The block-sparse decode attention's share of its roofline: the least time
the chip needs for the traced decode steps' SEEN positions (``flops/<config>.py
sparse_decode_least_seconds``: K and V of the chosen pages of the running
sequences, about 6.2k positions of any context past 6,208, not the contexts;
the larger of 16,384 operations a seen position a layer over 197e12 and 1,024
bytes over 819e9: bytes bound it on a v5e, 16 operations a byte) over the
device seconds of the operations whose name holds ``sparse_decode`` (the
kernel's ``pallas_call`` name) in the traced window. Nothing (never 0) where
no operation's name matches, as on a program without the kernel."""

from harness import trace_programs

KERNEL = "sparse_decode"


def traced_rows(ctx):
    """The probe's rows of the traced iterations: the window holds the traced
    iterations' decode runs but the last, in order."""
    _, runs = trace_programs.seconds_of(ctx["trace"], ctx["cell"]["programs"]["decode"])
    return [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs)]


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "sparse_decode_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    rows = traced_rows(ctx)
    if not seconds or not rows:
        return None
    least = sum(f.sparse_decode_least_seconds(ctx["config"], ctx["peaks"], r[6], r[7]) for r in rows)
    print(f"[bench] sparse_decode: {seconds / len(rows) * 1e3:.3f} ms a step over {len(rows)} traced steps; needed "
          f"{least / len(rows) * 1e3:.3f} ms a step at {sum(r[7] for r in rows) / max(1, sum(r[6] for r in rows)):.0f} "
          f"positions a sequence", flush=True)
    return 100.0 * least / seconds
