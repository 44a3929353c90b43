"""The absorbed latent attention's share of its roofline: the least time the
chip needs for the traced decode steps' cached latent rows at their REAL
lengths (``flops/<config>.py mla_decode_least_seconds``: the larger of 36,864
operations a position a layer over 197e12 and 640 bytes a position a layer
over 819e9: bytes bound it on a v5e, 57.6 operations a byte) over the device
seconds of the operations whose name holds ``mla_decode`` (the kernel's
``pallas_call`` name) in the traced window. Nothing (never 0) where no
operation's name matches, as on a program without the kernel."""

from harness import trace_programs

KERNEL = "mla_decode"


def traced_rows(ctx):
    """The probe's rows of the traced iterations: the window holds the traced
    iterations' decode runs but the last, in order."""
    _, runs = trace_programs.seconds_of(ctx["trace"], ctx["cell"]["programs"]["decode"])
    return [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs)]


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "mla_decode_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    rows = traced_rows(ctx)
    if not seconds or not rows:
        return None
    least = sum(f.mla_decode_least_seconds(ctx["config"], ctx["peaks"], r[7]) for r in rows)
    print(f"[bench] mla_decode: {seconds / len(rows) * 1e3:.3f} ms a step over {len(rows)} traced steps; needed "
          f"{least / len(rows) * 1e3:.3f} ms a step at {sum(r[7] for r in rows) / len(rows):.0f} positions", flush=True)
    return 100.0 * least / seconds
