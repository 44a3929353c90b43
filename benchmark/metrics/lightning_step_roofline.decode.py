"""The lightning layers' decode step's share of its roofline: the least time
the chip needs to read and write once the recurrent state of the traced decode
steps' RUNNING slots (``flops/<config>.py lightning_step_least_seconds``: 6
layers x 2 MB of float32 a slot, in and out, over 819e9; the 65,536 operations
a head are far below it) over the device seconds of the operations whose name
holds ``lightning_step`` (the kernel's ``pallas_call`` name) in the traced
window. Nothing (never 0) where no operation's name matches."""

from harness import trace_programs

KERNEL = "lightning_step"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "lightning_step_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs)]
    if not seconds or not rows:
        return None
    least = sum(f.lightning_step_least_seconds(ctx["config"], ctx["peaks"], r[6]) for r in rows)
    print(f"[bench] lightning_step: {seconds / len(rows) * 1e3:.3f} ms a step over {len(rows)} traced steps; needed "
          f"{least / len(rows) * 1e3:.3f} ms a step at {sum(r[6] for r in rows) / len(rows):.1f} running slots",
          flush=True)
    return 100.0 * least / seconds
