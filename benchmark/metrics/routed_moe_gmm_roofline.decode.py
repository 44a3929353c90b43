"""The grouped expert products' share of their roofline in a SERVING loop:
the least time the chip needs for the routed products the traced window
NEEDED (``flops/<config>.py gmm_least_seconds``, a program: the expected pairs
computed here, half an expert a token, and the weights of the held experts
expected to be hit; the larger of operations over 197e12 and bytes over
819e9), the decode steps' (one token a running sequence) and the prefills'
(a prompt's cached positions), over the device seconds of the operations whose
name holds ``moe_gmm`` (the kernel's ``pallas_call`` name) in the traced
window. A decode step's products are bound by the experts' weights; a
prefill's 256 rows an expert lie at the ridge. Nothing (never 0) where no
operation's name matches, as on a program without the kernel."""

from harness import trace_programs

KERNEL = "moe_gmm"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "gmm_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    if not seconds or len(rows) < 2:
        return None
    c, peaks = ctx["config"], ctx["peaks"]
    # the window runs from the first traced decode run to the last one's start: every
    # row's decode step but the last's, and the prefills of every row but the first
    decode = sum(f.gmm_least_seconds(c, peaks, r[6]) for r in rows[:-1])
    prefill = sum(f.gmm_least_seconds(c, peaks, n) for r in rows[1:] for n in r[8] if n > 0)
    print(f"[bench] moe_gmm: {seconds * 1e3:.3f} ms over {len(rows) - 1} traced iterations; needed "
          f"{decode * 1e3:.3f} ms by the decode steps and {prefill * 1e3:.3f} ms by "
          f"{sum(1 for r in rows[1:] for n in r[8] if n > 0)} prefills", flush=True)
    return 100.0 * (decode + prefill) / seconds
