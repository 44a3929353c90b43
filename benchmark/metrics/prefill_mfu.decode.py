"""The prefill programs' share of the chip's peak: operations the prompts
prefilled in the traced window NEED (``flops/<config>.py prefill_flops``: every
matmul weight once a cached position and the mixing over what each row sees,
at the prompts' REAL lengths, no padding to the bucket), over the device
seconds of the prefill programs' runs in that window and the peak bf16 FLOP/s.
A request's time to its first token is its prefill and one decode step, so
this is the share that moves ``ttft_p50_ms``; it bounds what a prefill
kernel's roofline can claim. Nothing (never 0) where the trace holds no run of
a prefill program."""

from harness import trace_programs


def read(ctx):
    t, f, first = ctx["trace"], ctx["flops"], ctx["traced_first"]
    if not t or ctx["peaks"] is None or f is None or first is None:
        return None
    seconds, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["prefill"])
    _, steps = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if first <= r[0]][:int(steps) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not runs or len(lengths) != int(runs):
        return None  # the probe's rows and the trace's runs must be the same prefills
    needed = f.prefill_flops(ctx["config"], lengths)
    print(f"[bench] prefill programs: {needed / 1e12:.2f} TFLOP needed by {len(lengths)} prompts of "
          f"{sum(lengths) / len(lengths):.0f} cached positions in {seconds * 1e3:.1f} ms", flush=True)
    return 100.0 * needed / seconds / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
