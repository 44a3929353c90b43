"""The prompt's delta-rule chunk form's share of its roofline: the least time
the chip needs for the delta-rule layers' chunks of the prompts prefilled in
the traced window (``flops/<config>.py gdn_chunk_least_seconds``: the larger
of the chunk form's products over the bf16 peak and its bytes, q, k, v,
beta, g and o once a REAL row and the state once, over the peak bandwidth)
over the device seconds of the operations whose name holds ``gdn_chunk`` (the
kernel's ``pallas_call`` name). The kernel's products with an fp32 operand
run at ``HIGHEST``, several passes of the MXU each, so the share against the
bf16 peak is small by nature. The kernel runs over the whole bucket, but
chunks past the prompt compute nothing; the count takes the real rows only.
It moves ``ttft_p50_ms``. Nothing (never 0) where no operation's name
matches."""

from harness import trace_programs

KERNEL = "gdn_chunk"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "gdn_chunk_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not lengths:
        return None
    least = sum(f.gdn_chunk_least_seconds(ctx["config"], ctx["peaks"], n) for n in lengths)
    flops = sum(f.gdn_chunk_flops(ctx["config"], n) for n in lengths)
    print(f"[bench] gdn_chunk: {seconds * 1e3:.3f} ms over {len(lengths)} traced prefills of "
          f"{sum(lengths) / len(lengths):.0f} positions; needed {least * 1e3:.3f} ms; "
          f"{flops / seconds / 1e12:.2f} TFLOP/s of the chunk form's products", flush=True)
    return 100.0 * least / seconds
