"""The block-sparse prefill attention's share of its roofline: the least time
the chip needs for the flash passes of the prompts prefilled in the traced
window (``flops/<config>.py sparse_prefill_least_seconds``: 16,384 operations
a seen (query, key) pair a layer over 197e12, the pairs counted row by row
from what each row SEES: all earlier positions up to 6,208, about 6.2k past
it) over the device seconds of the operations whose name holds
``sparse_prefill`` (the kernel's ``pallas_call`` name). With random weights the
rows of a query tile choose unlike blocks, so the kernel visits nearly every
causal tile and the share is bounded by seen pairs over causal pairs (0.6 at
16k positions). It moves ``ttft_p50_ms`` beside ``prefill_mfu.decode``, the
whole prefill's share of the peak. Nothing (never 0) where no operation's name
matches."""

from harness import trace_programs

KERNEL = "sparse_prefill"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "sparse_prefill_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not lengths:
        return None
    least = sum(f.sparse_prefill_least_seconds(ctx["config"], ctx["peaks"], n) for n in lengths)
    print(f"[bench] sparse_prefill: {seconds * 1e3:.3f} ms over {len(lengths)} traced prefills of "
          f"{sum(lengths) / len(lengths):.0f} positions; needed {least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / seconds
