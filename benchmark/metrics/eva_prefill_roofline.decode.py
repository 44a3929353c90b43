"""EVA's prefill attention's share of its roofline: the least time the chip
needs for the flash passes of the prompts prefilled in the traced window
(``flops/<config>.py eva_prefill_least_seconds``: 16,384 operations a (query,
row read) pair a layer over 197e12, the pairs counted row by row from what
each row READS: its window block's exact keys up to itself and one
compressed row a chunk of every earlier block, about 3.4k of a 24k prompt's
rows) over the device seconds of the operations whose name holds
``eva_prefill`` (the kernel's ``pallas_call`` name). The kernel runs over the
whole bucket, padding rows too, and the count takes the real rows only. It
moves ``ttft_p50_ms`` beside ``prefill_mfu.decode``, the whole prefill's share
of the peak. Nothing (never 0) where no operation's name matches."""

from harness import trace_programs

KERNEL = "eva_prefill"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "eva_prefill_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not lengths:
        return None
    least = sum(f.eva_prefill_least_seconds(ctx["config"], ctx["peaks"], n) for n in lengths)
    print(f"[bench] eva_prefill: {seconds * 1e3:.3f} ms over {len(lengths)} traced prefills of "
          f"{sum(lengths) / len(lengths):.0f} positions; needed {least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / seconds
