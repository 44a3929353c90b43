"""The prompt's selective scans' share of their roofline: the least time the
chip needs for the Mamba layers' scans of the prompts prefilled in the traced
window (``flops/<config>.py mamba_scan_least_seconds``: x, z, dt and y of
``d_inner`` and B and C of ``d_state``, each in the compute dtype, read or
written once a REAL row and layer, over the peak bandwidth) over the device
seconds of the operations whose name holds ``mamba_scan`` (the kernel's
``pallas_call`` name). A floor: the scan's exponentials and multiply-adds run
on the vector and transcendental units, which the peaks do not list, so the
share says how far the kernel is from its bytes, not from its arithmetic
(printed beside it). The kernel runs over the whole bucket, but chunks past
the prompt only write noughts; the count takes the real rows only. It moves
``ttft_p50_ms`` beside ``prefill_mfu.decode``, the whole prefill's share of
the peak. Nothing (never 0) where no operation's name matches."""

from harness import trace_programs

KERNEL = "mamba_scan"


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "mamba_scan_least_seconds"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not lengths:
        return None
    least = sum(f.mamba_scan_least_seconds(ctx["config"], ctx["peaks"], n) for n in lengths)
    exps, madds = (sum(x) for x in zip(*(f.mamba_scan_elementwise(ctx["config"], n) for n in lengths)))
    print(f"[bench] mamba_scan: {seconds * 1e3:.3f} ms over {len(lengths)} traced prefills of "
          f"{sum(lengths) / len(lengths):.0f} positions; needed {least * 1e3:.3f} ms of bytes; "
          f"{exps / seconds / 1e9:.1f} G exponentials/s and {madds / seconds / 1e9:.1f} G multiply-adds/s", flush=True)
    return 100.0 * least / seconds
