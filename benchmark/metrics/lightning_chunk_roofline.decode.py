"""The prompt's lightning scans' share of their roofline: the least time the
chip needs for the scans of the prompts prefilled in the traced window, at
their REAL lengths, over the device seconds of the operations whose name holds
``lightning_chunk`` (the kernel's ``pallas_call`` name). A prompt's least time
is the larger of its recurrence's operations (``flops/<config>.py``: 4 x H x
D^2 a position a lightning layer, as ``prefill_flops`` counts them) over the
peak FLOP/s and of the bytes no program can avoid over the peak bandwidth:
q, k and v read once in the compute dtype, o written once in float32 and the
state written once (:func:`least_seconds`; bound by bytes at these widths).
The count lives here: it counts the same work whatever implements the scan.
Not listed in ``BENCHMARK.json`` (as ``lightning_step_roofline.decode``); it
would move ``ttft_p50_ms`` beside ``prefill_mfu.decode``. Nothing (never 0)
where no operation's name matches."""

from harness import trace_programs

KERNEL = "lightning_chunk"


def least_seconds(f, c, peaks, n):
    """The lightning layers' scans of one prompt of ``n`` cached positions."""
    H, D = c["lightning_nh"], c["lightning_head_dim"]
    moved = n * H * D * (3 * f.DTYPE_BYTES[c["compute_dtype"]] + 4) + H * D * D * 4
    return f.n_lightning(c) * max(f.recurrence_flops_per_position(c) * n / peaks["bf16_flops"],
                                  moved / peaks["hbm_bytes_per_s"])


def read(ctx):
    t, f = ctx["trace"], ctx["flops"]
    if not t or ctx["peaks"] is None or ctx["traced_first"] is None or not hasattr(f, "recurrence_flops_per_position"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if KERNEL in name)
    _, runs = trace_programs.seconds_of(t, ctx["cell"]["programs"]["decode"])
    rows = [r for r in ctx["all_iterations"] if ctx["traced_first"] <= r[0]][:int(runs) + 1]
    # the window runs from the first traced decode run to the last one's start: the prefills of every row but the first
    lengths = [n for r in rows[1:] for n in r[8] if n > 0]
    if not seconds or not lengths:
        return None
    least = sum(least_seconds(f, ctx["config"], ctx["peaks"], n) for n in lengths)
    print(f"[bench] lightning_chunk: {seconds * 1e3:.3f} ms over {len(lengths)} traced prefills of "
          f"{sum(lengths) / len(lengths):.0f} positions; needed {least * 1e3:.3f} ms", flush=True)
    return 100.0 * least / seconds
