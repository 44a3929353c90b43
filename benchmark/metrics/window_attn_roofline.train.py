"""The flash attention kernels' share of the chip's bf16 peak where layers
differ in what a query sees: the operations attention NEEDS a step
(``flops/<config>.py attention_flops``: the exact (query, key) pairs of each
layer, window or full, forward and backward, no recompute) times the traced
steps, over the summed device seconds of the operations whose name holds
``flash_fwd`` or ``flash_bwd``, over the peak. Compute-bound at head size 128.
Nothing (never 0) where no name matches."""

KERNELS = ("flash_fwd", "flash_bwd")


def read(ctx):
    t, peaks, flops = ctx["trace"], ctx["peaks"], ctx["flops"]
    if not t or peaks is None or flops is None or not hasattr(flops, "attention_flops"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if any(k in name for k in KERNELS))
    if not seconds:
        return None
    return 100.0 * flops.attention_flops(ctx["config"]) * t["steps"] / seconds / peaks["bf16_flops"]
