"""The flash attention kernels' share of the chip's bf16 peak: the
operations causal attention NEEDS a step (``flops/<config>.py
attention_flops``: forward and backward, half of T^2, no recompute) times
the traced steps, over the summed device seconds of the operations whose
name holds ``flash_fwd`` or ``flash_bwd`` (the kernels' ``pallas_call``
names), over the peak. Compute-bound at head size 64, so the operations'
bound is the one that counts. Nothing (never 0) where no name matches."""

KERNELS = ("flash_fwd", "flash_bwd")


def read(ctx):
    t = ctx["trace"]
    if not t or ctx["peaks"] is None or ctx["flops"] is None or not hasattr(ctx["flops"], "attention_flops"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if any(k in name for k in KERNELS))
    if not seconds:
        return None
    needed = ctx["flops"].attention_flops(ctx["config"]) * t["steps"]
    return 100.0 * needed / seconds / ctx["peaks"]["bf16_flops"]
