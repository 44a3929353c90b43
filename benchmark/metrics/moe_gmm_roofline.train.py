"""The grouped expert products' share of their roofline: the time the chip
would need at least for the routed products the traced steps NEEDED
(``flops/<config>.py routed_flops`` and ``routed_bytes``: gate, up and down of
the held experts, forward, dX and dW, no recompute), the larger of operations
over peak bf16 FLOP/s and bytes over peak HBM bytes/s, over the summed device
seconds of the operations whose name holds ``moe_gmm`` or ``moe_tgmm`` (the
kernels' ``pallas_call`` names; the seconds include the forward's recompute
and the sweep over the dead tiles of the worst-case buffer).

The rows are the ones the traced steps really computed: the recorder's
``moe_pairs_here`` of those steps (routing moves as the weights do, and a
share's load with it); the expected load (one expert a token here) where the
rows carry no such counter. Nothing (never 0) where no kernel's name matches,
as on a program without the kernels."""

from harness import manifest

KERNELS = ("moe_gmm", "moe_tgmm")


def traced_rows(ctx, steps):
    """The recorder's rows of the traced steps: the driver starts the
    profiler at call ``FOLLOW + 2`` and the reduction keeps the first
    ``steps`` whole periods from there."""
    first = manifest.load_module("drivers", ctx["cell"]["driver"]).FOLLOW + 1
    return ctx["recorder"].history["train"][first:first + steps]


def read(ctx):
    t, peaks, flops = ctx["trace"], ctx["peaks"], ctx["flops"]
    if not t or peaks is None or flops is None or not hasattr(flops, "routed_flops"):
        return None
    seconds = sum(s for name, s in t["op_s"].items() if any(k in name for k in KERNELS))
    if not seconds:
        return None
    rows = traced_rows(ctx, t["steps"]) or [{}]
    least = sum(max(flops.routed_flops(ctx["config"], row.get("moe_pairs_here")) / peaks["bf16_flops"],
                    flops.routed_bytes(ctx["config"], row.get("moe_pairs_here")) / peaks["hbm_bytes_per_s"])
                for row in rows) / len(rows)  # a step's least seconds; a row without the counter: the expected load
    return 100.0 * least * t["steps"] / seconds
