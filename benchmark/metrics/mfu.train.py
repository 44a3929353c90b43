"""The whole step's share of the chip's peak: operations the forward and
backward passes NEED per step (``benchmark/flops/<config>.py``), times the
window's steps, over the window's seconds and chips x peak bf16 FLOP/s."""


def read(ctx):
    if ctx["peaks"] is None or ctx["flops"] is None or not ctx["seconds"]:
        return None
    needed = ctx["flops"].step_flops(ctx["config"]) * ctx["steps"]
    return 100.0 * needed / ctx["seconds"] / (ctx["chips"] * ctx["peaks"]["bf16_flops"])
