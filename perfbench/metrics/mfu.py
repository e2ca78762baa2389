"""Model FLOP utilization of the window: model FLOPs of every local step
completed in it (no padded steps, no recomputation) over window seconds x
chips x the chip's bf16 peak, in percent."""


def read(ctx):
    if "trace" in ctx or ctx["peaks"] is None:
        return None
    flops = ctx["rounds"] * ctx["flops_per_round"]
    return 100.0 * flops / (ctx["window_s"] * ctx["chips"]
                            * ctx["peaks"]["bf16_flops_per_s"])
