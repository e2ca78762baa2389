"""The whole round's share of the chip's bf16 peak over the traced window,
in percent: model FLOPs of the traced rounds' local steps over (traced
window seconds x chips x peak).  It bounds the share any one layer's gain
can add to ``mfu``."""


def read(ctx):
    s = ctx.get("trace")
    if not s or ctx["peaks"] is None:
        return None
    return 100.0 * ctx["flops_per_round"] * ctx["traced_rounds"] / (
        s.window_s * ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
