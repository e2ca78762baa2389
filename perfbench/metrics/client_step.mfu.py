"""Model FLOPs of the traced rounds' local steps over (client-step device
time x the chip's bf16 peak), in percent: the client step's own share of
the peak, idle time left out."""


def read(ctx):
    ms = ctx.value("client_step.ms_per_round")
    if ms is None or ctx["peaks"] is None:
        return None
    return 100.0 * ctx["flops_per_round"] / (
        ms * 1e-3 * ctx["peaks"]["bf16_flops_per_s"])
