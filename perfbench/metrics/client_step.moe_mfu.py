"""The MoE layers' share of the bf16 peak while they run, in percent: the
model FLOPs of the MoE layers in the traced rounds' local steps (the
family's ``moe_train_flops_per_token``: router, shared experts and the
routed share held here, forward and backward, no recomputation) over the
MoE layers' device seconds (``client_step.moe_ms_per_round``) x the chip's
bf16 peak.  None where the family has no such count or the trace no
``moe`` scope."""


def read(ctx):
    from perfbench import modelcfg
    ms = ctx.value("client_step.moe_ms_per_round")
    if ms is None or ctx["peaks"] is None:
        return None
    family = modelcfg.load_family(ctx["cell"].config)
    count = getattr(family, "moe_train_flops_per_token", None)
    if count is None:
        return None
    flops = (count(ctx["dims"]) * ctx["steps_per_round"]
             * ctx["tokens_per_step"])
    return 100.0 * flops / (ms * 1e-3 * ctx["peaks"]["bf16_flops_per_s"])
