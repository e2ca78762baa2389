"""Device milliseconds per round of the client step's ``mlp`` scope: the
op self time, inside ``client_step``, of the ops whose op-name path holds
``mlp`` (the pre-MLP norm, the SwiGLU projections and their residual add,
forward, recomputed and backward), from the traced run's reduction by the
program's spans and scopes (``perfbench/spans.py`` ``layers``)."""


def read(ctx):
    return (ctx.get("layers") or {}).get("client_step.mlp_ms_per_round")
