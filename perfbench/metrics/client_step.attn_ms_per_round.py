"""Device milliseconds per round of the client step's ``attn`` scope: the
op self time, inside ``client_step``, of the ops whose op-name path holds
``attn`` (the pre-attention norm, the q, k, v and output projections and
the attention itself, forward, recomputed and backward), from the traced
run's reduction by the program's spans and scopes (``perfbench/spans.py``
``layers``)."""


def read(ctx):
    return (ctx.get("layers") or {}).get("client_step.attn_ms_per_round")
