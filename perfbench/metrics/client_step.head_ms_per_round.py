"""Device milliseconds per round of the client step's ``head_loss`` scope:
the op self time, inside ``client_step``, of the ops whose op-name path
holds ``head_loss`` (each logit chunk's LM head and float32 cross-entropy,
forward, recomputed and backward), from the traced run's reduction by the
program's spans and scopes (``perfbench/spans.py`` ``layers``)."""


def read(ctx):
    return (ctx.get("layers") or {}).get("client_step.head_ms_per_round")
