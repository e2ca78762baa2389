"""Device milliseconds per round of the MoE layers inside the client step,
from the traced run's reduction by the program's spans and scopes
(``perfbench/spans.py`` ``layers``): the op self time of the ops whose
op-name path holds ``moe`` (the pre-MoE norm, the router, the sort and
permutations of the routed pairs, the shared experts, the combine and the
residual add, forward, recomputed and backward), plus the client step's
device time that its op events under ``client_step`` do not account for.

The second part holds whatever of the step the reduction's op events leave
out: the grouped-matmul kernels wherever the trace keeps no op event for
them (none of ``jax.lax.ragged_dot``'s kernels on the TPU), the program's
own ops without an op-name path (layout copies, about 15 ms a round in the
DeepSeek cell on a v5e) and any gap inside the program.
None where the trace has no reduction or no ``moe`` scope."""


def read(ctx):
    layers = ctx.get("layers")
    if not layers:
        return None
    detail = layers["detail"]
    s = detail["scope_s"].get("moe", 0.0)
    if not s:
        return None
    step_s = detail["device_s_by_span"].get("parrot.client_step", 0.0)
    outside = max(0.0, step_s - detail["client_step_ops_s"])
    return 1e3 * (s + outside) / ctx["traced_rounds"]
