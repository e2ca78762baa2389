"""Device milliseconds per round of the executor's local fold
(``core/aggregation.py`` ``LocalAggregator``, ``core/flat.py``): the
flatten of each client's update, the fold into the fp32 accumulator, and
the accumulator's zeroing."""
from perfbench.trace import seconds_matching

PROGRAMS = [r"^jit__flatten_impl$", r"^jit__flush_jnp$",
            r"^jit__fold_stacked_jnp$", r"^jit_agg_", r"^jit_broadcast_in_dim$"]


def read(ctx):
    s = ctx.get("trace")
    t = seconds_matching(s, PROGRAMS) if s else 0.0
    return 1e3 * t / ctx["traced_rounds"] if t > 0 else None
