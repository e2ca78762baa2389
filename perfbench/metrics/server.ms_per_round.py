"""Device milliseconds per round of the server side of the round: the
global fold (``core/engine.py`` BSP commit, ``core/aggregation.py``
``reduce_flat_partials``, ``core/placement.py``) and the server update
(``core/algorithms.py``): the division by the total weight, the unflatten
into leaves and the update of each leaf, each an eager operation."""
from perfbench.trace import seconds_matching

PROGRAMS = [r"^jit_(add|multiply|mul|subtract|sub|div|true_divide|"
            r"convert_element_type|slice|dynamic_slice|reshape|squeeze)$"]


def read(ctx):
    s = ctx.get("trace")
    t = seconds_matching(s, PROGRAMS) if s else 0.0
    return 1e3 * t / ctx["traced_rounds"] if t > 0 else None
