"""Device milliseconds per round of the whole server layer: the global fold
and the server update (``core/round.py`` ``ParrotServer.global_fold`` and
``server_update``).  A program that runs the update as one compiled step
launches ``jit__server_step``; one that runs it op by op launches the eager
slice, divide, reshape, multiply, add and convert programs.  Both are
counted, so the metric reads the same layer on either program."""
from perfbench.trace import seconds_matching

PROGRAMS = [r"^jit__server_step$",
            r"^jit_(add|multiply|mul|subtract|sub|div|true_divide|"
            r"convert_element_type|slice|dynamic_slice|reshape|squeeze)$"]


def read(ctx):
    s = ctx.get("trace")
    t = seconds_matching(s, PROGRAMS) if s else 0.0
    return 1e3 * t / ctx["traced_rounds"] if t > 0 else None
