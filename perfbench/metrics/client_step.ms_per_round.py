"""Device milliseconds per round of the compiled client step
(``core/client_step.py``, ``ClientStepEngine`` via ``core/executor.py``):
the whole local scan of one client, or of a vmapped block of clients."""
from perfbench.trace import seconds_matching

PROGRAMS = [r"^jit__run_one$", r"^jit_vmap__run_one$"]


def read(ctx):
    s = ctx.get("trace")
    t = seconds_matching(s, PROGRAMS) if s else 0.0
    return 1e3 * t / ctx["traced_rounds"] if t > 0 else None
