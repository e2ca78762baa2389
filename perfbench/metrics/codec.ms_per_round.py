"""Device milliseconds per round of the codec (``core/compression.py``):
the executor-side compress (error-feedback top-k) and the server-side
decompress of the compressed partial."""
from perfbench.trace import seconds_matching

PROGRAMS = [r"^jit_run$"]


def read(ctx):
    s = ctx.get("trace")
    t = seconds_matching(s, PROGRAMS) if s else 0.0
    return 1e3 * t / ctx["traced_rounds"] if t > 0 else None
