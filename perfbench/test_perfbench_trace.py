"""The trace reduction: interval arithmetic on hand-made intervals, and the
whole reduction on a small trace recorded here on the CPU."""
import jax
import jax.numpy as jnp
import pytest

from perfbench import trace


def test_union_and_gaps():
    busy = trace.union([(5, 7), (0, 2), (1, 3), (9, 10), (6, 8)])
    assert busy == [(0, 3), (5, 8), (9, 10)]
    assert trace.gaps(busy, 0, 12) == [(3, 5), (8, 9), (10, 12)]


def test_program_name_drops_run_id():
    assert trace.program_name("jit__run_one(123)") == "jit__run_one"
    assert trace.program_name("jit_add") == "jit_add"


def test_host_label_is_innermost_span():
    spans = [("perfbench.run_round", 0, 10), ("perfbench.sync", 4, 6)]
    assert trace.host_label(spans, 5) == "perfbench.sync"
    assert trace.host_label(spans, 2) == "perfbench.run_round"
    assert trace.host_label(spans, 11) == "no host span"


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A trace of two host spans around known device work."""
    d = str(tmp_path_factory.mktemp("trace"))

    @jax.jit
    def matmul_sin(x):
        return jnp.sin(x @ x)

    x = jnp.ones((256, 256))
    matmul_sin(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(d, profiler_options=opts):
        for _ in range(3):
            with jax.profiler.TraceAnnotation(trace.PREFIX + "run_round"):
                y = matmul_sin(x)
            with jax.profiler.TraceAnnotation(trace.PREFIX + "sync"):
                y.block_until_ready()
    return jax.profiler.ProfileData.from_file(trace.find_xplane(d))


def test_reduction_of_a_recorded_trace(recorded):
    spans = trace.host_spans(recorded)
    assert [n for n, _, _ in spans].count(trace.PREFIX + "run_round") == 3
    window = trace.span_window(recorded, trace.PREFIX + "run_round",
                               trace.PREFIX + "sync")
    s = trace.summarize(recorded, window)
    assert s.window_s > 0 and 0 < s.busy_s <= s.window_s
    assert sum(s.programs.values()) >= s.busy_s * 0.999
    assert all(v > 0 for v in s.programs.values())
    assert s.gaps and all(sec >= 0 for _, sec in s.gaps)
    assert all(label.startswith(trace.PREFIX) or label == "no host span"
               for label, _ in s.gaps)
    assert trace.seconds_matching(s, [r"."]) == pytest.approx(
        sum(s.programs.values()))
    assert trace.seconds_matching(s, [r"^no such program$"]) == 0.0
