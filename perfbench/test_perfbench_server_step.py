"""The server layer's reader on a hand-made trace summary: it counts the
compiled server step and the eager server programs (what the retired
``server.ms_per_round`` reader counted), and nothing of the client step or
the local fold."""
import pytest

from perfbench import harness, trace

CLIENT_AND_FOLD = {"jit__run_one": 5.5, "jit_vmap__run_one": 0.5,
                   "jit__flatten_impl": 0.07, "jit__flush_jnp": 0.03,
                   "jit__fold_stacked_jnp": 0.02, "jit_broadcast_in_dim": 0.01}
EAGER_SERVER = {"jit_dynamic_slice": 0.018, "jit_true_divide": 0.018,
                "jit_reshape": 0.020, "jit_multiply": 0.018,
                "jit_add": 0.022, "jit_convert_element_type": 0.014}


def _read(programs, rounds=3):
    s = trace.Summary(programs=programs, calls={n: 1 for n in programs},
                      busy_s=sum(programs.values()), window_s=6.0)
    return harness.load_reader("server.step_ms_per_round")(
        {"trace": s, "traced_rounds": rounds})


def test_counts_the_compiled_step():
    got = _read({**CLIENT_AND_FOLD, "jit__server_step": 0.03})
    assert got == pytest.approx(10.0)


def test_counts_the_eager_server_programs_as_the_old_reader_does():
    got = _read({**CLIENT_AND_FOLD, **EAGER_SERVER})
    assert got == pytest.approx(1e3 * sum(EAGER_SERVER.values()) / 3)


def test_counts_nothing_of_the_client_step_or_the_fold():
    assert _read(dict(CLIENT_AND_FOLD)) is None
    assert _read({}) is None
