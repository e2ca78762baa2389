"""The wall-clock lane: ``parrot.*`` spans in the JAX profiler's trace.

Two BSP rounds of a tiny LM through ``ParrotServer`` under
``jax.profiler.trace``: every span of ``WALL_SPANS`` the path reaches is
there, carries its round (and executor), nests inside its round, and counts
the client step's real and scanned local steps; the trace changes no
parameter."""
import glob

import jax
import numpy as np
import pytest

from repro.core.client_step import _bucket
from repro.core.clock import TickTimer
from repro.core.telemetry import SPAN_PREFIX, WALL_SPANS, span
from repro.data import make_lm_clients
from repro.launch import train

ROUNDS = 2
CASES = {
    # algorithm, codec, client block: the stateless path one client at a
    # time, and the stateful one in vmapped blocks through a codec
    "fedavg": ("fedavg", "none", 1),
    "scaffold-int8": ("scaffold", "int8", 4),
}


def _server(algorithm, compression, block):
    args = train.parse_args([
        "--model", "lm", "--algorithm", algorithm, "--executors", "2",
        "--clients", "6", "--clients-per-round", "6", "--local-epochs",
        "2", "--client-block", str(block), "--compression", compression,
        "--lr", "0.1", "--seed", "3"])
    cfg = train.model_config(args)
    grad_fn, params = train.build_grad_fn(cfg)
    data = make_lm_clients(6, vocab=cfg.vocab_size, seq_len=16,
                           mean_samples=10, batch_size=2, seed=3)
    server = train.build_server(args, grad_fn, params, data)
    for ex in server.executors.values():
        # measured times steer the schedule, and so the fold order: a
        # deterministic clock makes two runs comparable bit for bit
        ex.timer = TickTimer(1.0)
    return server, data


def _spans(directory):
    pd = jax.profiler.ProfileData.from_file(glob.glob(
        f"{directory}/plugins/profile/*/*.xplane.pb")[-1])
    out = []
    for plane in pd.planes:
        for line in plane.lines:
            out += [(e.name[len(SPAN_PREFIX):], e.start_ns,
                     e.start_ns + e.duration_ns, line.name,
                     {k: v for k, v in e.stats})
                    for e in line.events if e.name.startswith(SPAN_PREFIX)]
    return out


@pytest.fixture(scope="module", params=sorted(CASES))
def traced(request, tmp_path_factory):
    algorithm, compression, block = CASES[request.param]
    plain, _ = _server(algorithm, compression, block)
    plain.run(ROUNDS)
    server, data = _server(algorithm, compression, block)
    d = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(d):
        server.run(ROUNDS)
    return request.param, plain, server, data, _spans(d)


def test_reached_spans_are_recorded(traced):
    case, _, server, _, spans = traced
    want = {"round", "select", "schedule", "executor", "client_step",
            "fold", "global_fold", "server_update", "commit"}
    if server.algorithm.stateful:
        want.add("state_io")
    if server.compressor is not None:
        want.add("codec")
    names = {s[0] for s in spans}
    assert want <= names, want - names
    assert names <= set(WALL_SPANS)
    assert [s[4]["round"] for s in spans if s[0] == "round"] == \
        list(range(ROUNDS))


def test_spans_carry_round_and_nest_in_it(traced):
    _, _, _, _, spans = traced
    rounds = [s for s in spans if s[0] == "round"]
    execs = [s for s in spans if s[0] == "executor"]
    for name, start, end, line, stats in spans:
        assert "round" in stats, name
        held = [r for r in rounds if r[1] <= start and end <= r[2]
                and r[3] == line]
        assert len(held) == 1, name
        assert stats["round"] == held[0][4]["round"], name
        inside = [x for x in execs if x[1] <= start and end <= x[2]
                  and x[3] == line]
        if inside:
            assert stats["executor"] == inside[-1][4]["executor"], name


def test_client_step_counts_real_and_scanned_steps(traced):
    case, _, server, data, spans = traced
    epochs = server.algorithm.local_epochs
    steps = [s[4] for s in spans if s[0] == "client_step"]
    want = epochs * sum(len(d.batches) for d in data.values())
    for r in range(ROUNDS):
        # a first-seen shape runs twice (the re-run is timed, its result
        # discarded): it counts scanned steps and no real ones
        assert sum(s["steps"] for s in steps if s["round"] == r) == want
    if CASES[case][2] == 1:
        # one client a scan: the batches pad to their power-of-two bucket
        got = sorted((s["steps"], s["scanned"]) for s in steps
                     if s["steps"])
        assert got == sorted(
            (epochs * len(d.batches), epochs * _bucket(len(d.batches)))
            for d in data.values() for _ in range(ROUNDS))
    for s in steps:
        assert s["steps"] <= s["scanned"]
        assert s["scanned"] % epochs == 0


def test_the_trace_changes_no_parameter(traced):
    _, plain, server, _, _ = traced
    for a, b in zip(jax.tree.leaves(plain.params),
                    jax.tree.leaves(server.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.fixture(scope="module")
def server_step_trace(tmp_path_factory):
    """FedAvg's BSP rounds under the profiler, from a fresh server: the
    first round compiles the server step, the second reuses it."""
    server, _ = _server(*CASES["fedavg"])
    d = str(tmp_path_factory.mktemp("server_step"))
    opts = jax.profiler.ProfileOptions()      # as the benchmark records
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    with jax.profiler.trace(d, profiler_options=opts):
        server.run(ROUNDS)
        jax.block_until_ready(server.params)
    path = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")[-1]
    return jax.profiler.ProfileData.from_file(path), path, _spans(d)


def _programs_by_span(pd):
    from perfbench import spans
    out = {}
    for x, la in spans.link(spans.launches(pd), spans.executions(pd)):
        name = la.span.name[len(SPAN_PREFIX):] if la and la.span else None
        out.setdefault(name, []).append(
            (x.program, la.span.stats.get("round") if la and la.span
             else None))
    return out


def test_the_server_step_runs_once_a_round_under_its_span(server_step_trace):
    """One compiled program folds the aggregate into the model each round,
    launched inside ``parrot.server_update``; the global fold launches only
    the K-1 adds across the executors' partials."""
    pd, _, _ = server_step_trace
    by_span = _programs_by_span(pd)
    assert by_span["server_update"] == [("jit__server_step", r)
                                        for r in range(ROUNDS)]
    assert all(p == "jit__server_step" for p, _ in
               sum(by_span.values(), []) if "server_step" in p)
    assert {p for p, _ in by_span.get("global_fold", [])} <= {"jit_add"}


def test_the_server_update_span_counts_the_step_executables(
        server_step_trace):
    _, _, recorded = server_step_trace
    compiles = [s[4]["compiles"] for s in recorded if s[0] == "server_update"]
    # read as the span opens: the first round compiles, later ones reuse it
    assert compiles == [0] + [1] * (ROUNDS - 1)


def test_the_span_reduction_sums_the_server_layer(server_step_trace):
    from perfbench import spans
    pd, path, _ = server_step_trace
    events = spans.host_events(pd)
    window = (min(e.start for e in events), max(e.end for e in events))
    rep = spans.layers(pd, window, ROUNDS, spans.op_names(path))
    by = rep["detail"]["device_s_by_span"]
    step = by[SPAN_PREFIX + "server_update"]
    assert step > 0
    assert rep["server.span_ms_per_round"] == pytest.approx(
        (by.get(SPAN_PREFIX + "global_fold", 0.0) + step) * 1e3 / ROUNDS)


def test_span_names_come_from_the_table():
    with pytest.raises(KeyError):
        with span("no_such_phase"):
            pass
    with span("round", round=7):
        with span("fold", executor=2):
            pass
