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


def test_span_names_come_from_the_table():
    with pytest.raises(KeyError):
        with span("no_such_phase"):
            pass
    with span("round", round=7):
        with span("fold", executor=2):
            pass
