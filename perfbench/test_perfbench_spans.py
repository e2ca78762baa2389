"""The reduction by the program's spans and scopes: hand-made cases, and a
traced tiny run of the program recorded here on the CPU."""
import jax
import pytest

from perfbench import spans, trace

SEED = 2**31 + 23


def _ev(name, s, e, line="t", **stats):
    return spans.HostEvent(name, s, e, line, stats)


def test_names_agree_across_jit_and_vmap():
    assert spans.names_agree("_run_one", "jit__run_one")
    assert spans.names_agree("vmap(_flatten_impl)", "jit_vmap__flatten_impl")
    assert not spans.names_agree("_run_one", "jit__flush_jnp")


def test_hlo_op_from_stats_or_the_tpu_event_text():
    assert spans.hlo_op("dot.1", {"hlo_op": "dot.1"}) == "dot.1"
    assert spans.hlo_op("%fusion.382 = bf16[8]{0} fusion(bf16[8]{0} %p), "
                        "kind=kLoop", {}) == "fusion.382"
    assert spans.hlo_op("while", {}) == "while"


def test_link_in_launch_order_and_a_reordered_case_raises():
    la = [spans.Launch("_run_one", 0, 5, "t", (), None),
          spans.Launch("add", 6, 7, "t", (), None)]
    xs = [spans.Execution("jit__run_one", "d", 1, 9),
          spans.Execution("jit_add", "d", 9, 10)]
    assert [p[1] for p in spans.link(la, xs)] == la
    swapped = [spans.Execution("jit_add", "d", 1, 2),
               spans.Execution("jit__run_one", "d", 2, 9)]
    with pytest.raises(ValueError, match="disagree"):
        spans.link(la, swapped)
    by_id = [spans.Launch("add", 0, 1, "t", (7,), None)]
    with pytest.raises(ValueError, match="does not start"):
        spans.link(by_id, [spans.Execution("jit_mul", "d", 1, 2, 7)])


def test_innermost_label_prefers_the_program_phase():
    evs = [_ev("perfbench.run_round", 0, 100),
           _ev("parrot.round", 1, 90), _ev("parrot.commit", 80, 89),
           _ev("perfbench.sync", 100, 120), _ev("PjitFunction(f)", 81, 82)]
    assert spans.innermost_label(evs, 85) == "parrot.commit"
    assert spans.innermost_label(evs, 50) == "parrot.round"
    assert spans.innermost_label(evs, 95) == "perfbench.run_round"
    assert spans.innermost_label(evs, 110) == "perfbench.sync"
    assert spans.innermost_label(evs, 130) == "no host span"


def test_host_self_seconds_subtracts_children_on_its_thread():
    evs = [_ev("parrot.round", 0, 100e9), _ev("parrot.fold", 10e9, 30e9),
           _ev("parrot.client_step", 20e9, 50e9),
           _ev("parrot.fold", 60e9, 70e9, line="other")]
    assert spans.host_self_seconds(evs, "parrot.round") == pytest.approx(60)
    assert spans.host_self_seconds(
        evs, "parrot.round", ["parrot.fold"]) == pytest.approx(80)
    assert spans.host_self_seconds(
        evs, "parrot.round", window=(0, 40e9)) == pytest.approx(10)


def test_scope_seconds_count_nested_ops_once():
    ops = [spans.OpEvent("while", "m(1)", "jit(f)/client_step/while", 0,
                         10e9, "l"),
           spans.OpEvent("dot", "m(1)", "jit(f)/client_step/while/body/"
                         "transpose(jvp(attn))/dot_general", 1e9, 4e9, "l"),
           spans.OpEvent("fusion", "m(1)", "jit(f)/client_step/mlp/mul",
                         10e9, 12e9, "l"),
           spans.OpEvent("add", "m(2)", "jit(g)/fold/add", 12e9, 13e9, "l")]
    for o in ops:
        o.self_s = (o.end - o.start) * 1e-9
    ops[0].self_s -= 3.0
    got = spans.scope_device_seconds(ops, ("attn", "mlp"),
                                     within="client_step")
    assert got.seconds == {"attn": 3.0, "mlp": 2.0}
    assert got.total_s == pytest.approx(12.0)
    assert got.remainder == {"client_step/while": pytest.approx(7.0)}
    assert spans.scopes_of(None) == ()


def test_the_trace_must_hold_what_the_reduction_reads():
    with pytest.raises(ValueError, match=r"parrot\.\*.*XLA Ops"):
        spans.lines_used([], [spans.Execution("jit_f", "d", 0, 1)], [])
    ops = [spans.OpEvent("dot.1", "m(1)", None, 0, 1, "l")]
    with pytest.raises(ValueError, match="op-name paths"):
        spans.lines_used([_ev("parrot.round", 0, 1)],
                         [spans.Execution("jit_f", "d", 0, 1)], ops)


@pytest.fixture(scope="module")
def tiny_traced(tmp_path_factory):
    """Two rounds of the tiny cell recorded under the profiler, and their
    reduction by spans and scopes."""
    from perfbench import harness
    from perfbench.conftest import tiny_cell as make_cell
    cell = make_cell.__wrapped__()()
    server = harness.build(cell, SEED, require_tpu=False).server
    server.run_round()                    # compiles outside the trace
    jax.block_until_ready(server.params)  # and ends before it starts
    path, wall = spans.record(server, 2, str(tmp_path_factory.mktemp("tr")))
    rep = spans.reduce_trace(path, 2)
    rep["traced_round_s"] = wall / 2
    return path, rep


def test_protobuf_fields_agree_with_xla(tiny_traced):
    """The hand-read HLO protos name each instruction's op as XLA's own
    reading of the same bytes prints it."""
    import re
    from jax._src.lib import xla_client as xc
    path, _ = tiny_traced
    protos = spans.hlo_protos(path)
    assert any(m.startswith("jit__run_one(") for m in protos), list(protos)
    line = re.compile(r'^\s*(?:ROOT )?%(\S+) = (?:\w+\[[^\]]*\]'
                      r'(?:\{[^}]*\})? ([\w-]+)\()?')
    # the text escapes the name as a C string
    op_name = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"')
    checked = fusions = 0
    for module, blob in protos.items():
        text = xc._xla.HloModule.from_serialized_hlo_module_proto(
            spans.hlo_module(blob)).to_string(xc._xla.HloPrintOptions())
        mine = spans._hlo_op_names(blob)
        seen = set()
        for ln in text.splitlines():
            m = line.match(ln)
            if not m:
                continue
            name, code = m.groups()
            op = op_name.search(ln)
            seen.add(name)
            if op:
                assert mine[name][0] == op.group(1).encode().decode(
                    "unicode_escape"), (module, name)
                checked += 1
            if code and name in mine:
                assert mine[name][1] == code, (module, name)
        assert set(mine) <= seen, module
        # the computations each fusion calls, by id
        comps = spans.hlo_computations(blob)
        calls = {i.name: [comps[c][0] for c in i.called]
                 for _, ins in comps.values() for i in ins}
        for name, callee in re.findall(r"%(\S+) = .*? fusion\(.*?"
                                       r"calls=%([\w.-]+)", text):
            assert calls[name] == [callee], (module, name)
            fusions += 1
    assert checked > 100 and fusions > 10


def test_every_execution_is_linked_by_run_id(tiny_traced):
    _, rep = tiny_traced
    d = rep["detail"]
    assert d["executions"] > 0
    assert d["linked"] == d["linked_by_run_id"] == d["executions"]
    assert "no launch" not in d["device_s_by_span"]


def test_device_time_falls_under_the_launching_spans(tiny_traced):
    _, rep = tiny_traced
    assert rep["client_step.span_ms_per_round"] > 0
    assert rep["fold.span_ms_per_round"] > 0
    assert rep["server.span_ms_per_round"] > 0
    assert rep["detail"]["launching_share"] > 0.9
    assert rep["client_step.useful_step_frac"] == pytest.approx(100.0)
    assert rep["engine.host_ms_per_round"] > 0
    assert rep["traced_round_s"] > 0


def test_scopes_split_the_client_step(tiny_traced):
    _, rep = tiny_traced
    d = rep["detail"]
    for scope in ("attn", "mlp", "head_loss"):
        assert d["scope_s"][scope] > 0, scope
    assert sum(d["scope_s"][s] for s in ("attn", "mlp", "head_loss")) \
        <= d["client_step_ops_s"]
    # ops XLA adds at a program's top level (copies of its arguments) carry
    # no name at all
    assert d["ops_without_path_s"] < 0.05 * d["client_step_ops_s"]


def test_idle_gaps_are_named_by_phase(tiny_traced):
    _, rep = tiny_traced
    labels = [name for name, _ in rep["detail"]["idle_gaps"]]
    assert labels and any(n.startswith(spans.PREFIX) for n in labels)
    assert all(n.startswith((spans.PREFIX, trace.PREFIX))
               or n == "no host span" for n in labels)
