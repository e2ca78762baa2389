"""CPU rehearsal of one benchmark run at a tiny size: traffic and weights
from the seed, the program's first rounds, the window, the trace, the
reference and the result line's shape.  The chip check is skipped here;
the measurement CLI itself keeps refusing the CPU."""
import time

import pytest

from perfbench import datagen, harness

SEED = 2**31 + 11


def _run(cell, trace_on):
    return harness.run(cell, SEED, 0.3, trace_on,
                       t_start=time.perf_counter(), require_tpu=False,
                       compile_cache=False, log=lambda s: None)


@pytest.mark.parametrize("compression", ["none", "topk"])
def test_window_run_is_correct_and_well_formed(tiny_cell, compression):
    out = _run(tiny_cell(compression), False)
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert out["correct"] is True, out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["metrics"]["round_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["unit"] == "s"
    assert set(out["device"]) >= {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name


def test_traced_run_reports_per_layer_metrics(tiny_cell, monkeypatch):
    from perfbench import spans
    seen = []

    def layers(*a, **kw):
        seen.append(reduce(*a, **kw))
        return seen[-1]

    reduce = spans.layers
    monkeypatch.setattr(spans, "layers", layers)
    out = _run(tiny_cell(), True)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] >= out["device"]["busy_s"]
    assert 0 < out["metrics"]["device.idle_frac"]["value"] < 100
    assert "round_s" not in out["metrics"]
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10
    # the client step split by the program's own scopes, as the span
    # reduction of the same trace reads it, and held to the client step's
    # op time (on the CPU no program-name metric reads the client step: its
    # ops run on the thread pool, not on the client's thread)
    (rep,) = seen
    scopes = [out["metrics"][f"client_step.{k}_ms_per_round"]["value"]
              for k in ("mlp", "attn", "head")]
    assert all(v > 0 for v in scopes), scopes
    assert scopes == [rep[f"client_step.{k}_ms_per_round"]
                      for k in ("mlp", "attn", "head")]
    assert sum(scopes) <= (1e3 * rep["detail"]["client_step_ops_s"]
                           / out["attempted"])
    # idle gaps named by the innermost program or benchmark span
    assert out["breakdown"]["idle_gaps"] == [
        list(g) for g in rep["detail"]["idle_gaps"][:10]]


def test_cohorts_are_fedavg_sampling_from_the_seed():
    a = datagen.cohorts(SEED, 20, 4, 3)
    assert a == datagen.cohorts(SEED, 20, 4, 3)
    assert all(len(set(c)) == 4 for c in a)
    assert a != datagen.cohorts(SEED + 1, 20, 4, 3)
