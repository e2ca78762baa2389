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


def test_traced_run_reports_per_layer_metrics(tiny_cell):
    out = _run(tiny_cell(), True)
    assert out["correct"] is True, out["checks"]
    assert out["device"]["busy_s"] > 0
    assert out["device"]["window_s"] >= out["device"]["busy_s"]
    assert 0 < out["metrics"]["device.idle_frac"]["value"] < 100
    assert "round_s" not in out["metrics"]
    assert 0 < len(out["breakdown"]["device_ops"]) <= 10
    assert len(out["breakdown"]["idle_gaps"]) <= 10


def test_cohorts_are_fedavg_sampling_from_the_seed():
    a = datagen.cohorts(SEED, 20, 4, 3)
    assert a == datagen.cohorts(SEED, 20, 4, 3)
    assert all(len(set(c)) == 4 for c in a)
    assert a != datagen.cohorts(SEED + 1, 20, 4, 3)
