"""The comparison must call a broken run incorrect.  Each fault the cells
can have is planted underneath the timed path (the program's rounds run
with it), and the control, the reference computed one precision below the
configuration's, is put in the program's place."""
import time

import jax.numpy as jnp
import pytest

from perfbench import calibrate, compare, harness

SEED = 2**31 + 23


def _unchanged(monkeypatch):
    from repro.core.algorithms import FedAvg
    monkeypatch.setattr(FedAvg, "server_update",
                        lambda self, params, agg, st, n: (params, st))


def _half(monkeypatch):
    from repro.core.aggregation import LocalAggregator
    fold, seen = LocalAggregator.fold, []

    def every_other(self, result):
        seen.append(1)
        if len(seen) % 2:
            fold(self, result)

    monkeypatch.setattr(LocalAggregator, "fold", every_other)


def _negate(monkeypatch):
    from repro.core.algorithms import FedAvg
    finalize = FedAvg.finalize

    def negated(self, *a):
        out, st = finalize(self, *a)
        return {"delta": jax_neg(out["delta"])}, st

    monkeypatch.setattr(FedAvg, "finalize", negated)


def jax_neg(tree):
    import jax
    return jax.tree.map(lambda x: -x, tree)


@pytest.mark.parametrize("plant", [_unchanged, _half, _negate],
                         ids=["state_unchanged", "half_the_cohort",
                              "answer_altered"])
def test_planted_fault_is_not_correct(tiny_cell, monkeypatch, plant):
    plant(monkeypatch)
    out = harness.run(tiny_cell(), SEED, 0.2, False,
                      t_start=time.perf_counter(), require_tpu=False,
                      compile_cache=False, log=lambda s: None)
    assert out["correct"] is False, out["checks"]


def test_control_one_precision_below_is_not_correct(tiny_cell):
    cell = tiny_cell()
    got = calibrate.variant_numbers(cell, SEED, ["control"],
                                    log=lambda s: None)
    ok, rows = compare.verdict(got["control"], cell.limits)
    assert not ok, rows
    assert calibrate.VARIANTS["control"]["storage"] == jnp.dtype(
        "float8_e4m3fn").name
