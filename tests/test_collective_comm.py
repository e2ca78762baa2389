"""SPMD global aggregate == host global aggregate (the production path)."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.collective import spmd_global_aggregate
from repro.core.aggregation import (ClientResult, LocalAggregator, Op,
                                    global_aggregate)
from repro.launch.mesh import make_host_mesh


def _partials(K=4, seed=0):
    rng = np.random.default_rng(seed)
    ops = {"delta": Op.WEIGHTED_AVG, "count": Op.SUM}
    parts = []
    for k in range(K):
        agg = LocalAggregator(ops)
        for _ in range(3):
            agg.fold(ClientResult(
                {"delta": {"w": jnp.asarray(rng.normal(size=(6, 2)),
                                            jnp.float32)},
                 "count": jnp.ones((), jnp.float32)},
                ops, weight=float(rng.integers(1, 50))))
        parts.append(agg.partial())
    return parts, ops


def test_spmd_aggregate_matches_host():
    parts, ops = _partials()
    host = global_aggregate(parts, ops)
    spmd = spmd_global_aggregate(parts, ops, mesh=None)
    np.testing.assert_allclose(np.asarray(host["delta"]["w"]),
                               np.asarray(spmd["delta"]["w"]), rtol=1e-6)
    assert float(host["count"]) == float(spmd["count"])


def test_spmd_aggregate_with_mesh():
    mesh = make_host_mesh(1)
    parts, ops = _partials(K=3)
    host = global_aggregate(parts, ops)
    spmd = spmd_global_aggregate(parts, ops, mesh=mesh)
    np.testing.assert_allclose(np.asarray(host["delta"]["w"]),
                               np.asarray(spmd["delta"]["w"]), rtol=1e-6)
