"""The compiled server step against the eager reference.

``ParrotServer.server_update`` runs the server's side of a round as one
compiled program (``round.ServerStep``): slice, divide and unflatten the
reduced aggregate, then ``algorithm.server_update``.  The reference is the
eager path: ``global_aggregate`` then ``algorithm.server_update`` with the
same host-side scalars.  The two must agree bit for bit, for every
algorithm, bf16 and fp32 params, and every shape of aggregate the engines
hand on: one partial, K = 3, compressed wire buffers and the async engine's
staleness-scaled buffer.  Payloads are random normals, so any change in the
order or precision of an operation shows."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (ClientResult, LocalAggregator, Op,
                                    global_aggregate, merge_partials,
                                    reduce_partials, scale_partial)
from repro.core.algorithms import ALGORITHMS, make_algorithm
from repro.core.compression import make_compressor
from repro.core.round import ServerStep, server_step_for

N_TOTAL = 23
CASES = ("one", "k3", "compressed", "async")


def _tree(rng, dtype, scale=1.0):
    return {"w": jnp.asarray(rng.standard_normal((6, 5)) * scale, dtype),
            "b": jnp.asarray(rng.standard_normal((5,)) * scale, dtype)}


def _algorithm(name):
    return make_algorithm(name, lambda p, b: (0.0, p), 0.1,
                          server_lr=0.7)


def _results(algo, rng, dtype, n):
    """``n`` client results shaped as the algorithm's clients send them:
    deltas in the params' dtype, FedNova's tau a scalar, Mime's full-batch
    gradient a COLLECT entry."""
    out = []
    for _ in range(n):
        payload = {}
        for name in algo.ops():
            payload[name] = (jnp.float32(rng.integers(1, 9)) if name == "tau"
                             else _tree(rng, dtype, 0.01))
        out.append(ClientResult(payload, algo.ops(),
                                weight=float(rng.integers(1, 40))))
    return out


def _partials(algo, case, rng, dtype, n_clients=6):
    ops = algo.ops()
    k = {"one": 1, "k3": 3, "compressed": 2, "async": 2}[case]
    aggs = [LocalAggregator(ops) for _ in range(k)]
    for i, r in enumerate(_results(algo, rng, dtype, n_clients)):
        aggs[i % k].fold(r)
    parts = [a.partial() for a in aggs]
    if case == "compressed":
        codec = make_compressor("int8")
        parts = [codec.decompress_partial(codec.compress_partial(
            p, key=f"exec{i}")) for i, p in enumerate(parts)]
    if case == "async":
        buf = None
        for p, gamma in zip(parts, (1.0 / 1.5, 1.0 / 2.25)):
            buf = merge_partials(buf, scale_partial(p, gamma))
        parts = [buf]
    return parts


def _state(algo, params, rng):
    """The algorithm's server state with non-zero values of its dtypes."""
    return jax.tree.map(
        lambda z: jnp.asarray(rng.standard_normal(z.shape) * 0.1, z.dtype),
        algo.server_init(params))


def _assert_bit_exact(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        assert x.dtype == y.dtype
        np.testing.assert_array_equal(np.asarray(x, np.float32),
                                      np.asarray(y, np.float32))


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_compiled_step_matches_the_eager_reference(name, dtype, case):
    rng = np.random.default_rng(7)
    algo = _algorithm(name)
    params = _tree(rng, dtype)
    state = _state(algo, params, rng)
    parts = _partials(algo, case, rng, dtype)
    scalars = algo.server_scalars(5, N_TOTAL)
    ref = algo.server_update(params, global_aggregate(parts, algo.ops()),
                             state, scalars)
    got = ServerStep(algo)(params, state, reduce_partials(parts, algo.ops()),
                           scalars)
    _assert_bit_exact(ref, got)
    assert all(x.dtype == jnp.dtype(dtype)
               for x in jax.tree.leaves(got[0]))


@pytest.mark.parametrize("name", sorted(ALGORITHMS))
def test_three_rounds_compile_the_step_once(name):
    """Other weights and another ``_n_selected`` each round: the step's
    per-round numbers are traced, so one executable serves all three."""
    rng = np.random.default_rng(11)
    algo = _algorithm(name)
    params = _tree(rng, "bfloat16")
    state = algo.server_init(params)
    step = server_step_for(algo)
    assert step is server_step_for(algo)
    for rnd, n_selected in enumerate((4, 6, 5)):
        parts = _partials(algo, "k3", rng, "bfloat16")
        scalars = algo.server_scalars(n_selected, N_TOTAL)
        ref = algo.server_update(params,
                                 global_aggregate(parts, algo.ops()), state,
                                 scalars)
        params, state = step(params, state,
                             reduce_partials(parts, algo.ops()), scalars)
        _assert_bit_exact(ref, (params, state))
        assert step.compile_count() == 1, rnd


def test_reduce_partials_hands_on_divisors_and_collect_lists():
    rng = np.random.default_rng(3)
    algo = _algorithm("mime")
    parts = _partials(algo, "k3", rng, "float32")
    red = reduce_partials(parts, algo.ops())
    assert set(red["buffers"]) == {"weighted"}
    assert red["divisors"] == {"delta": float(sum(
        p["weights"]["delta"] for p in parts))}
    assert len(red["collected"]["full_grad"]) == 6
    with pytest.raises(ValueError, match="flat partials"):
        reduce_partials([{"sums": {}, "counts": {}}], {"x": Op.SUM})
