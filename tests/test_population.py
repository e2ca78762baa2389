"""Streamed population axis (DESIGN.md §11): registry-backed populations,
O(cohort) rng-identical selection, the hierarchical fold tree, and
streamed-vs-eager engine parity.

The selection anchor: ``ClientPopulation.sample`` draws positional indices
via ``rng.choice(pool_len, size, replace=False)`` and maps them through the
sorted registry — numpy's Generator consumes the bit stream identically to
``rng.choice(pool_list, ...)``, so cohorts must match the legacy
implementation (frozen below) id-for-id, in order, across sequential draws.
"""
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClientStateManager, LocalAggregator, ParrotServer,
                        SequentialExecutor, TickTimer, make_algorithm)
from repro.core.aggregation import (expand_aggregate, global_aggregate,
                                    tree_reduce_partials)
from repro.core.population import (EagerPopulation, LazyPopulation,
                                   as_population)
from repro.data import (make_classification_clients,
                        make_classification_population)

from test_flat_aggregation import OPS, _assert_bit_exact, _int_results


# ---------------------------------------------------------------------------
# legacy selection (frozen pre-population implementation — the rng pin)
# ---------------------------------------------------------------------------

def _legacy_select(rng, ids, k, exclude=None, avail=None):
    if exclude:
        pool = sorted(set(ids) - set(exclude))
    else:
        pool = sorted(ids)
    if avail is not None:
        pool = [c for c in pool if avail(c)]
    size = min(k, len(pool))
    if size <= 0:
        return []
    return [int(c) for c in rng.choice(pool, size=size, replace=False)]


def _sparse_ids(seed=0, n=200):
    rng = np.random.default_rng(seed)
    return sorted(int(c) for c in
                  rng.choice(10_000, size=n, replace=False))


# ---------------------------------------------------------------------------
# populations: mapping interface + fetch cache
# ---------------------------------------------------------------------------

def _lazy(n=30, cache=1 << 20, sizes=None):
    sizes = [10 + (c % 7) for c in range(n)] if sizes is None else sizes
    calls = []

    def factory(c):
        calls.append(c)
        from repro.core.algorithms import ClientData
        x = np.full((4, 2), float(c), np.float32)
        return ClientData(batches=[{"x": x}], n_samples=sizes[c])

    return LazyPopulation(sizes, factory, fetch_cache_bytes=cache), calls


def test_eager_population_is_mapping_compatible():
    data = make_classification_clients(12, dim=4, n_classes=3,
                                       mean_samples=10, batch_size=5)
    pop = as_population(data)
    assert isinstance(pop, EagerPopulation)
    assert as_population(pop) is pop
    assert len(pop) == 12 and sorted(pop) == sorted(data)
    assert 3 in pop and 99 not in pop
    assert pop[3] is data[3]
    assert pop.n_samples(3) == data[3].n_samples
    assert len(list(pop.values())) == 12          # Mapping mixin

    # the cached sorted registry survives repeated calls and tracks
    # membership changes
    ids = pop.ids_array()
    assert pop.ids_array() is ids
    data[100] = data[3]
    assert 100 in pop and pop.ids_array()[-1] == 100


def test_lazy_population_registry_without_materialization():
    pop, calls = _lazy(50)
    assert len(pop) == 50
    assert pop.n_samples(13) == 10 + 13 % 7
    assert 49 in pop and 50 not in pop
    with pytest.raises(KeyError):
        pop[50]
    assert calls == []            # registry reads never touch the factory
    d = pop[7]
    assert d.n_samples == pop.n_samples(7) and calls == [7]
    assert pop[7] is d            # cached: stable identity, no refetch
    assert calls == [7]


def test_lazy_population_fetch_cache_is_bounded():
    pop, calls = _lazy(30, cache=100)      # one client's batch is 32 bytes
    for c in range(30):
        pop[c]
    assert pop.cache_bytes <= 100
    assert pop.stats["evictions"] > 0
    # evicted client re-fetches deterministically
    first = np.asarray(pop[0].batches[0]["x"]).copy()
    assert calls.count(0) >= 2
    np.testing.assert_array_equal(first, np.full((4, 2), 0.0, np.float32))


def test_streamed_generator_matches_materialized_twin():
    pop = make_classification_population(15, dim=4, n_classes=3,
                                         mean_samples=12, batch_size=5,
                                         seed=3)
    twin = pop.materialize()
    assert sorted(twin) == list(range(15))
    for c in (0, 7, 14):
        assert pop.n_samples(c) == twin[c].n_samples
        for a, b in zip(pop[c].batches, twin[c].batches):
            np.testing.assert_array_equal(a["x"], b["x"])
            np.testing.assert_array_equal(a["y"], b["y"])
    # access order must not matter: a fresh population read backwards
    pop2 = make_classification_population(15, dim=4, n_classes=3,
                                          mean_samples=12, batch_size=5,
                                          seed=3)
    for c in reversed(range(15)):
        np.testing.assert_array_equal(pop2[c].batches[0]["x"],
                                      twin[c].batches[0]["x"])


# ---------------------------------------------------------------------------
# O(cohort) selection: rng-identical to the legacy implementation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("ids_kind", ["dense", "sparse"])
def test_sample_matches_legacy_no_filters(ids_kind):
    ids = list(range(100)) if ids_kind == "dense" else _sparse_ids()
    pop = EagerPopulation({c: None for c in ids})
    r1, r2 = np.random.default_rng(7), np.random.default_rng(7)
    for k in (1, 5, 17, len(ids), len(ids) + 10):
        got = pop.sample(r1, k)
        want = _legacy_select(r2, ids, k)
        assert got == want
    # sequential draws stay in lockstep (identical rng consumption)
    assert r1.bit_generator.state == r2.bit_generator.state


@pytest.mark.parametrize("ids_kind", ["dense", "sparse"])
def test_sample_matches_legacy_with_exclude(ids_kind):
    ids = list(range(100)) if ids_kind == "dense" else _sparse_ids(1)
    pop = EagerPopulation({c: None for c in ids})
    rng = np.random.default_rng(11)
    r1, r2 = np.random.default_rng(5), np.random.default_rng(5)
    for trial in range(25):
        n_ex = int(rng.integers(0, 40))
        # excludes may include ids not in the population (in-flight clients
        # of a shrunk population) and duplicates
        exclude = [int(c) for c in rng.choice(
            np.asarray(ids + [77777, 88888]), size=n_ex)] if n_ex else None
        k = int(rng.integers(1, 30))
        got = pop.sample(r1, k, exclude=exclude)
        want = _legacy_select(r2, ids, k, exclude=exclude)
        assert got == want, f"trial {trial}: {got} != {want}"
    assert r1.bit_generator.state == r2.bit_generator.state


def test_sample_matches_legacy_with_filters():
    ids = _sparse_ids(2)
    pop = EagerPopulation({c: None for c in ids})
    avail = lambda c: (c % 3) != 0
    r1, r2 = np.random.default_rng(3), np.random.default_rng(3)
    for k in (1, 9, 40):
        got = pop.sample(r1, k, exclude=[ids[0], ids[5]], filters=[avail])
        want = _legacy_select(r2, ids, k, exclude=[ids[0], ids[5]],
                              avail=avail)
        assert got == want
    assert r1.bit_generator.state == r2.bit_generator.state


def test_lazy_and_eager_sample_identically():
    pop, calls = _lazy(120)
    eager = EagerPopulation({c: None for c in range(120)})
    r1, r2 = np.random.default_rng(42), np.random.default_rng(42)
    for _ in range(10):
        assert pop.sample(r1, 13, exclude=[5, 6]) == \
            eager.sample(r2, 13, exclude=[5, 6])
    assert calls == []            # selection never materialises clients


def test_server_selection_pins_legacy_cohorts():
    """ParrotServer.select_clients end-to-end vs the frozen implementation
    (same seed, same sequence of calls — the satellite's rng pin)."""
    data = make_classification_clients(40, dim=4, n_classes=3,
                                       mean_samples=10, batch_size=5, seed=1)
    algo = make_algorithm("fedavg", _grad_fn(), 0.05, local_epochs=1)
    srv = ParrotServer(params=_params0(), algorithm=algo,
                       executors=[SequentialExecutor(0, algo)],
                       data_by_client=data, clients_per_round=10, seed=7)
    ref = np.random.default_rng(7)
    ids = sorted(data)
    for exclude in (None, [3, 4, 5], [0], None):
        tasks = srv.select_clients(exclude=exclude)
        want = _legacy_select(ref, ids, 10, exclude=exclude)
        assert [t.client for t in tasks] == want
        assert all(t.n_samples == data[t.client].n_samples for t in tasks)


# ---------------------------------------------------------------------------
# hierarchical fold tree
# ---------------------------------------------------------------------------

def _partials(K, n_results=11, seed=0):
    results = _int_results(n_results, seed=seed)
    aggs = [LocalAggregator(OPS) for _ in range(K)]
    for i, r in enumerate(results):
        aggs[i % K].fold(r)
    return [a.partial() for a in aggs]


@pytest.mark.parametrize("K", [1, 2, 3, 5])
@pytest.mark.parametrize("fan_in", [2, 3])
def test_tree_fold_bit_identical_to_flat(K, fan_in):
    """The fan-in tree must reproduce the flat left-fold exactly on the
    integer payloads (every partial sum exactly representable, so any
    grouping must yield identical bits) — COLLECT order included."""
    flat = global_aggregate(_partials(K), OPS)
    tree = global_aggregate(
        tree_reduce_partials(_partials(K), fan_in), OPS)
    _assert_bit_exact(flat["delta"], tree["delta"])
    _assert_bit_exact(flat["tau"], tree["tau"])
    _assert_bit_exact(flat["count"], tree["count"])
    assert [w for w, _ in flat["trace"]] == [w for w, _ in tree["trace"]]
    for (_, a), (_, b) in zip(flat["trace"], tree["trace"]):
        _assert_bit_exact(a, b)


def test_tree_fold_narrow_list_is_passthrough():
    parts = _partials(3)
    assert tree_reduce_partials(parts, 8) is not parts  # copied list
    assert tree_reduce_partials(parts, 8) == parts      # same objects


def test_tree_fold_reduces_width():
    parts = _partials(13, n_results=26)
    level = tree_reduce_partials(parts, 4)
    assert len(level) <= 4
    _assert_bit_exact(global_aggregate(parts, OPS)["delta"],
                      global_aggregate(level, OPS)["delta"])


def test_server_global_fold_wide_k_routes_through_tree():
    data = make_classification_clients(8, dim=4, n_classes=3,
                                       mean_samples=10, batch_size=5)
    algo = make_algorithm("fedavg", _grad_fn(), 0.05, local_epochs=1)
    srv = ParrotServer(params=_params0(), algorithm=algo,
                       executors=[SequentialExecutor(0, algo)],
                       data_by_client=data, clients_per_round=4,
                       fold_fan_in=3, seed=0)
    parts = _partials(7, n_results=21)
    ops = algo.ops()
    _assert_bit_exact(global_aggregate(parts, ops)["delta"],
                      expand_aggregate(srv.global_fold(parts), ops)["delta"])


# ---------------------------------------------------------------------------
# streamed vs eager engine parity (all three engines)
# ---------------------------------------------------------------------------

def _grad_fn():
    def _loss(params, batch):
        logits = batch["x"] @ params["w"] + params["b"]
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
        return jnp.mean(lse - gold)
    return jax.jit(jax.value_and_grad(_loss))


def _params0():
    return {"w": jnp.zeros((6, 3)), "b": jnp.zeros((3,))}


def _run(engine, data_or_pop, rounds=3):
    algo = make_algorithm("scaffold", _grad_fn(), 0.05, local_epochs=1)
    sm = ClientStateManager(tempfile.mkdtemp(prefix="pop_"),
                            memory_budget_bytes=1 << 14, shard_clients=8)
    execs = [SequentialExecutor(k, algo, state_manager=sm,
                                timer=TickTimer(1.0))
             for k in range(3)]
    srv = ParrotServer(params=_params0(), algorithm=algo, executors=execs,
                       data_by_client=data_or_pop, clients_per_round=8,
                       round_engine=engine, seed=7)
    hist = [srv.run_round() for _ in range(rounds)]
    return srv.params, [m.makespan for m in hist], hist


@pytest.mark.parametrize("engine", ["bsp", "semi-sync", "async"])
def test_streamed_run_bit_exact_with_eager(engine):
    """A registry-backed streamed population must replay the eager run
    params-bit-exactly under every engine (selection, scheduling, folds and
    virtual time all identical) even with a tiny fetch cache forcing
    evictions mid-round."""
    def pop():
        return make_classification_population(
            20, dim=6, n_classes=3, mean_samples=12, batch_size=5, seed=2,
            fetch_cache_bytes=4 << 10)

    eager_params, eager_ms, _ = _run(engine, pop().materialize())
    lazy_params, lazy_ms, _ = _run(engine, pop())
    for a, b in zip(jax.tree.leaves(eager_params),
                    jax.tree.leaves(lazy_params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert eager_ms == lazy_ms


# ---------------------------------------------------------------------------
# state-manager stats surfacing
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine", ["bsp", "semi-sync", "async"])
def test_round_metrics_surface_state_manager_stats(engine):
    data = make_classification_clients(12, dim=6, n_classes=3,
                                       mean_samples=10, batch_size=5, seed=4)
    _, _, hist = _run(engine, data, rounds=2)
    for m in hist:
        sm = m.extra.get("state_manager")
        assert sm is not None
        for key in ("hits", "misses", "spills", "loads", "prefetched",
                    "mem_bytes", "shard_ram_bytes", "disk_bytes"):
            assert key in sm
    # round 2 re-selects known clients: the cache must report activity
    r2 = hist[1].extra["state_manager"]
    assert r2["hits"] + r2["misses"] > 0


def test_stateless_runs_omit_state_manager_extra():
    data = make_classification_clients(10, dim=6, n_classes=3,
                                       mean_samples=10, batch_size=5)
    algo = make_algorithm("fedavg", _grad_fn(), 0.05, local_epochs=1)
    sm = ClientStateManager(tempfile.mkdtemp(prefix="pop_"))
    srv = ParrotServer(params=_params0(), algorithm=algo,
                       executors=[SequentialExecutor(0, algo,
                                                     state_manager=sm)],
                       data_by_client=data, clients_per_round=4, seed=0)
    m = srv.run_round()
    assert "state_manager" not in m.extra
