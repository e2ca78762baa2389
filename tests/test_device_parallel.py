"""Device-parallel executors (DESIGN.md §8): placement, device-keyed caches,
device-resident wire path, and K-device vs single-device bit-exactness.

The parity matrix needs K > 1 real (virtual host) devices, and the device
count is frozen at backend init — so those scenarios run in a subprocess
that forces ``--xla_force_host_platform_device_count=4``
(``device_parity_driver.py``).  Everything else runs in-process and adapts
to however many devices this process has (1 in the plain tier-1 job, 4 in
the CI multi-device job).
"""
import json
import os
import re
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ClientStateManager, DevicePlacement, TickTimer,
                        make_algorithm)
from repro.core.aggregation import (LocalAggregator, Op, expand_aggregate,
                                    global_aggregate)
from repro.core.clock import VirtualClock
from repro.core.client_step import engine_for
from repro.core.flat import FlatLayout, flat_sums
from repro.core.placement import colocate
from repro.comm.local import LocalComm

HERE = os.path.dirname(__file__)


# ---------------------------------------------------------------------------
# placement unit tests (device-count agnostic)
# ---------------------------------------------------------------------------

def test_placement_round_robin_and_release():
    devs = jax.devices()
    pl = DevicePlacement(range(2 * len(devs) + 1))
    for k in range(2 * len(devs) + 1):
        assert pl.device(k) is devs[k % len(devs)]
    assert pl.server_device is devs[0]
    pl.release(0)
    assert 0 not in pl.executors()
    # mesh covers the distinct live devices, in pin order
    assert [d.id for d in pl.mesh().devices.flat] == \
        [d.id for d in pl.devices()]


def test_placement_from_pins_preserves_map():
    devs = jax.devices()
    pins = {7: devs[0], 3: devs[-1]}
    pl = DevicePlacement.from_pins(pins)
    assert pl.device(7) is devs[0] and pl.device(3) is devs[-1]
    assert pl.executors() == [3, 7]


def test_fail_device_repins_or_raises():
    devs = jax.devices()
    pl = DevicePlacement(range(4))
    if len(devs) == 1:
        with pytest.raises(RuntimeError):
            pl.fail_device(devs[0])
        return
    moved = pl.fail_device(devs[0])
    assert moved                      # executors lived there
    live_ids = {d.id for d in devs[1:]}
    for k in pl.executors():
        assert pl.device(k).id in live_ids


@pytest.mark.parametrize("psum_min", [0, None])
def test_global_fold_matches_host_aggregate(psum_min):
    """Placement fold == plain global_aggregate, bitwise, on however many
    devices this process has.  ``psum_min=0`` forces the shard_map/psum
    branch whenever each partial owns its own device (multi-device runs —
    the 4-virtual-device CI job), so the sharded reduction itself is
    pinned, not just the colocating fallback the small default threshold
    selects at test sizes."""
    devs = jax.devices()
    ops = {"delta": Op.WEIGHTED_AVG, "count": Op.SUM}
    payload = {"delta": {"w": np.arange(12, dtype=np.float32)},
               "count": np.float32(1.0)}
    layout = FlatLayout.build(ops, payload)
    rng = np.random.default_rng(0)
    K = max(2, len(devs))
    parts = []
    for i in range(K):
        buf = {"weighted": rng.standard_normal(12).astype(np.float32) * 11,
               "unit": rng.standard_normal(1).astype(np.float32)}
        parts.append({"sums": flat_sums(
            {g: jax.device_put(jnp.asarray(b), devs[i % len(devs)])
             for g, b in buf.items()}),
            "layout": layout, "weights": {"delta": 2.0 + i},
            "counts": {"delta": 2, "count": 1}, "collected": {},
            "n_clients": 2})
    pl = DevicePlacement(range(K))
    if psum_min is not None:
        pl.psum_min_elements = psum_min
    reduced = pl.global_fold(parts, ops)
    folded = expand_aggregate(reduced, ops)
    host_parts = [dict(p, sums=flat_sums(
        {g: np.asarray(b) for g, b in p["sums"]["buffers"].items()}))
        for p in parts]
    ref = global_aggregate(host_parts, ops)
    np.testing.assert_array_equal(np.asarray(folded["delta"]["w"]),
                                  np.asarray(ref["delta"]["w"]))
    np.testing.assert_array_equal(np.asarray(folded["count"]),
                                  np.asarray(ref["count"]))
    # the fold lands on the server device
    for buf in reduced["buffers"].values():
        assert list(buf.sharding.device_set) == [pl.server_device]


def test_colocate_moves_only_when_needed():
    devs = jax.devices()
    a = jax.device_put(jnp.ones(3), devs[0])
    assert colocate(a, a) is a
    b = jax.device_put(jnp.ones(3), devs[-1])
    moved = colocate(b, a)
    assert list(moved.sharding.device_set) == [devs[0]]


# ---------------------------------------------------------------------------
# device-keyed caches
# ---------------------------------------------------------------------------

def _grad_fn():
    def loss(params, batch):
        return jnp.mean((batch["x"] @ params["w"] - batch["y"]) ** 2)
    return jax.jit(jax.value_and_grad(loss))


def _client_data(n_batches=2, seed=0):
    from repro.core.algorithms import ClientData
    rng = np.random.default_rng(seed)
    bs = [{"x": rng.standard_normal((4, 3)).astype(np.float32),
           "y": rng.standard_normal((4,)).astype(np.float32)}
          for _ in range(n_batches)]
    return ClientData(batches=bs, n_samples=4 * n_batches)


def test_engine_for_keys_on_device():
    algo = make_algorithm("fedavg", _grad_fn(), 0.1)
    devs = jax.devices()
    default = engine_for(algo)
    assert engine_for(algo) is default            # stable for None
    pinned = engine_for(algo, devs[0])
    assert pinned is not default                  # device-keyed
    assert engine_for(algo, devs[0]) is pinned    # stable per device
    if len(devs) > 1:
        assert engine_for(algo, devs[1]) is not pinned
    assert pinned.device is devs[0]


def test_pinned_engine_outputs_resident():
    devs = jax.devices()
    dev = devs[-1]
    algo = make_algorithm("fedavg", _grad_fn(), 0.1)
    eng = engine_for(algo, dev)
    params = {"w": np.zeros(3, dtype=np.float32)}
    payload = algo.broadcast_payload(params, algo.server_init(params))
    res, _ = eng.run_client(payload, _client_data(), None)
    for leaf in jax.tree.leaves(res.payload):
        assert list(leaf.sharding.device_set) == [dev]


def test_flatten_device_commit():
    ops = {"delta": Op.WEIGHTED_AVG}
    payload = {"delta": {"w": np.ones((4, 4), np.float32)}}
    layout = FlatLayout.build(ops, payload)
    dev = jax.devices()[-1]
    bufs = layout.flatten(payload, device=dev)
    assert list(bufs["weighted"].sharding.device_set) == [dev]
    # same layout, other placements: no cross-wiring, values identical
    host = layout.flatten(payload)
    np.testing.assert_array_equal(np.asarray(bufs["weighted"]),
                                  np.asarray(host["weighted"]))
    assert list(layout.zeros(dev)["weighted"].sharding.device_set) == [dev]


def test_local_aggregator_device_resident_partial():
    dev = jax.devices()[-1]
    ops = {"delta": Op.WEIGHTED_AVG}
    agg = LocalAggregator(ops, device=dev)
    from repro.core.aggregation import ClientResult
    agg.fold(ClientResult({"delta": {"w": np.ones(5, np.float32)}}, ops, 2.0))
    part = agg.partial()
    buf = part["sums"]["buffers"]["weighted"]
    assert list(buf.sharding.device_set) == [dev]


# ---------------------------------------------------------------------------
# stacked-batch device cache
# ---------------------------------------------------------------------------

def _executor(**kw):
    from repro.core.executor import SequentialExecutor
    algo = make_algorithm("fedavg", _grad_fn(), 0.1)
    return SequentialExecutor(0, algo, **kw)


def test_batch_cache_hit_and_identity():
    ex = _executor(device=jax.devices()[-1])
    data = _client_data()
    s1, m1 = ex._prep_batches(1, data)
    s2, m2 = ex._prep_batches(1, data)
    assert s1 is s2 and m1 is m2                  # served from cache
    for leaf in jax.tree.leaves(s1):
        assert list(leaf.sharding.device_set) == [jax.devices()[-1]]


def test_batch_cache_lru_eviction_respects_budget():
    data = {i: _client_data(seed=i) for i in range(8)}
    one = _executor()
    s, m = one._prep_batches(0, data[0])
    per_client = sum(int(x.nbytes) for x in jax.tree.leaves(s)) + m.nbytes
    ex = _executor(batch_cache_bytes=3 * per_client)
    for i in range(8):
        ex._prep_batches(i, data[i])
    assert len(ex._batch_cache) == 3
    assert set(ex._batch_cache) == {5, 6, 7}      # LRU kept the newest
    assert ex._batch_cache_used <= ex.batch_cache_bytes
    # re-touch oldest survivor, insert one more: 5 was just used, 6 evicts
    ex._prep_batches(5, data[5])
    ex._prep_batches(0, data[0])
    assert set(ex._batch_cache) == {7, 5, 0}


def test_batch_cache_invalidates_on_swapped_dataset():
    ex = _executor()
    d1, d2 = _client_data(seed=1), _client_data(seed=2)
    s1, _ = ex._prep_batches(1, d1)
    s2, _ = ex._prep_batches(1, d2)               # same client, new data
    assert s1 is not s2
    np.testing.assert_array_equal(np.asarray(jax.tree.leaves(s2)[0][0]),
                                  d2.batches[0]["x"])


def test_batch_cache_disabled_with_zero_budget():
    ex = _executor(batch_cache_bytes=0)
    data = _client_data()
    ex._prep_batches(1, data)
    assert len(ex._batch_cache) == 0


def test_set_device_drops_device_caches_keeps_costs():
    devs = jax.devices()
    ex = _executor(device=devs[0])
    ex._prep_batches(1, _client_data())
    ex._block_cost[("sig", 4)] = 0.5
    ex.set_device(devs[-1] if len(devs) > 1 else None)
    assert not ex._batch_cache and ex._payload_cache._key is None
    assert ex._block_cost == {("sig", 4): 0.5}


# ---------------------------------------------------------------------------
# device-aware state manager
# ---------------------------------------------------------------------------

def test_state_manager_device_load_and_keep_device():
    dev = jax.devices()[-1]
    with tempfile.TemporaryDirectory() as d:
        sm = ClientStateManager(d)
        st = {"c": np.arange(6, dtype=np.float32)}
        sm.save(0, st)
        out = sm.load_many([0, 1], device=dev)
        assert out[1] is None
        assert list(out[0]["c"].sharding.device_set) == [dev]
        np.testing.assert_array_equal(np.asarray(out[0]["c"]), st["c"])
        # keep_device save keeps the jax array; spill still round-trips
        dev_state = {"c": jax.device_put(jnp.arange(3.0), dev)}
        sm.save_many({2: dev_state}, keep_device=True)
        assert sm.load(2)["c"] is dev_state["c"]
        sm2 = ClientStateManager(d, memory_budget_bytes=1)  # spill everything
        sm2.save_many({3: dev_state, 4: dev_state}, keep_device=True)
        np.testing.assert_array_equal(np.asarray(sm2.load(3)["c"]),
                                      np.arange(3.0, dtype=np.float32))


# ---------------------------------------------------------------------------
# device-resident wire path (no host round-trip, no copy)
# ---------------------------------------------------------------------------

def test_local_comm_ships_device_buffers_by_reference():
    dev = jax.devices()[-1]
    buf = jax.device_put(jnp.arange(8.0), dev)
    partial = {"sums": flat_sums({"weighted": buf}), "layout": None,
               "weights": {}, "counts": {}, "collected": {}, "n_clients": 1}
    comm = LocalComm()
    comm.executor_send(3, partial, tag="partial")
    got = comm.poll(3, tag="partial")
    assert got is partial                               # zero-copy
    assert got["sums"]["buffers"]["weighted"] is buf    # still resident
    assert list(buf.sharding.device_set) == [dev]
    assert comm.stats.bytes_sent > 0                    # accounted anyway


def test_collective_comm_ships_device_buffers_by_reference():
    from repro.comm.collective import CollectiveComm
    dev = jax.devices()[-1]
    buf = jax.device_put(jnp.arange(8.0), dev)
    partial = {"sums": flat_sums({"weighted": buf}), "collected": {}}
    comm = CollectiveComm()
    comm.executor_send(1, partial, tag="partial")
    got = comm.poll(1, tag="partial")
    assert got is partial
    assert got["sums"]["buffers"]["weighted"] is buf


# ---------------------------------------------------------------------------
# clock serialisation (async checkpoint plumbing)
# ---------------------------------------------------------------------------

def test_virtual_clock_state_roundtrip_preserves_order_and_seq():
    c = VirtualClock()
    c.push(2.0, "b", "late")
    c.push(1.0, "a", "early")
    c.push(1.0, "a2", "tie")        # same time: seq breaks the tie
    c.pop()                          # consume "early"; now=1.0, seq=3
    r = VirtualClock.from_state_dict(c.state_dict())
    assert r.now == c.now and r._seq == c._seq
    ev = r.push(1.5, "new")
    assert ev.seq == 3               # numbering continues, not restarts
    kinds = [r.pop().kind for _ in range(len(r))]
    assert kinds == ["a2", "new", "b"]


# ---------------------------------------------------------------------------
# K-device parity matrix (subprocess with 4 forced host devices)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_four_device_parity_matrix():
    env = dict(os.environ)
    flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "",
                   env.get("XLA_FLAGS", ""))
    env["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=4"
                        ).strip()
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, os.path.join(HERE, "device_parity_driver.py")],
        capture_output=True, text=True, env=env, timeout=900)
    assert r.returncode == 0, r.stderr[-4000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    out = json.loads(line[len("RESULT "):])
    assert out["n_devices"] == 4
    failures = {k: v for k, v in out.items() if v is False}
    assert not failures, f"parity failures: {failures}"
