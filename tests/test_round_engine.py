"""Event-driven round engine tests (DESIGN.md §3).

The anchor is bit-exactness: ``round_engine="bsp"`` must reproduce the
pre-engine monolithic ``run_round`` *exactly* — same params (same float
summation order) and same makespan history.  ``LegacyServer`` below freezes
the pre-refactor loop verbatim as the golden reference; executors run under
a deterministic :class:`TickTimer` so measured durations are a pure function
of the code path taken, which makes makespan equality a proof that the
engine issues the identical call sequence.

Around the anchor: semi-sync deadline carry-over, async bounded-staleness
convergence against the flat single-process reference, failure injection
under every mode, the comm ``poll`` contract, chunked ``run_queue``
emission, and the orphaned-pending-schedule (dropped clients) regression.
"""
import concurrent.futures as cf
import math
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.comm.collective import CollectiveComm
from repro.comm.local import LocalComm
from repro.core import (ClientStateManager, LocalAggregator, Op, ParrotServer,
                        RoundMetrics, SequentialExecutor, TickTimer,
                        VirtualClock, make_algorithm, run_flat_reference)
from repro.core.aggregation import (ClientResult, global_aggregate,
                                    merge_partials, scale_partial,
                                    staleness_weight)
from repro.core.executor import ExecutorFailure, hetero_gpus
from repro.core.scheduler import ClientTask, Schedule, split_chunks
from repro.data import make_classification_clients


def _loss_fn(params, batch):
    logits = batch["x"] @ params["w"] + params["b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(lse - gold)


GRAD_FN = jax.jit(jax.value_and_grad(_loss_fn))
PARAMS0 = {"w": jnp.zeros((8, 4)), "b": jnp.zeros((4,))}


def _data(n=40, seed=1):
    return make_classification_clients(n, dim=8, n_classes=4,
                                       mean_samples=30, batch_size=10,
                                       seed=seed)


def _eval_loss(params, data):
    tot, n = 0.0, 0
    for d in data.values():
        for b in d.batches:
            tot += float(_loss_fn(params, b)) * len(b["y"])
            n += len(b["y"])
    return tot / n


def _max_diff(a, b):
    return max(float(jnp.max(jnp.abs(x - y)))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def _make_server(algo, data, K=4, fail_at=None, speed=None, tick=None,
                 clients_per_round=10, **kw):
    sm = ClientStateManager(tempfile.mkdtemp())
    execs = []
    for k in range(K):
        e = SequentialExecutor(
            k, algo, state_manager=sm,
            speed_model=speed or (lambda kk, r: 0.0),
            timer=TickTimer(1.0) if tick else None)
        if fail_at and k == fail_at[0]:
            e.fail_at = fail_at[1]
        execs.append(e)
    return ParrotServer(params=PARAMS0, algorithm=algo, executors=execs,
                        data_by_client=data,
                        clients_per_round=clients_per_round, seed=7, **kw)


# ---------------------------------------------------------------------------
# the frozen pre-engine loop (golden reference for bit-exactness)
# ---------------------------------------------------------------------------

class LegacyServer(ParrotServer):
    """Verbatim copy of the monolithic ``run_round``/``_dispatch`` as they
    existed before the engine refactor (PR 2 state).  Frozen here as the
    golden reference — do not "fix" or modernise this code."""

    def _dispatch(self, rnd, schedule, payload, skip_map=None):
        live = list(self.executors)
        self.comm.broadcast(payload, live, tag="broadcast")
        reports = []
        failed = []
        done_clients = set()

        def run(k):
            return self.executors[k].run_queue(
                rnd, schedule.queue(k), payload, self.data_by_client,
                skip_clients=(skip_map or {}).get(k))

        if self.parallel_dispatch:
            with cf.ThreadPoolExecutor(max_workers=len(live)) as pool:
                futs = {pool.submit(run, k): k for k in live}
                for fut in cf.as_completed(futs):
                    k = futs[fut]
                    try:
                        reports.append(fut.result())
                    except ExecutorFailure:
                        failed.append(k)
        else:
            for k in live:
                try:
                    reports.append(run(k))
                except ExecutorFailure:
                    failed.append(k)

        if failed:
            for rep in reports:
                done_clients.update(rep.completed_clients)
            survivors = [k for k in live if k not in failed]
            if not survivors:
                raise RuntimeError("all executors failed")
            leftovers = []
            for k in failed:
                for t in schedule.queue(k):
                    if t.client not in done_clients:
                        done_clients.add(t.client)
                        leftovers.append(t)
                del self.executors[k]
            for i, t in enumerate(leftovers):
                k = survivors[i % len(survivors)]
                rep = self.executors[k].run_queue(
                    rnd, [t], payload, self.data_by_client)
                reports.append(rep)

        for rep in reports:
            self.comm.executor_send(rep.executor,
                                    self._maybe_compress(rep.partial),
                                    tag="partial")
            rep.partial = self._maybe_decompress(
                self.comm.recv_from_executor(rep.executor, tag="partial"))
        return reports, len(failed)

    def run_round(self):
        rnd = self.round
        t_wall = time.perf_counter()
        if self._next_tasks is not None:
            tasks, self._next_tasks = self._next_tasks, None
        else:
            tasks = self.select_clients()

        if self._pending_schedule is not None:
            schedule, overlapped = self._pending_schedule, True
            self._pending_schedule = None
        else:
            schedule, overlapped = self.scheduler.schedule(
                rnd, tasks, list(self.executors)), False

        payload = self.algorithm.broadcast_payload(self.params,
                                                   self.server_state)
        skip_map, n_backups = self._plan_backups(schedule)
        reports, n_failed = self._dispatch(rnd, schedule, payload, skip_map)

        if self.overlap_scheduling:
            self.estimator.record_many(
                [rec for r in reports for rec in r.records])
            self._next_tasks = self.select_clients()
            self._pending_schedule = self.scheduler.schedule(
                rnd + 1, self._next_tasks, list(self.executors))

        partials = [r.partial for r in reports]
        ops = self.algorithm.ops()
        agg = global_aggregate(partials, ops)
        self.params, self.server_state = self.algorithm.server_update(
            self.params, agg, self.server_state,
            self.algorithm.server_scalars(sum(r.n_tasks for r in reports),
                                          len(self.data_by_client)))

        records = [rec for r in reports for rec in r.records]
        err = float("nan")
        if self.estimator.last_fit:
            err = self.estimator.estimation_error(self.estimator.last_fit,
                                                  records)
        if not self.overlap_scheduling:
            self.estimator.record_many(records)
        makespan = max((r.virtual_time for r in reports), default=0.0)
        stats = self.comm.stats.reset()
        metrics = RoundMetrics(
            round=rnd, makespan=makespan,
            wall_time=time.perf_counter() - t_wall,
            schedule_time=0.0 if overlapped else schedule.schedule_time_s,
            estimate_time=0.0 if overlapped else schedule.estimate_time_s,
            predicted_makespan=schedule.predicted_makespan,
            comm_bytes=stats.bytes_sent, comm_trips=stats.trips,
            n_clients=len(tasks), n_executors=len(self.executors),
            estimation_error=err, failures=n_failed,
            extra={"backup_tasks": float(n_backups)})
        self.history.append(metrics)
        self.round += 1

        if self.checkpoint_manager is not None:
            self.checkpoint_manager.maybe_save(self)
        return metrics


BSP_VARIANTS = {
    "plain": {},
    "overlap": {"overlap_scheduling": True},
    "backups": {"backup_fraction": 0.3, "warmup_rounds": 2},
    "failure": {"warmup_rounds": 2},     # + fail_at on executor 2
}


@pytest.mark.parametrize("variant", sorted(BSP_VARIANTS))
def test_bsp_bit_exact_vs_legacy(variant):
    """mode="bsp" reproduces the pre-engine loop bit-exactly: identical
    params (same float summation order) AND identical makespan history
    (under TickTimer, makespan equality == call-sequence equality)."""
    kw = dict(BSP_VARIANTS[variant])
    fail = (2, (1, 1)) if variant == "failure" else None
    data = _data()
    legacy = LegacyServer.__new__(LegacyServer)
    srv_l = _make_server(make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
                         fail_at=fail, tick=True, **kw)
    # rebind to the legacy loop with identical construction
    srv_l.__class__ = LegacyServer
    srv_e = _make_server(make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
                         fail_at=fail, tick=True, round_engine="bsp", **kw)
    ms_l = [srv_l.run_round() for _ in range(5)]
    ms_e = [srv_e.run_round() for _ in range(5)]
    assert _max_diff(srv_l.params, srv_e.params) == 0.0
    assert [m.makespan for m in ms_l] == [m.makespan for m in ms_e]
    assert [m.comm_trips for m in ms_l] == [m.comm_trips for m in ms_e]
    assert [m.n_executors for m in ms_l] == [m.n_executors for m in ms_e]
    if variant == "failure":
        assert ms_e[1].failures == 1 and ms_e[2].n_executors == 3


def test_bsp_bit_exact_stateful_scaffold():
    srv_l = _make_server(make_algorithm("scaffold", GRAD_FN, 0.1), _data(),
                         tick=True)
    srv_l.__class__ = LegacyServer
    srv_e = _make_server(make_algorithm("scaffold", GRAD_FN, 0.1), _data(),
                         tick=True, round_engine="bsp")
    ms_l = [srv_l.run_round() for _ in range(4)]
    ms_e = [srv_e.run_round() for _ in range(4)]
    assert _max_diff(srv_l.params, srv_e.params) == 0.0
    assert [m.makespan for m in ms_l] == [m.makespan for m in ms_e]


# ---------------------------------------------------------------------------
# clock + timer
# ---------------------------------------------------------------------------

def test_virtual_clock_orders_by_time_then_seq():
    c = VirtualClock()
    c.push(2.0, "b")
    c.push(1.0, "a")
    c.push(1.0, "a2")
    c.push(3.0, "c")
    kinds = [c.pop().kind for _ in range(4)]
    assert kinds == ["a", "a2", "b", "c"]
    assert c.now == 3.0
    with pytest.raises(ValueError):
        c.push(1.0, "past")


def test_tick_timer_is_deterministic():
    t1, t2 = TickTimer(0.5), TickTimer(0.5)
    assert [t1() for _ in range(3)] == [t2() for _ in range(3)]


# ---------------------------------------------------------------------------
# comm poll pair
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("comm_cls", [LocalComm, CollectiveComm])
def test_poll_non_blocking_contract(comm_cls):
    comm = comm_cls()
    assert comm.poll(0, "partial") is None
    comm.executor_send(0, {"x": 1}, tag="partial")
    assert comm.poll(1, "partial") is None          # wrong executor
    assert comm.poll(0, "other") is None            # wrong tag
    assert comm.poll(0, "partial") == {"x": 1}
    assert comm.poll(0, "partial") is None          # consumed


def test_local_poll_preserves_fifo():
    comm = LocalComm()
    comm.executor_send(3, "a", tag="t")
    comm.executor_send(3, "b", tag="t")
    assert comm.poll(3, "t") == "a"
    assert comm.poll(3, "t") == "b"


# ---------------------------------------------------------------------------
# chunked executor emission
# ---------------------------------------------------------------------------

def test_chunked_run_queue_emits_and_merges():
    data = _data(12)
    algo = make_algorithm("fedavg", GRAD_FN, 0.1)
    tasks = [ClientTask(c, data[c].n_samples) for c in sorted(data)[:10]]
    payload = algo.broadcast_payload(PARAMS0, algo.server_init(PARAMS0))

    ex1 = SequentialExecutor(0, algo)
    whole = ex1.run_queue(0, tasks, payload, data)

    seen = []
    ex2 = SequentialExecutor(1, algo)
    chunked = ex2.run_queue(0, tasks, payload, data, chunk_size=3,
                            on_partial=seen.append)
    assert len(seen) == math.ceil(len(tasks) / 3)
    assert [r.n_tasks for r in seen] == [3, 3, 3, 1]
    # same clients complete (order differs: signature-blocking is per-chunk)
    assert sorted(chunked.completed_clients) == sorted(whole.completed_clients)
    # merged chunk partials aggregate to the same result as one span
    ops = algo.ops()
    a = global_aggregate([whole.partial], ops)
    b = global_aggregate([chunked.partial], ops)
    assert _max_diff(a["delta"], b["delta"]) < 1e-6
    # per-chunk partials fold independently to the same aggregate too
    c = global_aggregate([r.partial for r in seen], ops)
    assert _max_diff(a["delta"], c["delta"]) < 1e-6


def test_chunked_fail_at_uses_global_task_index():
    data = _data(12)
    algo = make_algorithm("fedavg", GRAD_FN, 0.1)
    tasks = [ClientTask(c, data[c].n_samples) for c in sorted(data)[:8]]
    payload = algo.broadcast_payload(PARAMS0, algo.server_init(PARAMS0))
    ex = SequentialExecutor(0, algo, fail_at=(0, 5))
    seen = []
    with pytest.raises(ExecutorFailure) as ei:
        ex.run_queue(0, tasks, payload, data, chunk_size=2,
                     on_partial=seen.append)
    assert ei.value.task_index == 5
    assert len(seen) == 2          # chunks [0,1] and [2,3] completed first


def test_split_chunks():
    ts = [ClientTask(i, 1) for i in range(7)]
    assert [len(c) for c in split_chunks(ts, 3)] == [3, 3, 1]
    assert [t.client for c in split_chunks(ts, 3) for t in c] == list(range(7))


# ---------------------------------------------------------------------------
# staleness weighting on the wire format
# ---------------------------------------------------------------------------

def _partial_of(value, weight, extra_sum=None):
    ops = {"delta": Op.WEIGHTED_AVG}
    if extra_sum is not None:
        ops["cnt"] = Op.SUM
    agg = LocalAggregator(ops)
    payload = {"delta": {"w": jnp.full((4,), float(value))}}
    if extra_sum is not None:
        payload["cnt"] = jnp.asarray([float(extra_sum)])
    agg.fold(ClientResult(payload, ops, weight=weight))
    return agg.partial(), ops


def test_staleness_weight_formula():
    assert staleness_weight(0, 0.5) == 1.0
    assert staleness_weight(1, 0.5) == pytest.approx(1 / 1.5)
    assert staleness_weight(4, 0.25) == pytest.approx(0.5)


def test_scale_partial_weighted_avg_discounts_contribution():
    p1, ops = _partial_of(1.0, weight=2.0, extra_sum=10.0)
    p2, _ = _partial_of(5.0, weight=2.0, extra_sum=10.0)
    gamma = 0.5
    out = global_aggregate([p1, scale_partial(p2, gamma)], ops)
    # weighted avg with relative weight gamma on the stale partial
    expect = (2.0 * 1.0 + gamma * 2.0 * 5.0) / (2.0 + gamma * 2.0)
    assert _max_diff(out["delta"], {"w": jnp.full((4,), expect)}) < 1e-6
    # SUM entries are discounted to gamma * value
    assert float(out["cnt"][0]) == pytest.approx(10.0 + gamma * 10.0)


def test_scale_partial_gamma_one_is_identity():
    p, _ = _partial_of(3.0, weight=1.0)
    assert scale_partial(p, 1.0) is p


def test_merge_partials_matches_list_aggregate():
    p1, ops = _partial_of(1.0, weight=1.0)
    p2, _ = _partial_of(2.0, weight=3.0)
    p3, _ = _partial_of(-4.0, weight=2.0)
    merged = None
    for p in (p1, p2, p3):
        merged = merge_partials(merged, p)
    a = global_aggregate([p1, p2, p3], ops)
    b = global_aggregate([merged], ops)
    assert _max_diff(a["delta"], b["delta"]) < 1e-6
    assert merged["n_clients"] == 3
    # merging never mutated the source partials
    c = global_aggregate([p1, p2, p3], ops)
    assert _max_diff(a["delta"], c["delta"]) == 0.0


# ---------------------------------------------------------------------------
# schedule remap (orphaned pending schedule → dropped clients fix)
# ---------------------------------------------------------------------------

def test_schedule_remap_rehomes_orphans():
    ts = [ClientTask(i, 10) for i in range(6)]
    s = Schedule({0: ts[:2], 1: ts[2:4], 7: ts[4:]}, 0.0, 0.0, 0.0)
    moved = s.remap([0, 1])
    assert moved == 2
    assert 7 not in s.assignment
    got = sorted(t.client for q in s.assignment.values() for t in q)
    assert got == list(range(6))
    assert s.remap([0, 1]) == 0    # idempotent


def test_orphaned_pending_schedule_clients_still_run():
    """Regression: with overlap_scheduling, an executor lost between rounds
    leaves the pre-computed schedule assigning a queue to a dead id; the
    engine must re-map it instead of silently dropping those clients."""
    def run(overlap):
        srv = _make_server(make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
                           overlap_scheduling=overlap)
        srv.run_round()
        del srv.executors[3]        # elastic removal between rounds
        m = srv.run_round()
        return srv, m

    srv_o, m_o = run(True)          # pending schedule had 4 executors
    srv_n, m_n = run(False)         # fresh schedule over 3 executors
    assert m_o.extra.get("remapped_tasks", 0.0) > 0
    # every selected client folded: the overlapped run matches the
    # non-overlapped run (same rng stream → same cohort)
    assert _max_diff(srv_o.params, srv_n.params) < 1e-5


# ---------------------------------------------------------------------------
# semi-sync
# ---------------------------------------------------------------------------

def _semi_server(data, deadline_frac=0.5, over_select=1.5, speed=None, K=4,
                 fail_at=None, **kw):
    return _make_server(
        make_algorithm("fedavg", GRAD_FN, 0.1), data, K=K, speed=speed,
        fail_at=fail_at, round_engine="semi-sync",
        engine_opts={"deadline_frac": deadline_frac,
                     "over_select": over_select, "chunk_size": 2}, **kw)


def test_semi_sync_warmup_round_completes_fully():
    """No workload model yet → deadline ∞ → the whole (over-selected)
    cohort lands and nothing carries."""
    srv = _semi_server(_data())
    m = srv.run_round()
    assert m.extra["carried_tasks"] == 0.0
    assert m.extra["landed_clients"] == m.n_clients == 15   # ceil(1.5 × 10)


def test_semi_sync_deadline_carries_unfinished_tasks():
    """A 19×-slow executor cannot drain its queue by the deadline: its tail
    carries into the next round's pool and still gets trained."""
    srv = _semi_server(_data(), deadline_frac=0.5,
                       speed=hetero_gpus({3: 18.0}), warmup_rounds=1)
    loss0 = _eval_loss(srv.params, srv.data_by_client)
    ms = srv.run(8)
    carried = [m.extra["carried_tasks"] for m in ms]
    assert sum(carried) > 0, carried
    # a round after a carry still folds work and the pool includes the
    # carried tasks (n_clients == carried + fresh)
    r = next(i for i, c in enumerate(carried) if c > 0)
    assert ms[r + 1].n_clients == 15
    assert ms[r + 1].extra["landed_clients"] > 0
    assert _eval_loss(srv.params, srv.data_by_client) < loss0


def test_semi_sync_failure_recovers_and_shrinks_K():
    srv = _semi_server(_data(), fail_at=(2, (1, 1)), warmup_rounds=2)
    ms = srv.run(4)
    assert sum(m.failures for m in ms) == 1
    assert len(srv.executors) == 3
    assert ms[-1].n_executors == 3
    assert all(np.isfinite(jax.tree.leaves(srv.params)[0]).all()
               for _ in [0])
    assert ms[-1].extra["landed_clients"] > 0


# ---------------------------------------------------------------------------
# async (bounded staleness)
# ---------------------------------------------------------------------------

def _async_server(data, lam=0.5, speed=None, K=4, fail_at=None,
                  scheduler_policy="parrot", **kw):
    return _make_server(
        make_algorithm("fedavg", GRAD_FN, 0.1), data, K=K, speed=speed,
        fail_at=fail_at, round_engine="async",
        scheduler_policy=scheduler_policy,
        engine_opts={"staleness_lambda": lam, "chunk_size": 2}, **kw)


def test_async_converges_close_to_flat_reference():
    """20 bounded-staleness update windows land within tolerance of the
    20-round synchronous flat reference (ISSUE acceptance: async trains,
    staleness discount does not stall convergence)."""
    data = _data(60, seed=3)
    flat, _ = run_flat_reference(
        PARAMS0, make_algorithm("fedavg", GRAD_FN, 0.1), data,
        clients_per_round=10, n_rounds=20, seed=7)
    # TickTimer pins the event interleaving (and therefore the staleness
    # pattern), so the comparison does not drift with host noise
    srv = _async_server(_data(60, seed=3), tick=True)
    srv.run(20)
    loss0 = _eval_loss(PARAMS0, data)
    loss_flat = _eval_loss(flat, data)
    loss_async = _eval_loss(srv.params, srv.data_by_client)
    assert loss_async < loss0                       # it learned
    assert abs(loss_async - loss_flat) / loss_flat < 0.10


def test_async_stale_folds_are_discounted_and_counted():
    srv = _async_server(_data())
    ms = srv.run(8)
    stale = sum(m.extra["stale_folds"] for m in ms)
    # pipelining guarantees in-flight chunks across update boundaries
    assert stale > 0
    assert all(m.extra["mean_staleness"] >= 0 for m in ms)


def test_async_work_stealing_engages_under_heterogeneity():
    """With round-robin placement and one 15×-slow executor, fast executors
    drain their queues first and must steal from the straggler."""
    srv = _async_server(_data(60, seed=3), speed=hetero_gpus({0: 15.0}),
                        scheduler_policy="none")
    ms = srv.run(6)
    assert sum(m.extra["steals"] for m in ms) > 0


def test_async_failure_recovers_and_shrinks_K():
    srv = _async_server(_data(), fail_at=(1, (0, 1)))
    ms = srv.run(5)
    assert sum(m.failures for m in ms) == 1
    assert len(srv.executors) == 3
    loss = _eval_loss(srv.params, srv.data_by_client)
    assert np.isfinite(loss)


def test_async_failure_at_update_boundary_does_not_resurrect():
    """Regression: a failure event pushed by the very fold that reaches the
    update goal used to leave the loop with the event pending; the post-
    update wake then re-dispatched onto the doomed executor and the next
    round crashed (KeyError) when the stale chunk_done popped.  The executor
    must stay dead, its post-failure refill tasks must re-home, and no
    client may be lost from the in-flight set."""
    # rnd=-1 wildcard: die at the 4th dispatched task whichever update
    # window it lands in — with goal=2 every fold is an update boundary,
    # so the failure event is pending when a window closes
    srv = _make_server(
        make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
        K=3, fail_at=(1, (-1, 3)), tick=True, clients_per_round=2,
        round_engine="async", engine_opts={"chunk_size": 2})
    ms = srv.run(8)          # used to raise KeyError on a matching config
    assert sum(m.failures for m in ms) == 1
    assert len(srv.executors) == 2
    assert 1 not in srv.executors
    # the engine keeps making progress after the K shrink
    assert ms[-1].n_clients > 0


def test_bsp_only_knobs_rejected_by_des_engines():
    for knob in ({"backup_fraction": 0.2}, {"parallel_dispatch": True},
                 {"overlap_scheduling": True}):
        for mode in ("semi-sync", "async"):
            with pytest.raises(ValueError):
                _make_server(make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
                             round_engine=mode, **knob)


def test_async_fail_at_index_is_cumulative_across_refills():
    """fail_at's task index counts tasks dispatched by the executor
    cumulatively across refills (a per-refill reset made mid-stream indices
    unreachable): index 9 only exists if offsets accumulate past the first
    refill (each executor starts with ~4 queued tasks)."""
    srv = _make_server(
        make_algorithm("fedavg", GRAD_FN, 0.1), _data(),
        K=4, fail_at=(1, (-1, 9)), tick=True, clients_per_round=8,
        round_engine="async", engine_opts={"chunk_size": 2})
    ms = srv.run(6)
    assert sum(m.failures for m in ms) == 1
    assert len(srv.executors) == 3


def test_async_deterministic_under_tick_timer():
    def run():
        srv = _async_server(_data(), tick=True)
        ms = srv.run(6)
        return srv.params, [m.makespan for m in ms]

    p1, m1 = run()
    p2, m2 = run()
    assert m1 == m2
    assert _max_diff(p1, p2) == 0.0


def test_async_makespan_beats_bsp_under_heterogeneity():
    """The headline claim: folding partials as they land hides stragglers
    that BSP must wait for.  Both modes run under identical dynamic
    heterogeneity and a TickTimer, so every executor block costs the same
    virtual dt and the comparison is deterministic: BSP pays
    ``max_k Σ (1+η_k)``, async pays roughly the fleet mean."""
    from repro.core.executor import dynamic_env

    def mean_makespan(mode, opts=None):
        srv = _make_server(
            make_algorithm("fedavg", GRAD_FN, 0.1), _data(80, seed=3),
            speed=dynamic_env(4, 10), round_engine=mode, tick=True,
            engine_opts=opts or {}, warmup_rounds=2,
            clients_per_round=32)
        ms = [srv.run_round().makespan for _ in range(10)]
        return float(np.mean(ms[3:]))

    bsp = mean_makespan("bsp")
    asy = mean_makespan("async", {"chunk_size": 8})
    assert asy < bsp * 0.75, (bsp, asy)
