"""Executors: sequential client simulation on a device (Algorithm 2,
``Device_Executes``).

``SequentialExecutor`` is the real thing: it loads client state, runs the
algorithm's client_update, saves state, and folds results into the local
aggregator — measuring per-task wall time for the workload estimator.

``speed_model`` implements the paper's Appendix-A protocol for benchmarking
scheduling under heterogeneous / unstable devices on homogeneous hardware: a
per-(executor, round) slowdown ratio η_k(r) scales the *reported* task time.
We account the scaled time in virtual time rather than sleeping, which makes
the paper's timing experiments deterministic and fast; the round engine then
computes the BSP round time as max_k Σ_task time — exactly the paper's
"server waits for the slowest executor".

Straggler backup tasks: when ``backup_fraction > 0`` the round engine
duplicates the tail of the predicted-slowest queue onto the
predicted-fastest executor (speculative duplicates resolved through the
``skip_clients`` hook below, so each client folds exactly once) — tail
mitigation at 1000-node scale where a single dead/slow host would
otherwise stall every round.

Aggregation inside ``run_queue`` uses the flat-buffer ``LocalAggregator``:
the first round builds a ``FlatLayout`` for the algorithm's payload, which
is cached here and reused for every subsequent round (flatten-once), and
client deltas fold in micro-batches of ``agg_micro_batch`` — one kernel
dispatch per B clients instead of one per pytree leaf per client.

Chunked execution (DESIGN.md §3): a *chunk* — a slice of the queue run as
its own span via ``run_queue(<slice>, task_offset=)``, yielding its own
shippable flat partial — is the executor-side unit the event-driven engines
dispatch.  The engines drive chunks one at a time through the shared
virtual clock (lazy dispatch is what makes the DES causally correct), so
they call ``run_queue`` per chunk themselves; ``run_queue(chunk_size=,
on_partial=)`` is the self-contained streaming form of the same contract
for callers without an event loop, and delegates to the identical per-chunk
path.  The wall-clock source is injectable (``timer``; see
``core/clock.py``) so engine-equivalence tests can pin down measured
durations deterministically.

Client training itself runs through the compiled engine
(``core.client_step``): ``run_queue`` groups same-signature clients into
blocks of ``client_block`` and runs one vmapped jit-scan per block, folding
the stacked (B, ...) deltas straight into the flat aggregator
(``fold_block``) — no per-client ``ClientResult`` round-trip.  Virtual time
for a block is attributed per client (block time / B, scaled by the speed
model's η), so the workload estimator keeps seeing per-client records.  The
eager per-task path is kept for ``use_compiled_steps=False``, for ragged
clients, and for rounds with a pending ``fail_at`` injection (task-index
granularity must stay exact there).

Device pinning (DESIGN.md §8): ``device=`` pins the executor to one local
JAX device — the broadcast payload is committed there once per round, the
client-step executables compile per device (``engine_for(algorithm,
device)``), client states load onto / stay resident on it, the flat
aggregator folds there, and the emitted partial ships device-resident.  A
pinned executor also dispatches *steady-state* blocks without blocking
(``nonblocking``): once a (signature, B) block cost has been measured, the
cached cost stands in for the wall measurement and the device computation is
left in flight — K pinned executors driven from one Python thread then
genuinely overlap on K devices, which is where the device-count speedup
comes from.  Virtual-time semantics are unchanged: the cached cost is
exactly what the running-min filter would have converged to, and under a
``TickTimer`` both paths measure identical durations (every same-shaped
span contains the same number of timer calls), so the K-device parity tests
stay bit-exact.

Stacked-batch cache: stacking a client's batches (host stack + transfer)
repeats every round in the vanilla path; ``batch_cache_bytes`` bounds an
LRU cache of per-client stacked (batches, mask) arrays resident on the
executor's device, so steady-state rounds re-use them and the block stack
runs on-device (``jnp.stack``).
"""
from __future__ import annotations

import dataclasses
import time
import weakref
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core import client_step
from repro.core.aggregation import ClientResult, LocalAggregator, Op
from repro.core.algorithms import ClientData, FLAlgorithm
from repro.core.scheduler import ClientTask
from repro.core.state_manager import ClientStateManager
from repro.core.telemetry import span
from repro.core.workload import RunRecord


SpeedModel = Callable[[int, int], float]   # (executor, round) -> eta >= 0


def homogeneous(executor: int, rnd: int) -> float:
    return 0.0


def hetero_gpus(ratios: Dict[int, float]) -> SpeedModel:
    """Fixed per-executor slowdown ratios η_k (paper Appendix A, Hete. GPU)."""
    return lambda k, r: ratios.get(k, 0.0)


def dynamic_env(n_executors: int, total_rounds: int) -> SpeedModel:
    """Unstable devices: η_k(r) = 1 + cos(3.14 r / R + k) (paper Appendix A)."""
    import math

    def eta(k: int, r: int) -> float:
        return 1.0 + math.cos(3.14 * r / max(total_rounds, 1) + k)

    return eta


@dataclass
class ExecutorReport:
    executor: int
    partial: Dict[str, Any]
    records: List[RunRecord]
    virtual_time: float          # Σ per-task simulated time (BSP makespan input)
    wall_time: float
    n_tasks: int
    completed_clients: List[int] = field(default_factory=list)
    # achieved wire size of the shipped partial (set by the engines when a
    # NetworkModel prices uploads; 0 = not measured)
    wire_bytes: int = 0
    # jit compiles observed while this report ran (jax.monitoring listener
    # in client_step) — host-side cost attribution, process-local: warm jit
    # caches legitimately zero it, so it never enters trace determinism
    compiles: int = 0


class SequentialExecutor:
    """One Parrot device (a GPU in the paper; a mesh slice on TPU)."""

    def __init__(self, executor_id: int, algorithm: FLAlgorithm,
                 state_manager: Optional[ClientStateManager] = None,
                 speed_model: SpeedModel = homogeneous,
                 use_agg_kernel: bool = False,
                 agg_micro_batch: int = 16,
                 use_compiled_steps: bool = True,
                 client_block: int = 8,
                 fail_at: Optional[Tuple[int, int]] = None,
                 timer: Optional[Callable[[], float]] = None,
                 device: Optional[Any] = None,
                 nonblocking: Optional[bool] = None,
                 batch_cache_bytes: int = 128 << 20):
        self.id = executor_id
        self.algorithm = algorithm
        self.state_manager = state_manager
        self.speed_model = speed_model
        self.use_agg_kernel = use_agg_kernel
        self.agg_micro_batch = agg_micro_batch
        self.use_compiled_steps = use_compiled_steps
        self.client_block = max(1, int(client_block))
        # device pin (core/placement.py): None = process default device
        # (the pre-multi-device behaviour, bit-for-bit)
        self.device = device
        # non-blocking steady-state dispatch only makes sense when pinned
        # (unpinned executors all share the default device anyway)
        self.nonblocking = (device is not None if nonblocking is None
                            else bool(nonblocking))
        # LRU cache of per-client stacked (batches, mask), device-resident
        # when pinned; 0 disables
        self.batch_cache_bytes = int(batch_cache_bytes)
        self._batch_cache: "OrderedDict[int, Tuple[Any, Any, Any, int]]" = \
            OrderedDict()
        self._batch_cache_used = 0
        # whole-block stacks for the gang path (repeated cohorts re-use the
        # assembled (B, ...) arrays; shares the byte budget above).  Not
        # kept on donating backends — the block jit would invalidate them.
        self._block_stack_cache: "OrderedDict[Tuple, Tuple]" = OrderedDict()
        self._payload_cache = client_step.PlacedCache()
        # injectable wall-clock source (core/clock.py): the engine
        # equivalence tests swap in a deterministic TickTimer so measured
        # durations become a pure function of the code path taken
        self.timer = timer or time.perf_counter
        self._layout_cache = None   # FlatLayout, computed once, reused per round
        # steady-state block cost per (signature, B): running minimum of
        # clean measurements — virtual time stays deterministic-ish on a
        # noisy shared host, as the paper's Appendix-A protocol intends
        self._block_cost: Dict[Any, float] = {}
        # per-client batch signature, keyed on the ClientData identity (a
        # weakref, so a swapped dataset re-keys and a recycled id() cannot
        # alias): the walk is O(n_batches x n_leaves) and must not repeat
        # every round
        self._sig_cache: Dict[int, Tuple[Any, Any]] = {}
        # fault-injection hook for the fault-tolerance tests:
        # (round, task_index) at which this executor dies.  Round -1 is a
        # wildcard (any round); see ``fail_pending``.  Scheduled fault plans
        # (core/faults.py) are the first-class path — this remains the
        # task-index-granular escape hatch.
        self.fail_at = fail_at
        # step stats of the compiled clients folded since the server last
        # took them (device arrays; ``ParrotServer._commit_metrics``)
        self._step_stats: List[Any] = []

    def _keep_stats(self, payload: Dict[str, Any]) -> Dict[str, Any]:
        """``payload`` without the compiled step's ``"stats"``, which are
        kept for ``take_step_stats``.  Called where a result is folded, so
        a discarded re-run or a padded block row never counts."""
        if "stats" not in payload:
            return payload
        payload = dict(payload)
        self._step_stats.append(payload.pop("stats"))
        return payload

    def take_step_stats(self) -> List[Any]:
        """The step stats kept since the last call (one tree per client, or
        per block with a leading client axis), forgotten here."""
        out, self._step_stats = self._step_stats, []
        return out

    def fail_pending(self, rnd: int) -> bool:
        """A ``fail_at`` injection is armed for round ``rnd`` (round -1
        wildcards to every round).  The single definition of the wildcard —
        ``run_queue``'s eager-path switch and the gang-dispatch eligibility
        check must agree, or a gang wave could run a round the injection
        was due to interrupt at task granularity."""
        return self.fail_at is not None and self.fail_at[0] in (rnd, -1)

    # ------------------------------------------------------------- device
    def set_device(self, device: Optional[Any]) -> None:
        """Re-pin the executor (placement remap after a device failure).
        Device-resident caches are dropped; measured block costs survive
        (they describe the computation, not the silicon it sat on)."""
        if device is self.device:
            return
        self.device = device
        self._batch_cache.clear()
        self._block_stack_cache.clear()
        self._batch_cache_used = 0
        self._payload_cache.clear()
        if self.nonblocking and device is None:
            self.nonblocking = False

    def _place_payload(self, payload: Dict) -> Dict:
        """Commit the broadcast payload to the executor's device ONCE per
        payload object (engines broadcast one object per round/version;
        chunks of the same round reuse the committed copy).  This covers
        the eager path too; the engine's own ``_commit_payload`` memo then
        sees the placed object and its walk is a one-time no-op."""
        if self.device is None:
            return payload
        return self._payload_cache.get(
            (payload,), lambda: jax.device_put(payload, self.device))

    def _prep_batches(self, client: int, data: ClientData) -> Tuple[Any, Any]:
        """The client's stacked (batches, mask), served from the
        device-resident LRU cache (capped at ``batch_cache_bytes``)."""
        hit = self._batch_cache.get(client)
        if hit is not None and hit[0]() is data:
            self._batch_cache.move_to_end(client)
            return hit[1], hit[2]
        stacked, mask = client_step.stack_batches(data, assume_uniform=True)
        if self.device is not None:
            stacked = jax.device_put(stacked, self.device)
            mask = jax.device_put(mask, self.device)
        if self.batch_cache_bytes <= 0:
            return stacked, mask
        nbytes = int(mask.nbytes) + sum(
            int(x.nbytes) for x in jax.tree.leaves(stacked))
        if hit is not None:          # stale entry (dataset swapped)
            self._batch_cache_used -= self._batch_cache.pop(client)[3]
        self._batch_cache[client] = (weakref.ref(data), stacked, mask, nbytes)
        self._batch_cache_used += nbytes
        self._evict_to_budget()
        return stacked, mask

    def _evict_to_budget(self) -> None:
        """Shrink the shared byte budget across BOTH stacked-batch caches:
        cohort block stacks go first (they are speculative — a cohort that
        never repeats is dead weight, and per-client entries can rebuild
        them), then per-client LRU entries down to the last one."""
        while self._batch_cache_used > self.batch_cache_bytes:
            if self._block_stack_cache:
                self._batch_cache_used -= \
                    self._block_stack_cache.popitem(last=False)[1][3]
            elif len(self._batch_cache) > 1:
                self._batch_cache_used -= \
                    self._batch_cache.popitem(last=False)[1][3]
            else:
                break

    def _prep_block_stack(self, block: List[ClientTask],
                          data_by_client: Dict[int, ClientData],
                          B_pad: int) -> Tuple[Any, Any]:
        """The block's padded (B_pad, ...) stacked batches + masks, cached
        by cohort: repeated schedules (full participation, stable LPT
        splits) re-dispatch the identical block every round, so the
        assembled device arrays are re-served instead of re-stacked.
        Falls through to a fresh stack on donating backends (the block jit
        consumes its batch buffers there) or when caching is disabled."""
        cacheable = (self.batch_cache_bytes > 0
                     and jax.default_backend() not in ("tpu", "gpu"))
        key = (tuple(t.client for t in block), B_pad)
        if cacheable:
            hit = self._block_stack_cache.get(key)
            if hit is not None and all(
                    w() is data_by_client[c]
                    for c, w in zip(key[0], hit[0])):
                self._block_stack_cache.move_to_end(key)
                return hit[1], hit[2]
        cp = [self._prep_batches(t.client, data_by_client[t.client])
              for t in block]
        cp = cp + [cp[0]] * (B_pad - len(block))
        eng = client_step.engine_for(self.algorithm, self.device)
        stacked, mask = eng._stack_jit([p[0] for p in cp],
                                       [p[1] for p in cp])
        if cacheable:
            nbytes = int(mask.nbytes) + sum(
                int(x.nbytes) for x in jax.tree.leaves(stacked))
            refs = tuple(weakref.ref(data_by_client[c]) for c in key[0])
            if key in self._block_stack_cache:
                self._batch_cache_used -= self._block_stack_cache.pop(key)[3]
            self._block_stack_cache[key] = (refs, stacked, mask, nbytes)
            self._batch_cache_used += nbytes
            self._evict_to_budget()
        return stacked, mask

    def run_queue(self, rnd: int, tasks: List[ClientTask], payload: Dict,
                  data_by_client: Dict[int, ClientData],
                  skip_clients: Optional[set] = None,
                  chunk_size: Optional[int] = None,
                  on_partial: Optional[Callable[["ExecutorReport"], None]]
                  = None,
                  task_offset: int = 0) -> ExecutorReport:
        """Run a task queue (``Device_Executes``).

        ``chunk_size`` switches to chunked *streaming* execution: the queue
        is cut into chunks of at most that many tasks, each chunk runs as
        its own span (own LocalAggregator, so its partial is shippable on
        its own) and is emitted through ``on_partial`` the moment it
        completes.  The returned report merges the chunk reports; its
        ``partial`` is the merge of the chunk partials (identical aggregate
        to one unchunked run).  The engines themselves call this method once
        per chunk with ``task_offset`` instead (their event loop owns the
        interleaving) — both routes run the same per-chunk code.

        ``task_offset`` keeps ``fail_at``'s task index global to the
        executor's dispatch stream when the caller passes slices of it.
        """
        with span("executor", round=rnd, executor=self.id):
            if chunk_size is not None:
                return self._run_chunked(rnd, tasks, payload, data_by_client,
                                         skip_clients, chunk_size, on_partial,
                                         task_offset)
            agg = LocalAggregator(self.algorithm.ops(),
                                  use_kernel=self.use_agg_kernel,
                                  micro_batch=self.agg_micro_batch,
                                  layout=self._layout_cache,
                                  device=self.device)
            payload = self._place_payload(payload)
            records: List[RunRecord] = []
            completed: List[int] = []
            t_start = self.timer()
            c0 = client_step.compile_events()
            eta = self.speed_model(self.id, rnd)
            # fail_at is task-index-granular: a round with a pending injection
            # runs the eager per-task loop so the index semantics stay exact
            # (round -1 is a wildcard: fire at that dispatch index in any round
            # — the async engine's dispatch stream spans update boundaries)
            if self.use_compiled_steps and not self.fail_pending(rnd):
                vtime = self._run_blocked(rnd, tasks, payload,
                                          data_by_client, skip_clients, agg,
                                          records, completed, eta)
            else:
                vtime = self._run_eager(rnd, tasks, payload, data_by_client,
                                        skip_clients, agg, records, completed,
                                        eta, task_offset)
            self._layout_cache = agg.layout     # flatten-once across rounds
            return ExecutorReport(
                executor=self.id, partial=agg.partial(), records=records,
                virtual_time=vtime, wall_time=self.timer() - t_start,
                n_tasks=len(completed), completed_clients=completed,
                compiles=client_step.compile_events() - c0)

    def _run_chunked(self, rnd, tasks, payload, data_by_client, skip_clients,
                     chunk_size, on_partial, task_offset) -> ExecutorReport:
        from repro.core.aggregation import merge_partials
        from repro.core.scheduler import split_chunks
        merged: Optional[Dict] = None
        records: List[RunRecord] = []
        completed: List[int] = []
        vtime = wall = 0.0
        compiles = 0
        offset = task_offset
        for chunk in split_chunks(tasks, chunk_size):
            rep = self.run_queue(rnd, chunk, payload, data_by_client,
                                 skip_clients, task_offset=offset)
            offset += len(chunk)
            if on_partial is not None:
                on_partial(rep)
            merged = merge_partials(merged, rep.partial)
            records.extend(rep.records)
            completed.extend(rep.completed_clients)
            vtime += rep.virtual_time
            wall += rep.wall_time
            compiles += rep.compiles
        return ExecutorReport(
            executor=self.id, partial=merged if merged is not None else
            LocalAggregator(self.algorithm.ops()).partial(),
            records=records, virtual_time=vtime, wall_time=wall,
            n_tasks=len(completed), completed_clients=completed,
            compiles=compiles)

    # ------------------------------------------------------------------
    def _run_eager(self, rnd, tasks, payload, data_by_client, skip_clients,
                   agg, records, completed, eta, task_offset=0) -> float:
        """Legacy per-task reference path (one eager client_update per
        task; also the fault-injection path)."""
        vtime = 0.0
        for i, task in enumerate(tasks, start=task_offset):
            if self.fail_at is not None and self.fail_at[1] == i \
                    and self.fail_pending(rnd):
                raise ExecutorFailure(
                    self.id, rnd, i, device=self.device,
                    chunk=(task_offset, task_offset + len(tasks)),
                    vtime=vtime)
            if skip_clients and task.client in skip_clients:
                continue  # result already produced by a backup replica
            t0 = self.timer()
            state = None
            if self.algorithm.stateful:
                state = self.state_manager.load(task.client)
                if state is None:
                    state = self.algorithm.client_init_state(payload["params"])
            steps = self.algorithm.local_epochs * len(
                data_by_client[task.client].batches)
            with span("client_step", steps=steps, scanned=steps):
                result, new_state = self.algorithm.client_update(
                    payload, data_by_client[task.client], state)
            if self.algorithm.stateful and new_state is not None:
                self.state_manager.save(task.client, new_state)
            agg.fold(result)
            completed.append(task.client)
            measured = self.timer() - t0
            simulated = measured * (1.0 + eta)
            vtime += simulated
            records.append(RunRecord(round=rnd, client=task.client,
                                     executor=self.id,
                                     n_samples=task.n_samples,
                                     time=simulated))
        return vtime

    # ------------------------------------------------------------------
    def _plan_blocks(self, tasks: List[ClientTask],
                     data_by_client: Dict[int, ClientData]
                     ) -> List[Tuple[Tuple, List[ClientTask]]]:
        """Group same-signature clients into blocks of ``client_block``
        (first-seen group order; queue order within a group).  Ragged
        clients get singleton eager blocks."""
        groups: Dict[Any, List[ClientTask]] = {}
        order: List[Any] = []
        for t in tasks:
            data = data_by_client[t.client]
            cached = self._sig_cache.get(t.client)
            if cached is not None and cached[0]() is data:
                sig = cached[1]
            else:
                sig = client_step.batch_signature(data)
                self._sig_cache[t.client] = (weakref.ref(data), sig)
            key = ("eager", t.client) if sig is None else ("block", sig)
            if key not in groups:
                groups[key] = []
                order.append(key)
            groups[key].append(t)
        if len(self._sig_cache) > 4096:
            # streamed populations cycle through many distinct clients; an
            # entry whose ClientData was evicted (dead weakref) can never
            # hit again, so shed those instead of growing O(M)
            self._sig_cache = {c: v for c, v in self._sig_cache.items()
                               if v[0]() is not None}
        blocks: List[Tuple[Any, List[ClientTask]]] = []
        for key in order:
            q = groups[key]
            if key[0] == "eager":
                blocks.append((key, q))
            else:
                for i in range(0, len(q), self.client_block):
                    blocks.append((key, q[i:i + self.client_block]))
        return blocks

    def _run_blocked(self, rnd, tasks, payload, data_by_client, skip_clients,
                     agg, records, completed, eta) -> float:
        """Compiled-engine path: one vmapped jit-scan per block, stacked
        deltas folded straight into the flat aggregator.  Device-pinned
        executors serve stacked batches from the on-device LRU cache and
        dispatch steady-state blocks without blocking (the cached block
        cost stands in for the measurement), so the device computation is
        left in flight while the caller moves on to another executor."""
        engine = client_step.engine_for(self.algorithm, self.device)
        todo = [t for t in tasks
                if not (skip_clients and t.client in skip_clients)]
        vtime = 0.0
        blocks = self._plan_blocks(todo, data_by_client)
        for bi, (key, block) in enumerate(blocks):
            kind = key[0]
            if self.algorithm.stateful and self.state_manager is not None \
                    and bi + 1 < len(blocks):
                # schedule-keyed look-ahead: stage the NEXT block's state
                # shards into the manager's RAM tier while this block's
                # compute occupies the device — the load overlaps compute
                # on the virtual clock (prefetch is outside the timed span
                # and never perturbs the per-client LRU)
                self.state_manager.prefetch(
                    [t.client for t in blocks[bi + 1][1]])
            compiles0 = client_step.compile_events()
            states = None
            if self.algorithm.stateful:
                states = self.state_manager.load_many(
                    [t.client for t in block], device=self.device)
                states = [s if s is not None
                          else self.algorithm.client_init_state(
                              payload["params"])
                          for s in states]
            datas = [data_by_client[t.client] for t in block]

            # the timed span is exactly the client compute (stack + engine
            # + sync on the outputs; jax dispatch is async, so without the
            # sync it would measure host dispatch, not training); state IO
            # and the aggregation fold stay outside so the compile
            # re-measure below can reproduce the identical span.  The
            # stacked-batch prep runs lazily INSIDE the span — the cache
            # makes repeat rounds cheap, but the cost that IS paid must
            # show up in the measured block time (virtual-time accounting
            # on the unpinned default path stays faithful to the work
            # done)
            preps = None
            steps = self.algorithm.local_epochs * sum(
                len(d.batches) for d in datas)

            def run_engine(sync: bool = True, useful: bool = True):
                nonlocal preps
                # counted from shapes: the scan's steps, block and batch
                # padding included, against the client's real steps (none
                # in a re-run whose result is discarded)
                rows = 1 if len(block) == 1 else client_step._bucket(
                    len(block))
                scanned = self.algorithm.local_epochs * rows * \
                    client_step._bucket(len(datas[0].batches))
                with span("client_step", steps=steps if useful else 0,
                          scanned=scanned):
                    if preps is None:
                        preps = [self._prep_batches(t.client,
                                                    data_by_client[t.client])
                                 for t in block]
                    if len(block) == 1:
                        res, st = engine.run_client(
                            payload, datas[0], states[0] if states else None,
                            assume_uniform=True, prep=preps[0])
                        if sync:
                            jax.block_until_ready((res.payload, st))
                        return res, st
                    out = engine.run_block(payload, datas, states,
                                           preps=preps)
                    if sync:
                        jax.block_until_ready(out)
                    return out

            cost_key = (key[1], len(block)) if kind != "eager" else None
            steady = (self.nonblocking and cost_key is not None
                      and cost_key in self._block_cost)
            t0 = self.timer()
            if kind == "eager":           # ragged batches: reference path
                assert len(block) == 1
                with span("client_step", steps=steps, scanned=steps):
                    result, new_state = self.algorithm.client_update(
                        payload, datas[0], states[0] if states else None)
                new_states = [new_state]
                measured = self.timer() - t0
            elif steady:
                # non-blocking dispatch: the executable for this
                # (signature, B) exists (its cost was measured), so no
                # compile can hide in the span; the device crunches while
                # the host dispatches the next executor's chunk
                out = run_engine(sync=False)
                new_states = None
                self.timer()              # span close (call parity with
                measured = self._block_cost[cost_key]   # the synced path)
            else:
                out = run_engine()
                new_states = None
                measured = self.timer() - t0
                # a first-seen shape just paid its one-off compile inside
                # the timed span; re-run the (pure) computation once,
                # result discarded, so virtual time and the workload
                # estimator see steady-state throughput, not compile spikes
                if client_step.compile_events() > compiles0:
                    t0 = self.timer()
                    run_engine(useful=False)
                    measured = self.timer() - t0

            if kind == "eager":
                agg.fold(result)
            elif len(block) == 1:
                result, new_state = out
                if "stats" in result.payload:
                    result = dataclasses.replace(
                        result, payload=self._keep_stats(result.payload))
                agg.fold(result)
                new_states = [new_state]
            else:
                stacked, new_states = out
                stacked = self._keep_stats(stacked)
                agg.fold_block(stacked,
                               [float(d.n_samples) for d in datas])
                if new_states is None:
                    new_states = [None] * len(block)
            if self.algorithm.stateful:
                self.state_manager.save_many(
                    {t.client: s for t, s in zip(block, new_states)
                     if s is not None},
                    keep_device=self.device is not None)
            completed.extend(t.client for t in block)
            if cost_key is not None and not steady:
                # steady-state filter: host-noise spikes (GC, co-tenant
                # load) would otherwise dominate the BSP makespan now that
                # a round is a handful of coarse blocks instead of many
                # small tasks
                measured = min(measured,
                               self._block_cost.get(cost_key, measured))
                self._block_cost[cost_key] = measured
            # per-client virtual-time attribution: the block's measured time
            # splits evenly across its B clients (same batch bucket => same
            # compute), each scaled by the speed model's η
            simulated = measured * (1.0 + eta)
            per_client = simulated / len(block)
            vtime += simulated
            records.extend(
                RunRecord(round=rnd, client=t.client, executor=self.id,
                          n_samples=t.n_samples, time=per_client)
                for t in block)
        return vtime


def run_queues_ganged(executors: Dict[int, "SequentialExecutor"], rnd: int,
                      queues: Dict[int, List[ClientTask]], payload: Dict,
                      data_by_client: Dict[int, ClientData],
                      placement, skip_map: Optional[Dict[int, set]] = None
                      ) -> Optional[Dict[int, "ExecutorReport"]]:
    """SPMD gang dispatch of a whole BSP round (DESIGN.md §8).

    Per-device dispatches serialize inside the CPU PJRT client (virtual
    host devices share one execute thread), so the per-executor
    non-blocking path cannot realise wall-clock overlap there.  This path
    can: when every live executor is pinned to its own device and their
    queues plan into aligned block *waves* — wave i holds every executor's
    i-th block, all sharing one (signature, padded-B) bucket — each wave
    runs as ONE sharded execution over the placement mesh
    (``ClientStepEngine.run_blocks_sharded``), which XLA fans out with one
    thread per device.  Folds, state IO and virtual-time accounting stay
    per-executor on the per-device output shards, so reports are identical
    in content and order to the per-executor path (and bit-identical on
    CPU: the local shard program equals the single-device block program).

    Returns executor-id -> ExecutorReport, or None when the round is not
    gangable (heterogeneous waves, ragged/eager clients, a pending
    ``fail_at`` injection, executors sharing devices, K == 1, ...) — the
    caller then falls back to the ordinary per-executor dispatch."""
    if placement is None or len(queues) < 2:
        return None
    live = sorted(queues)
    exs = [executors[k] for k in live]
    devs = [ex.device for ex in exs]
    if any(d is None for d in devs) or \
            len({d.id for d in devs}) != len(devs):
        return None
    mesh = placement.mesh()
    if [d.id for d in mesh.devices.flat] != [d.id for d in devs]:
        return None
    algo = exs[0].algorithm
    timer = exs[0].timer
    for ex in exs:
        if (not ex.use_compiled_steps or ex.algorithm is not algo
                or ex.timer is not timer or ex.fail_pending(rnd)):
            # gang waves are timed once on the shared timer; executors with
            # private timers keep per-executor measurement semantics via
            # the fallback path
            return None

    # ---- plan waves -----------------------------------------------------
    plans = []
    for k, ex in zip(live, exs):
        todo = [t for t in queues[k]
                if not (skip_map and t.client in skip_map.get(k, ()))]
        plans.append(ex._plan_blocks(todo, data_by_client))
    n_waves = len(plans[0])
    if any(len(p) != n_waves for p in plans):
        return None
    for i in range(n_waves):
        keys = {(p[i][0], client_step._bucket(len(p[i][1]))) for p in plans}
        if len(keys) != 1 or next(iter(keys))[0][0] != "block":
            return None

    # ---- run ------------------------------------------------------------
    # the gang's executor span: executor -1 stands for all of them
    with span("executor", round=rnd, executor=-1):
        engine = client_step.engine_for(algo)       # hosts the sharded cache
        gang_c0 = client_step.compile_events()      # gang-level compile delta
        etas = [ex.speed_model(ex.id, rnd) for ex in exs]
        aggs, placed = [], []
        for ex in exs:
            aggs.append(LocalAggregator(
                algo.ops(), use_kernel=ex.use_agg_kernel,
                micro_batch=ex.agg_micro_batch, layout=ex._layout_cache,
                device=ex.device))
            placed.append(ex._place_payload(payload))
        records: List[List[RunRecord]] = [[] for _ in exs]
        completed: List[List[int]] = [[] for _ in exs]
        vtimes = [0.0] * len(exs)
        walls = [0.0] * len(exs)
        gang_cost = placement._gang_cost

        for i in range(n_waves):
            blocks = [p[i][1] for p in plans]
            sig = plans[0][i][0][1]
            B_pad = client_step._bucket(max(len(b) for b in blocks))
            if algo.stateful and i + 1 < n_waves:
                # stage wave i+1's state shards while wave i computes
                for j, ex in enumerate(exs):
                    if ex.state_manager is not None:
                        ex.state_manager.prefetch(
                            [t.client for t in plans[j][i + 1][1]])
            preps, states = [], None
            if algo.stateful:
                states = []
            for j, (k, ex) in enumerate(zip(live, exs)):
                block = blocks[j]
                preps.append(ex._prep_block_stack(block, data_by_client,
                                                  B_pad))
                if algo.stateful:
                    st = ex.state_manager.load_many(
                        [t.client for t in block], device=ex.device)
                    st = [s if s is not None
                          else algo.client_init_state(placed[j]["params"])
                          for s in st]
                    st = st + [st[0]] * (B_pad - len(block))
                    states.append(jax.tree.map(lambda *xs: jnp.stack(xs), *st))

            cost_key = (sig, B_pad, len(live))
            steady = (all(ex.nonblocking for ex in exs)
                      and cost_key in gang_cost)
            compiles0 = client_step.compile_events()
            steps = algo.local_epochs * sum(
                len(data_by_client[t.client].batches)
                for b in blocks for t in b)
            scanned = algo.local_epochs * len(live) * B_pad * sig[0]

            def run_wave(sync, useful=True):
                with span("client_step", steps=steps if useful else 0,
                          scanned=scanned):
                    outs = engine.run_blocks_sharded(payload, preps, states,
                                                     mesh)
                    if sync:
                        jax.block_until_ready(outs)
                    return outs

            t0 = timer()
            outs = run_wave(sync=not steady)
            if steady:
                timer()                         # span close (call parity)
                measured = gang_cost[cost_key]
            else:
                measured = timer() - t0
                if client_step.compile_events() > compiles0 \
                        and jax.default_backend() == "cpu":
                    # first-seen bucket paid its compile in the span: re-run
                    # once from the warm cache for a steady-state measurement
                    # (CPU only: on TPU/GPU the block jit donates the batch
                    # buffers, so the wave's preps cannot be replayed)
                    t0 = timer()
                    run_wave(sync=True, useful=False)
                    measured = timer() - t0
                measured = min(measured, gang_cost.get(cost_key, measured))
                gang_cost[cost_key] = measured

            for j, (k, ex) in enumerate(zip(live, exs)):
                block = blocks[j]
                out_payload, new_states = outs[j]
                if B_pad > len(block):
                    out_payload = jax.tree.map(lambda x: x[:len(block)],
                                               out_payload)
                out_payload = ex._keep_stats(out_payload)
                aggs[j].fold_block(
                    out_payload,
                    [float(t.n_samples) for t in block])
                if algo.stateful and new_states is not None:
                    ex.state_manager.save_many(
                        {t.client: jax.tree.map(lambda x: x[b], new_states)
                         for b, t in enumerate(block)},
                        keep_device=ex.device is not None)
                completed[j].extend(t.client for t in block)
                simulated = measured * (1.0 + etas[j])
                vtimes[j] += simulated
                walls[j] += measured
                per_client = simulated / len(block)
                records[j].extend(
                    RunRecord(round=rnd, client=t.client, executor=k,
                              n_samples=t.n_samples, time=per_client)
                    for t in block)

        reports = {}
        for j, (k, ex) in enumerate(zip(live, exs)):
            ex._layout_cache = aggs[j].layout
            reports[k] = ExecutorReport(
                executor=k, partial=aggs[j].partial(), records=records[j],
                virtual_time=vtimes[j], wall_time=walls[j],
                n_tasks=len(completed[j]), completed_clients=completed[j],
                # sharded waves compile once for the whole gang: the delta is
                # attributed to the first lane (host-side accounting only)
                compiles=(client_step.compile_events() - gang_c0
                          if j == 0 else 0))
        return reports


class ExecutorFailure(RuntimeError):
    """An executor died mid-dispatch.

    Carries where (device), what was in flight (the chunk's global task
    range) and when (virtual seconds into the chunk's span) — and pickles
    round-trip cleanly (``__reduce__``), so an in-flight failure can ride a
    checkpoint blob across process boundaries."""

    def __init__(self, executor: int, rnd: int, task_index: int,
                 device: Optional[Any] = None,
                 chunk: Optional[Tuple[int, int]] = None,
                 vtime: Optional[float] = None):
        # keep only the plain device id: jax Device objects don't pickle
        device = getattr(device, "id", device)
        msg = f"executor {executor} failed at round {rnd}, task {task_index}"
        detail = []
        if device is not None:
            detail.append(f"device={device}")
        if chunk is not None:
            detail.append(f"chunk=[{chunk[0]},{chunk[1]})")
        if vtime is not None:
            detail.append(f"t={vtime:.6g}s")
        if detail:
            msg += " (" + ", ".join(detail) + ")"
        super().__init__(msg)
        self.executor = executor
        self.rnd = rnd
        self.task_index = task_index
        self.device = device
        self.chunk = chunk
        self.vtime = vtime

    def __reduce__(self):
        # RuntimeError's default reduce would replay __init__ with the
        # formatted message as the sole argument; rebuild from fields so
        # pickle.loads(pickle.dumps(e)) preserves every attribute
        return (type(self), (self.executor, self.rnd, self.task_index,
                             self.device, self.chunk, self.vtime))
