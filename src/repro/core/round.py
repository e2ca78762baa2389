"""Parrot server — Algorithm 2 (``Server_Executes``) over a pluggable
round engine.

One ``ParrotServer`` owns: the FL algorithm, the heterogeneity-aware
scheduler + workload estimator, K sequential executors, the client state
managers, a Communicator, and (optionally) a checkpoint manager and a delta
compressor.  ``run_round`` delegates to a :class:`~repro.core.engine.
RoundEngine` — the synchronization policy is a constructor knob
(``round_engine=``, DESIGN.md §3):

  bsp        — the paper's loop, strict barrier:
               select clients → Task_Schedule (Alg. 3) → broadcast Θ^r +
               queues → Device_Executes on each executor → collect K
               partials (one trip each) → GlobalAggregate → server update.
               Round time is ``max_k Σ_{m∈M_k} T̂_{m,k}`` — the makespan the
               scheduler minimises.
  semi-sync  — over-select, fold whatever landed by a model-derived
               virtual-time deadline, carry the rest to the next round.
  async      — fold chunk partials as they land with a bounded-staleness
               weight; update every ``clients_per_round`` folds; idle
               executors steal from the predicted-slowest queue.

Executor failures mid-round are engine events: the dead executor's
*remaining* work re-runs on the survivors (clients are idempotent within a
round: state saves are keyed per round) and K shrinks for subsequent rounds
(elastic membership).

``mode="parrot"`` uses hierarchical aggregation; ``mode="flat"`` emulates
SD-Dist/FA-Dist accounting (every client result shipped to the server
individually) for the Table-1 comparison benchmarks.
"""
from __future__ import annotations

import math
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.comm.base import Communicator
from repro.comm.local import LocalComm
from repro.core.aggregation import (expand_aggregate, flat_aggregate,
                                    is_flat_partial, reduce_partials,
                                    tree_reduce_partials)
from repro.core.algorithms import ClientData, FLAlgorithm
from repro.core.executor import SequentialExecutor
from repro.core.population import ClientPopulation, as_population
from repro.core.faults import FaultInjector, FaultPlan, RetryPolicy
from repro.core.network import ClientAvailability, NetworkModel
from repro.core.placement import DevicePlacement
from repro.core.scheduler import ClientTask, ParrotScheduler, Schedule
from repro.core.telemetry import span
from repro.core.workload import WorkloadEstimator


@dataclass
class RoundMetrics:
    round: int
    makespan: float               # BSP round time (max executor virtual time)
    wall_time: float
    schedule_time: float
    estimate_time: float
    predicted_makespan: float
    comm_bytes: int
    comm_trips: int
    n_clients: int
    n_executors: int
    estimation_error: float = float("nan")
    failures: int = 0
    # deliberately Any-valued: alongside scalar counters/gauges this carries
    # the nested state-manager stats dict and per-executor utilization dict.
    # The full key schema lives in telemetry.EXTRA_SCHEMA / DESIGN.md §13;
    # a server with telemetry attached mirrors every numeric key into the
    # typed MetricsRegistry at round commit.
    extra: Dict[str, Any] = field(default_factory=dict)


class ParrotServer:
    def __init__(self, *, params: Any, algorithm: FLAlgorithm,
                 executors: Sequence[SequentialExecutor],
                 data_by_client: Dict[int, ClientData],
                 clients_per_round: int,
                 scheduler_policy: str = "parrot",
                 time_window: int = 0,
                 warmup_rounds: int = 1,
                 comm: Optional[Communicator] = None,
                 compressor: Optional[Any] = None,
                 checkpoint_manager: Optional[Any] = None,
                 mode: str = "parrot",
                 parallel_dispatch: bool = False,
                 overlap_scheduling: bool = False,
                 backup_fraction: float = 0.0,
                 round_engine: str = "bsp",
                 engine_opts: Optional[Dict[str, Any]] = None,
                 placement: Optional[DevicePlacement] = None,
                 gang_dispatch: bool = True,
                 network: Optional[NetworkModel] = None,
                 availability: Optional[ClientAvailability] = None,
                 faults: Optional[FaultPlan] = None,
                 retry: Optional[RetryPolicy] = None,
                 control: Optional[Any] = None,
                 telemetry: Optional[Any] = None,
                 fold_fan_in: int = 16,
                 seed: int = 0):
        from repro.core.engine import make_engine
        self.params = params
        self.algorithm = algorithm
        self.executors: Dict[int, SequentialExecutor] = {e.id: e for e in executors}
        # device placement (DESIGN.md §8): an explicit placement pins the
        # executors here; otherwise one is derived from executors that were
        # constructed pre-pinned (``device=``).  None = the single default
        # device, bit-for-bit the pre-multi-device behaviour.
        if placement is not None:
            placement.assign(executors)
        else:
            pins = {e.id: e.device for e in executors
                    if getattr(e, "device", None) is not None}
            if pins:
                placement = DevicePlacement.from_pins(pins)
        self.placement = placement
        # SPMD gang dispatch of gangable BSP rounds (no-op without a
        # multi-device placement; see engine.BSPEngine._dispatch)
        self.gang_dispatch = bool(gang_dispatch)
        # the population axis (DESIGN.md §11): a plain dict wraps into an
        # EagerPopulation (cached sorted-id registry); a ClientPopulation —
        # e.g. a registry-backed LazyPopulation streaming batches through a
        # bounded fetch cache — passes through, so dataset memory can stay
        # O(cohort) at million-client scale.  ``data_by_client`` stays the
        # read path everywhere (populations are Mappings).
        self.population: ClientPopulation = as_population(data_by_client)
        self.data_by_client = self.population
        self.clients_per_round = clients_per_round
        # hierarchical aggregation (executor → group → server): partial
        # lists wider than this fold through a fan-in tree of merge_partials
        # levels so server-side buffers stay O(fan_in), not O(K).  At or
        # below the fan-in (every pinned small-K configuration) the flat
        # left-fold runs unchanged — bit-exactly the legacy path.
        # ``fold_fan_in=0`` disables the tree outright.
        self.fold_fan_in = int(fold_fan_in)
        # previous cumulative state-manager counters (per-round deltas for
        # RoundMetrics.extra["state_manager"])
        self._sm_stats_prev: Dict[str, float] = {}
        self.estimator = WorkloadEstimator(time_window=time_window)
        self.scheduler = ParrotScheduler(self.estimator,
                                         warmup_rounds=warmup_rounds,
                                         policy=scheduler_policy)
        self.comm = comm or LocalComm()
        if isinstance(compressor, str):
            # convenience: compressor="topk"/"int8"/"powersgd" builds the
            # compiled default via make_compressor
            from repro.core.compression import make_compressor
            compressor = make_compressor(compressor)
        self.compressor = compressor
        self.checkpoint_manager = checkpoint_manager
        self.mode = mode
        # trace-driven network & availability simulation (DESIGN.md §9):
        # None for both (the default) keeps every engine on its pre-network
        # code path bit-exactly — params AND makespan histories unchanged
        self.network = network
        self.availability = availability
        # fault injection (DESIGN.md §10): a seeded FaultPlan schedules
        # crashes / restarts / dropouts / corruption / blackouts / slowdowns
        # on the virtual axis; None (the default) keeps every engine on its
        # pre-fault code path bit-exactly.  An empty plan behaves
        # identically to None (pinned by the equivalence tests).
        self.faults: Optional[FaultInjector] = (
            FaultInjector(faults, retry) if faults is not None
            or retry is not None else None)
        # adaptive control plane (DESIGN.md §12): self-tuning λ / deadline
        # controllers, window-fit selection, comm overlap, gang waves and
        # queue rebalancing, plus oracle-gap tracking.  None (the default)
        # keeps every engine on its pre-control code path bit-exactly, and
        # ControlPlane.observer() is pinned behaviour-identical to None.
        self.control = control
        # virtual-time telemetry (DESIGN.md §13): span tracer + metrics
        # registry + utilization accounting.  None (the default) is
        # consulted nowhere — every engine stays bit-exact (params AND
        # makespans); ``telemetry=True`` builds a default bundle.  The same
        # object is shared with the fault injector and control plane so
        # their events land on the common lanes.
        if telemetry is True:
            from repro.core.telemetry import Telemetry
            telemetry = Telemetry()
        self.telemetry = telemetry
        if telemetry is not None:
            if self.faults is not None:
                self.faults.telemetry = telemetry
                telemetry.trace_plan(self.faults.plan)
            if control is not None and hasattr(control, "telemetry"):
                control.telemetry = telemetry
        # crashed executors park here so a scheduled restart (or a
        # checkpoint restore of a pre-crash topology) can revive them
        self._retired: Dict[int, SequentialExecutor] = {}
        # cumulative simulated time across rounds — the availability axis
        # (BSP / semi-sync advance it by each round's makespan; async pins
        # it to its persistent clock)
        self.virtual_now = 0.0
        self._last_payload_nbytes = 0    # comm-cost estimates (round r-1's)
        self._wire_ratio = 1.0           # achieved wire/raw compression ratio
        self.parallel_dispatch = parallel_dispatch
        self.overlap_scheduling = overlap_scheduling
        self.backup_fraction = backup_fraction
        self._next_tasks: Optional[List[ClientTask]] = None
        self.server_state = algorithm.server_init(params)
        self.rng = np.random.default_rng(seed)
        self.round = 0
        self.history: List[RoundMetrics] = []
        self._pending_schedule: Optional[Schedule] = None
        self.engine = make_engine(round_engine, **(engine_opts or {}))
        if self.engine.mode != "bsp":
            # BSP-specific knobs would silently no-op under the DES engines
            # (which serialize execution and mitigate tails via deadline
            # carry-over / work stealing instead of backups) — fail loudly
            for knob, val in (("backup_fraction", backup_fraction),
                              ("parallel_dispatch", parallel_dispatch),
                              ("overlap_scheduling", overlap_scheduling)):
                if val:
                    raise ValueError(
                        f"{knob} only applies to round_engine='bsp' "
                        f"(got {self.engine.mode!r})")

    # ------------------------------------------------------------------
    @span("select")
    def select_clients(self, n: Optional[int] = None,
                       exclude: Optional[Any] = None) -> List[ClientTask]:
        """Sample the round's cohort without replacement.  ``n`` overrides
        ``clients_per_round`` (semi-sync over-selection, async refills);
        ``exclude`` removes clients already in flight.  With an availability
        model, clients offline at the current virtual time are filtered
        before sampling.

        Cost is O(cohort), not O(M log M): the population keeps a cached
        sorted-id registry, draws positional indices into the virtual
        (ids minus exclude) pool and rank-adjusts past the excluded
        positions — rng-identical to the original
        ``rng.choice(sorted_pool, ...)`` (pinned by tests/test_population.
        py), so every engine bit-exactness pin holds.  Availability/fault
        filters apply per candidate without materialising a boxed-int
        pool.  Task sample counts come from the registry, so selection
        never materialises client batches."""
        filters = []
        if self.availability is not None:
            av, now = self.availability, self.virtual_now
            filters.append(lambda c: av.available(c, now))
            ctrl = self.control
            if ctrl is not None and getattr(ctrl, "window_fit", False):
                # window-fit selection (DESIGN.md §12): skip clients whose
                # availability window can't hold their predicted span (+
                # comm round-trip) — they'd only land a dispatch-time skip
                # or a lost upload.  Needs at least one fitted model (the
                # fleet average prices executor-agnostically, since the
                # client isn't scheduled yet); before the first fit this
                # filter is inert, preserving the warmup cohort.
                from repro.core.workload import fleet_average
                avg = fleet_average(self.estimator.last_fit)
                if avg is not None:
                    n_of = self.population.n_samples
                    net, down = self.network, self._last_payload_nbytes
                    up = int(down * self._wire_ratio)

                    def _fits(c, av=av, now=now, avg=avg, n_of=n_of,
                              net=net, down=down, up=up):
                        dur = avg.predict(n_of(c))
                        if net is not None:
                            dur += net.client_comm_time(c, down, up)
                        return av.fits(c, now, dur)

                    filters.append(_fits)
        if self.faults is not None:
            fi, now = self.faults, self.virtual_now
            filters.append(lambda c: not fi.client_down(c, now))
        ids = self.population.sample(
            self.rng, self.clients_per_round if n is None else n,
            exclude=exclude, filters=filters)
        n_of = self.population.n_samples
        return [ClientTask(c, n_of(c)) for c in ids]

    # ------------------------------------------------------------------
    def _plan_backups(self, schedule: Schedule
                      ) -> Tuple[Dict[int, Set[int]], int]:
        """Speculative backup tasks (tail mitigation at 1000-node scale):
        duplicate the tail of the predicted-slowest queue onto the
        predicted-fastest executor and tell the slow executor to skip those
        clients (the ``skip_clients`` hook) — each client still folds exactly
        once, so aggregation stays exact, and if either executor dies the
        normal leftover re-run covers the duplicated clients."""
        if self.backup_fraction <= 0 or len(self.executors) < 2:
            return {}, 0
        models = self.estimator.last_fit

        def load(k: int) -> float:
            m = models.get(k)
            q = schedule.queue(k)
            if m is not None:
                return sum(m.predict(t.n_samples) for t in q)
            return float(sum(t.n_samples for t in q))

        ks = list(self.executors)
        slow = max(ks, key=load)
        fast = min(ks, key=load)
        queue = schedule.queue(slow)
        if slow == fast or not queue:
            return {}, 0
        n = min(len(queue), max(1, int(round(self.backup_fraction
                                             * len(queue)))))
        tail = queue[-n:]
        schedule.assignment.setdefault(fast, []).extend(tail)
        return {slow: {t.client for t in tail}}, len(tail)

    @span("global_fold")
    def global_fold(self, partials: List[Dict]) -> Dict[str, Any]:
        """``GlobalAggregate``'s reduction across the partials, and nothing
        after it: K-1 buffer adds (none for one partial), routed through
        the device placement when one is active — device-resident partials
        reduce with one sharded psum per weight group (or colocating D2D
        left-folds, both bit-identical to the host path) and the reduced
        buffers land on the server device.  Returns the reduced aggregate
        (``aggregation.reduce_partials``); ``server_update`` divides,
        unflattens and applies it in one compiled step.

        Partial lists wider than ``fold_fan_in`` first reduce through the
        hierarchical fan-in tree (executor → group → server, reusing the
        flat incremental fold at each level) so the final reduce — and the
        placement's collective — sees at most ``fold_fan_in`` partials.  At
        or below the fan-in this is byte-for-byte the legacy flat
        left-fold."""
        ops = self.algorithm.ops()
        if (self.fold_fan_in > 1 and len(partials) > self.fold_fan_in
                and all(is_flat_partial(p) for p in partials)):
            partials = tree_reduce_partials(partials, self.fold_fan_in)
        if self.placement is not None:
            return self.placement.global_fold(partials, ops)
        return reduce_partials(partials, ops)

    def server_update(self, agg: Dict[str, Any]) -> None:
        """Fold the round's reduced aggregate into the model: every engine's
        ``ServerUpdate`` step, one compiled program (:class:`ServerStep`).
        ``agg["_n_selected"]`` (set by the engine) enters through the
        algorithm's host-side ``server_scalars``."""
        step = server_step_for(self.algorithm)
        scalars = self.algorithm.server_scalars(agg.get("_n_selected", 0),
                                                len(self.data_by_client))
        with span("server_update", compiles=step.compile_count()):
            self.params, self.server_state = step(
                self.params, self.server_state, agg, scalars)

    def _state_manager_extra(self) -> Optional[Dict[str, Any]]:
        """Per-round client-state cache observability: cumulative
        ``ClientStateManager.stats`` counters (deduped across executors
        sharing one manager) are diffed against the previous round, and the
        current tier byte gauges are attached as-is.  Engines put the
        result under ``RoundMetrics.extra["state_manager"]``."""
        managers = {}
        for ex in self.executors.values():
            sm = getattr(ex, "state_manager", None)
            if sm is not None and hasattr(sm, "stats_snapshot"):
                managers[id(sm)] = sm
        if not managers or not self.algorithm.stateful:
            return None
        total: Dict[str, float] = {}
        for sm in managers.values():
            for key, val in sm.stats_snapshot().items():
                total[key] = total.get(key, 0) + val
        out: Dict[str, float] = {}
        for key, val in total.items():
            if key.endswith("_bytes"):
                out[key] = val                               # gauge
            else:
                out[key] = val - self._sm_stats_prev.get(key, 0)
        self._sm_stats_prev = total
        return out

    def _drop_executor(self, k: int) -> None:
        """Elastic K shrink: retire a dead executor (and release its device
        pin).  The object parks in ``_retired`` so a scheduled restart can
        rejoin it later — its measured block costs survive the outage."""
        ex = self.executors.pop(k, None)
        if ex is not None:
            self._retired[k] = ex
        if self.placement is not None:
            self.placement.release(k)

    def _revive_executor(self, k: int) -> bool:
        """A crashed executor rejoins (restart fault event / restore of a
        pre-crash topology): re-pin it through the placement's deterministic
        least-loaded choice and put it back in the live set.  Subsequent
        schedules see K grow again.  False if ``k`` is not revivable."""
        ex = self._retired.pop(k, None)
        if ex is None or k in self.executors:
            return False
        if self.placement is not None:
            ex.set_device(self.placement.pin(k))
        self.executors[k] = ex
        # canonical live order: plain insertion would park the revived k at
        # the dict's tail, making round iteration (dispatch and fold order)
        # depend on the process's crash history — a resumed process rebuilds
        # the dict in constructor order and would fold in a different order,
        # breaking bit-exact auto-resume
        if list(self.executors) != sorted(self.executors):
            self.executors = {j: self.executors[j]
                              for j in sorted(self.executors)}
        return True

    # ------------------------------------------------------------------
    # network/availability plumbing (no-ops when both are None)
    def _sched_comm_cost(self):
        """Per-task comm-cost closure for the scheduler's Eq. 4 (None when
        no network is modelled).  Prices one client round-trip at the last
        broadcast's size and the compressor's last achieved wire ratio —
        round 0 prices latency only (no payload has been sized yet), which
        the uniform warmup schedule ignores anyway."""
        if self.network is None:
            return None
        net, down = self.network, self._last_payload_nbytes
        up = int(down * self._wire_ratio)
        return lambda task: net.client_comm_time(task.client, down, up)

    def _next_available_time(self, exclude: Optional[Any] = None) -> float:
        """Earliest virtual time any selectable client comes online (inf if
        never) — the engines fast-forward an empty round to it."""
        if self.availability is None:
            return self.virtual_now
        ex = {int(c) for c in (exclude or ())}
        return min((self.availability.next_available(int(c), self.virtual_now)
                    for c in self.population.ids_array()
                    if int(c) not in ex), default=float("inf"))

    def _next_availability_change(self, exclude: Optional[Any] = None
                                  ) -> float:
        """Earliest FUTURE instant any selectable client's availability
        flips: window start for offline clients, window *end* for online
        ones.  The fast-forward target when a round made zero progress even
        though clients are nominally online — every dropped client was
        predicted to expire mid-chunk, and within its current window that
        prediction can only get worse, so time must jump past a window
        boundary for the availability state to change at all."""
        if self.availability is None:
            return float("inf")
        t = self.virtual_now
        best = float("inf")
        ex = {int(c) for c in (exclude or ())}
        for c in self.population.ids_array():
            c = int(c)
            if c in ex:
                continue
            if self.availability.available(c, t):
                r = self.availability.remaining(c, t)
                if math.isfinite(r) and r > 0:
                    best = min(best, t + r)
            else:
                nxt = self.availability.next_available(c, t)
                if nxt > t:
                    best = min(best, nxt)
        return best

    def _maybe_compress(self, partial: Dict,
                        executor: Optional[int] = None) -> Dict:
        if self.compressor is None:
            return partial
        # key stateful compressor state (top-k error-feedback residuals) by
        # the sending executor: each executor owns its residual stream, so
        # compressed values don't depend on cross-executor ship order
        return self.compressor.compress_partial(
            partial, key=None if executor is None else f"exec{executor}")

    def _maybe_decompress(self, partial: Dict) -> Dict:
        if self.compressor is None:
            return partial
        return self.compressor.decompress_partial(partial)

    # ------------------------------------------------------------------
    @span("commit")
    def _commit_metrics(self, metrics: RoundMetrics, t0: float) -> None:
        """Round-boundary commit: every engine routes its finished
        RoundMetrics through here with the round window's virtual start
        time.  With telemetry attached, the round's extra is ingested into
        the metrics registry and per-executor busy/comm/idle fractions over
        ``[t0, t0 + makespan]`` land in ``metrics.extra["utilization"]``
        BEFORE the metrics join history (so checkpointed history carries
        them); without it this is exactly ``history.append``.  The step
        counters the executors kept from the round's compiled clients are
        taken here either way, and read only with telemetry."""
        kept = [s for ex in (list(self.executors.values())
                             + list(self._retired.values()))
                for s in ex.take_step_stats()]
        if self.telemetry is not None:
            metrics.extra.update(_step_counters(kept))
            self.telemetry.on_round(self, metrics, t0)
        self.history.append(metrics)

    def run_round(self) -> RoundMetrics:
        """One server round under the configured engine: a full BSP barrier,
        a deadline-bounded semi-sync round, or one bounded-staleness update
        window (see ``core/engine.py``)."""
        with span("round", round=self.round):
            return self.engine.run_round(self)

    def run(self, n_rounds: int,
            auto_resume: bool = False) -> List[RoundMetrics]:
        """Run rounds.  With ``auto_resume=True``, first restore the newest
        valid checkpoint (walking past torn/corrupt ones) and then run until
        ``n_rounds`` TOTAL rounds have completed — the crash-recovery entry
        point: after a mid-round kill, a fresh server constructed with the
        same configuration resumes from the last durable round boundary and
        replays deterministically (params digest matches the uninterrupted
        run).  Without it, behaviour is unchanged: ``n_rounds`` more rounds
        from wherever the server stands."""
        if not auto_resume:
            return [self.run_round() for _ in range(n_rounds)]
        if self.checkpoint_manager is None:
            raise ValueError("auto_resume needs a checkpoint_manager")
        from repro.checkpoint.manager import restore_latest
        restore_latest(self, self.checkpoint_manager.directory)
        while self.round < n_rounds:
            self.run_round()
        return list(self.history[:n_rounds])


def _step_counters(kept: List[Dict[str, Any]]) -> Dict[str, float]:
    """The round's model step counters from the step stats its compiled
    clients returned (one tree per client, or per block with a leading
    client axis).  Read at the commit, after the round's last program is
    dispatched: the host waits for the client steps only, which the server
    update already follows on the device.

    ``moe_routed_rows``: the (token, expert) pairs the held experts
    computed; ``moe_max_expert_share``: over the MoE layers, the fullest
    held expert's rows over the mean held expert's in the round."""
    rows = [s["moe_expert_rows"] for s in kept if "moe_expert_rows" in s]
    if not rows:
        return {}
    per = sum(np.asarray(jax.device_get(r), np.int64)
              .reshape((-1,) + r.shape[-2:]).sum(axis=0) for r in rows)
    mean = per.mean(axis=-1)
    share = np.where(mean > 0, per.max(axis=-1) / np.maximum(mean, 1), 0)
    return {"moe_routed_rows": float(per.sum()),
            "moe_max_expert_share": float(share.max())}


def run_flat_reference(params, algorithm: FLAlgorithm,
                       data_by_client: Dict[int, ClientData],
                       clients_per_round: int, n_rounds: int, seed: int = 0,
                       state_store: Optional[Dict[int, Any]] = None):
    """Single-process original-FL reference (SP scheme): the ground truth the
    hierarchical scheme must match (used by the Fig. 4 equivalence tests)."""
    rng = np.random.default_rng(seed)
    server_state = algorithm.server_init(params)
    state_store = {} if state_store is None else state_store
    for rnd in range(n_rounds):
        ids = rng.choice(sorted(data_by_client),
                         size=min(clients_per_round, len(data_by_client)),
                         replace=False)
        def results():
            """Each client's result as it lands, so the fold never holds
            every client's model-sized delta at once."""
            for c in ids:
                c = int(c)
                state = state_store.get(c)
                if algorithm.stateful and state is None:
                    state = algorithm.client_init_state(params)
                payload = algorithm.broadcast_payload(params, server_state)
                res, new_state = algorithm.client_update(
                    payload, data_by_client[c], state)
                if algorithm.stateful and new_state is not None:
                    state_store[c] = new_state
                yield res

        agg = flat_aggregate(results(), algorithm.ops())
        params, server_state = algorithm.server_update(
            params, agg, server_state,
            algorithm.server_scalars(len(ids), len(data_by_client)))
    return params, server_state


class ServerStep:
    """The server's side of a round as ONE compiled program: each entry
    sliced from the reduced group buffers and divided
    (``aggregation.expand_aggregate``), then ``algorithm.server_update``,
    fused by XLA in place of one eager pass over the model per op.

    The update runs in the buffers' flat order: params, state and COLLECT
    values enter as 1-D leaves and the results are reshaped back.  On a TPU
    an N-d leaf is tiled, so a flat segment reshaped to the leaf's shape is
    a relayout; moving the bf16 params into flat order and the result back
    moves half the bytes of relayouting the fp32 aggregate, and the
    optimization barriers keep XLA from moving the reshapes onto the fp32
    side.  Every ``server_update`` is elementwise over leaves, so the flat
    order changes no number.

    The divisors, the COLLECT lists' client weights and the algorithm's
    ``server_scalars`` enter as traced scalars (Python floats, so weakly
    typed exactly as in the eager reference): one executable serves every
    round whatever its weights.  Executables are cached per layout
    structure, and inside that by the params' and state's shapes (the
    COLLECT lists' length included).  The params are not donated: the
    executor's payload cache and callers' snapshots still hold them."""

    def __init__(self, algorithm: FLAlgorithm):
        self.algorithm = algorithm
        self._jits: Dict[Any, Any] = {}

    def _build(self, layout):
        algorithm = self.algorithm

        def flat(tree):
            return jax.tree.map(
                lambda x: jax.lax.optimization_barrier(jnp.ravel(x)), tree)

        @jax.named_scope("server")
        def _server_step(params, state, buffers, divisors, collected,
                         scalars):
            agg = expand_aggregate(
                {"layout": layout, "buffers": buffers, "divisors": divisors,
                 "collected": {k: [(w, flat(v)) for w, v in lst]
                               for k, lst in collected.items()}},
                algorithm.ops(), shaped=False)
            out = algorithm.server_update(flat(params), agg, flat(state),
                                          scalars)
            return jax.tree.map(
                lambda y, x: jax.lax.optimization_barrier(y).reshape(x.shape),
                out, (params, state))

        # bf16 intermediates round to bf16 as each eager op rounds them
        return jax.jit(_server_step, compiler_options={
            "xla_allow_excess_precision": False})

    def __call__(self, params, state, agg: Dict[str, Any],
                 scalars: Dict[str, float]):
        layout = agg["layout"]
        key = None if layout is None else layout.structure()
        fn = self._jits.get(key)
        if fn is None:
            fn = self._jits[key] = self._build(layout)
        return fn(params, state, agg["buffers"], agg["divisors"],
                  agg["collected"], scalars)

    def compile_count(self) -> int:
        """Executables compiled so far, over every layout."""
        return sum(fn._cache_size() for fn in self._jits.values())


def server_step_for(algorithm: FLAlgorithm) -> ServerStep:
    """The algorithm instance's compiled server step (one compile cache per
    algorithm, as ``client_step.engine_for`` keeps one per algorithm)."""
    step = getattr(algorithm, "_server_step", None)
    if step is None:
        step = algorithm._server_step = ServerStep(algorithm)
    return step
