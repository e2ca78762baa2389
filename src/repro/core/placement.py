"""Executor → device placement and the sharded cross-device global fold.

Parrot's scaling claim is "1000+ simulated clients across flexible GPU
counts": K executors must actually *occupy* K local devices, not time-share
device 0.  A :class:`DevicePlacement` pins each executor to one local JAX
device (round-robin when K exceeds the device count); the executor then
compiles its client-step executables for that device
(``client_step.engine_for(algorithm, device)``), keeps its
``LocalAggregator`` accumulator and staged buffers resident there, and ships
device-resident flat partials through the comm layer with no host round-trip.

The server-side fold of the K per-device partials is the one point where
devices must meet.  ``global_fold`` keeps it device-native:

* **psum path** — when each partial sits on its own device (the one-executor-
  per-device case the benchmarks run), the per-device ``(n,)`` group buffers
  are assembled *in place* into one ``(K, n)`` array sharded ``P("data",
  None)`` over the placement's mesh (``jax.make_array_from_single_device_
  arrays`` — zero copy, no gather) and reduced with a single
  ``shard_map``/``psum`` per weight group.  On CPU host devices (and TPU ICI)
  the rank-ordered psum is bit-identical to the host path's left-fold
  ``b0+b1+…`` — the K-device parity tests rely on this.
* **colocate path** — any other shape (K not equal to the mesh size, partials
  sharing devices, legacy nested partials): buffers are copied device-to-
  device onto the fold device and left-folded exactly like the host path,
  preserving bit-exactness trivially.

Failure handling mirrors the engines' elastic membership: ``release`` drops a
dead executor's pin, and ``fail_device`` re-pins every executor that was
living on a dead device onto the remaining live devices (the executor's
device-resident caches are invalidated via ``SequentialExecutor.set_device``).

Tests run under ``XLA_FLAGS=--xla_force_host_platform_device_count=4`` so all
of this exercises real multi-device semantics on CPU.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


def local_devices(backend: Optional[str] = None) -> List[Any]:
    """The devices a placement may pin executors to (process-local)."""
    return list(jax.local_devices(backend=backend))


def _device_of(x) -> Optional[Any]:
    """The single device a committed array lives on, else None."""
    sh = getattr(x, "sharding", None)
    if sh is None:
        return None
    try:
        devs = list(sh.device_set)
    except Exception:
        return None
    return devs[0] if len(devs) == 1 else None


class DevicePlacement:
    """Executor id → local device map (+ the cross-device fold mesh).

    ``devices=None`` takes every local device; a single-device placement is
    valid (and degenerates to today's behaviour everywhere).  ``server``
    names the device where folded aggregates land (default: the first
    placement device, which is also where un-pinned server work runs).
    """

    def __init__(self, executor_ids: Sequence[int],
                 devices: Optional[Sequence[Any]] = None,
                 server: Optional[Any] = None):
        devices = list(devices) if devices is not None else local_devices()
        if not devices:
            raise ValueError("DevicePlacement needs at least one device")
        self._devices = devices
        self._map: Dict[int, Any] = {
            k: devices[i % len(devices)]
            for i, k in enumerate(sorted(executor_ids))}
        self.server_device = server if server is not None else devices[0]
        self._mesh_cache: Optional[Mesh] = None
        # steady-state gang-wave costs, shared by the ganged executors
        # (executor.run_queues_ganged): (sig, B_pad, K) -> seconds
        self._gang_cost: Dict[Tuple, float] = {}

    @classmethod
    def from_pins(cls, pins: Dict[int, Any],
                  server: Optional[Any] = None) -> "DevicePlacement":
        """Adopt an existing executor→device map (executors constructed
        with explicit ``device=`` pins)."""
        self = cls.__new__(cls)
        devs, seen = [], set()
        for k in sorted(pins):
            d = pins[k]
            if d.id not in seen:
                seen.add(d.id)
                devs.append(d)
        self._devices = devs
        self._map = dict(pins)
        self.server_device = server if server is not None else devs[0]
        self._mesh_cache = None
        self._gang_cost = {}
        return self

    # ------------------------------------------------------------------
    def device(self, executor: int) -> Any:
        return self._map[executor]

    def executors(self) -> List[int]:
        return sorted(self._map)

    def devices(self) -> List[Any]:
        """Distinct live devices, in first-pinned order."""
        seen, out = set(), []
        for k in sorted(self._map):
            d = self._map[k]
            if d.id not in seen:
                seen.add(d.id)
                out.append(d)
        return out

    @property
    def n_devices(self) -> int:
        return len({d.id for d in self._map.values()})

    def assign(self, executors: Sequence[Any]) -> None:
        """Pin a set of ``SequentialExecutor``s to their mapped devices."""
        for ex in executors:
            ex.set_device(self._map[ex.id])

    # ------------------------------------------------------------------
    def release(self, executor: int) -> None:
        """Drop a dead executor's pin (elastic K shrink)."""
        self._map.pop(executor, None)
        self._mesh_cache = None

    def pin(self, executor: int) -> Any:
        """Pin a (re)joining executor to the least-loaded live device
        (ties break on placement order — deterministic, so a crashed
        executor's restart re-pin is reproducible on resume).  Returns the
        chosen device; the caller pushes it into the executor via
        ``SequentialExecutor.set_device``."""
        if not self._devices:
            raise RuntimeError("no live devices to pin onto")
        load = {d.id: 0 for d in self._devices}
        for d in self._map.values():
            load[d.id] = load.get(d.id, 0) + 1
        dev = min(self._devices, key=lambda d: load[d.id])
        self._map[executor] = dev
        self._mesh_cache = None
        return dev

    def rebalance(self, queues, horizons, models, comm_cost=None):
        """Pollen-style throughput-driven re-pinning at queue granularity:
        re-pack every undispatched task across the executor set from the
        CURRENT fitted per-device workload models, seeding each lane with
        its busy horizon (``scheduler.rebalance_queues``).  Returns
        ``(assignment, moved)``."""
        from repro.core.scheduler import rebalance_queues
        return rebalance_queues(queues, horizons, models, comm_cost)

    def fail_device(self, device: Any) -> List[int]:
        """A device died: re-pin its executors round-robin onto the live
        devices.  Returns the re-pinned executor ids (the caller must push
        the new pin into each executor via ``set_device`` / ``assign``)."""
        dead_id = getattr(device, "id", device)
        live = [d for d in self._devices if d.id != dead_id]
        if not live:
            raise RuntimeError("no live devices left")
        self._devices = live
        moved = sorted(k for k, d in self._map.items() if d.id == dead_id)
        for i, k in enumerate(moved):
            self._map[k] = live[i % len(live)]
        self._mesh_cache = None
        return moved

    # ------------------------------------------------------------------
    def mesh(self) -> Mesh:
        """``("data", "model")`` host mesh over the placement's live
        devices, in pinned executor order (``launch.mesh.make_host_mesh``
        with ``model_axis=1`` — the fold mesh ``global_fold`` reduces over
        its data-parallel axes, which ``sharding.specs.dp_axes`` names)."""
        from repro.launch.mesh import make_host_mesh
        devs = self.devices()
        if self._mesh_cache is None or \
                [d.id for d in self._mesh_cache.devices.flat] != \
                [d.id for d in devs]:
            self._mesh_cache = make_host_mesh(devices=devs)
        return self._mesh_cache

    # ------------------------------------------------------------------
    def global_fold(self, partials: List[Dict[str, Any]],
                    ops: Dict[str, Any]) -> Dict[str, Any]:
        """``GlobalAggregate``'s reduction over device-resident flat
        partials (``aggregation.reduce_partials``).

        Partials whose buffers each sit on their own distinct device (in
        partial order matching the fold mesh) reduce with ONE
        ``shard_map``/``psum`` per weight group; anything else colocates
        onto the fold device and left-folds — both orders are bit-identical
        to the host path's ``b0+b1+…``.  The reduced buffers (and the
        COLLECT lists) land on ``server_device``, where the compiled server
        step reads them."""
        from repro.core.aggregation import reduce_partials

        out = reduce_partials(partials, ops, self._make_reduce(partials))
        return _put_tree(out, self.server_device)

    # below this per-group element count the colocating left-fold beats the
    # sharded psum: a multi-device SPMD dispatch costs ~10ms of host time on
    # CPU, far more than D2D-copying a few KB (the collective pays for
    # itself on real model sizes — and always on TPU ICI)
    psum_min_elements: int = 1 << 16

    def _make_reduce(self, partials: List[Dict[str, Any]]):
        mesh = self.mesh()
        mesh_ids = [d.id for d in mesh.devices.flat]

        def reduce_group(bufs: List[jnp.ndarray]) -> jnp.ndarray:
            devs = [_device_of(b) for b in bufs]
            ids = [getattr(d, "id", None) for d in devs]
            if (len(bufs) == len(mesh_ids) > 1 and ids == mesh_ids
                    and bufs[0].size >= self.psum_min_elements):
                # land the replicated psum output on the server device at
                # once: every downstream op (entry slicing, the per-OP
                # divisions, the server update) would otherwise run as an
                # SPMD eager dispatch over the whole mesh — an order of
                # magnitude more host overhead per op than the
                # single-device path
                return jax.device_put(_psum_rows(mesh, bufs),
                                      self.server_device)
            # colocate path: D2D copies onto the fold device, then the
            # host path's exact left fold
            target = self.server_device
            total = jax.device_put(bufs[0], target)
            for b in bufs[1:]:
                total = total + jax.device_put(b, target)
            return total

        return reduce_group


# the traced+compiled psum reduce, cached per (mesh identity, row count):
# rebuilding the shard_map closure per call would re-trace (and re-compile)
# every round
_REDUCE_CACHE: Dict[Tuple, Any] = {}


def _psum_reducer(mesh: Mesh, k: int):
    from repro.sharding.specs import dp_axes
    key = (tuple(d.id for d in mesh.devices.flat), k)
    fn = _REDUCE_CACHE.get(key)
    if fn is None:
        dp = dp_axes(mesh)

        @jax.jit
        @partial(jax.shard_map, mesh=mesh, in_specs=P(dp, None), out_specs=P())
        def fn(x):
            return jax.lax.psum(jnp.squeeze(x, 0), dp)

        _REDUCE_CACHE[key] = fn
    return fn


def _psum_rows(mesh: Mesh, bufs: List[jnp.ndarray]) -> jnp.ndarray:
    """One rank-ordered psum over per-device ``(n,)`` buffers: each buffer
    becomes its own ``(1, n)`` shard of a ``(K, n)`` array laid out over
    the mesh's data-parallel axes — assembled zero-copy from the
    single-device pieces, no host gather — and one collective reduces
    them."""
    from repro.sharding.specs import dp_axes, stacked_partial_spec
    dp = dp_axes(mesh)
    n = bufs[0].shape[0]
    sharding = NamedSharding(mesh, stacked_partial_spec(mesh))
    rows = [jnp.reshape(b, (1, n)) for b in bufs]   # on-device reshape
    stacked = jax.make_array_from_single_device_arrays(
        (len(bufs), n), sharding, rows)
    return _psum_reducer(mesh, len(bufs))(stacked)


def _put_tree(tree: Any, device: Any) -> Any:
    """Move every array leaf of an aggregate onto ``device`` (D2D; leaves
    already there are untouched, non-arrays pass through)."""
    def leaf(x):
        if hasattr(x, "sharding"):
            if _device_of(x) is device:
                return x
            return jax.device_put(x, device)
        return x
    return jax.tree.map(leaf, tree)


def colocate(x: Any, like: Any) -> Any:
    """Return ``x`` placed so it can combine with ``like`` (device-to-device
    copy when their single-device shardings differ; no-op otherwise)."""
    sh = getattr(like, "sharding", None)
    xsh = getattr(x, "sharding", None)
    if sh is None or xsh is None or xsh == sh:
        return x
    return jax.device_put(x, sh)
