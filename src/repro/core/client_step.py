"""Compiled client-training engine — the simulator's true hot path.

``FLAlgorithm.client_update`` (the eager reference path, kept and used by
``run_flat_reference``) dispatches one un-jitted op per pytree leaf per SGD
step per client; simulating 1000 clients is then dominated by Python/XLA
dispatch overhead rather than FLOPs.  ``ClientStepEngine`` instead rolls each
algorithm's pure ``(carry, batch, mask) -> carry`` step (see
``FLAlgorithm.local_step``) into ONE ``jax.jit``-compiled ``lax.scan`` over
all tau = local_epochs x n_batches local steps — one dispatch per client —
and additionally ``vmap``s that scan over a block of B same-shape clients —
one dispatch per block — producing stacked ``(B, ...)`` deltas that feed the
flat-buffer aggregator directly (``LocalAggregator.fold_block``), with no
per-client unflatten/refold round-trip through ``ClientResult``.

Shape discipline (bounded compile count): per-client batch counts and block
sizes are padded up to the next power of two — batches with repeats of the
client's first batch plus a 0/1 step mask, blocks with replicas of the first
client whose outputs are sliced off.  A masked step multiplies the update by
zero, so padding is *exact*; jit then caches one executable per (algorithm,
payload shapes, batch bucket[, block bucket]) instead of one per raw
(n_batches, B) pair.  On accelerator backends the stacked-batch and mask
arguments are donated (they are rebuilt per call) and the scan carry is
donated by XLA internally; on CPU donation is skipped (it would only warn).

Clients whose batches are ragged (shapes differ within one client) cannot be
scanned; the engine transparently falls back to the eager reference path for
exactly those clients.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.aggregation import ClientResult
from repro.core.algorithms import ClientData, FLAlgorithm

Pytree = Any


def _bucket(n: int) -> int:
    """Next power of two >= n (n >= 1) — the scan-length / block bucket."""
    return 1 << max(n - 1, 0).bit_length()


# Process-wide XLA compile counter.  Executors snapshot it around a timed
# block: if it advanced, the block's wall time paid a one-off compile
# (engine scan, flatten_batch, fold — any jit anywhere in the region) and
# the measurement is re-taken from the warm caches so virtual time reflects
# steady-state throughput.
_compile_events = 0


def _on_compile_event(event: str, duration: float, **kw) -> None:
    global _compile_events
    if event.startswith("/jax/core/compile"):
        _compile_events += 1


jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


def compile_events() -> int:
    """Monotonic count of XLA compile events in this process."""
    return _compile_events


def batch_signature(data: ClientData) -> Optional[Tuple]:
    """Hashable grouping key for cross-client blocking: clients with equal
    signatures stack into one vmapped scan.  The batch count enters through
    its power-of-two bucket (mask padding makes unequal counts compatible).
    Returns None when the client's batches are ragged (eager fallback)."""
    bs = data.batches
    if not bs:
        return None
    treedef = jax.tree.structure(bs[0])
    shapes = tuple((tuple(np.shape(l)), str(getattr(l, "dtype", "?")))
                   for l in jax.tree.leaves(bs[0]))
    for b in bs[1:]:
        if jax.tree.structure(b) != treedef:
            return None
        if tuple((tuple(np.shape(l)), str(getattr(l, "dtype", "?")))
                 for l in jax.tree.leaves(b)) != shapes:
            return None
    return (_bucket(len(bs)), treedef, shapes)


def stack_batches(data: ClientData, *, assume_uniform: bool = False
                  ) -> Optional[Tuple[Any, np.ndarray]]:
    """One leading-axis batch pytree + 0/1 step mask for a client, padded to
    the power-of-two bucket with repeats of the first batch (finite data, so
    the masked zero-update is exact).  None when the batches are ragged.

    ``assume_uniform=True`` skips the ragged check when the caller already
    grouped clients by :func:`batch_signature` (the executor's block
    planner) — the signature walk is O(n_batches x n_leaves) per client and
    would otherwise run twice per round on the hot path."""
    if not assume_uniform and batch_signature(data) is None:
        return None
    bs = data.batches
    n, n_pad = len(bs), _bucket(len(bs))
    padded = list(bs) + [bs[0]] * (n_pad - n)
    stacked = jax.tree.map(lambda *xs: np.stack([np.asarray(x) for x in xs]),
                           *padded)
    mask = np.zeros((n_pad,), np.float32)
    mask[:n] = 1.0
    return stacked, mask


class PlacedCache:
    """Single-slot identity-keyed memo of 'host object(s) -> placed copy'.

    Payload placement is memoized in three spots (executor payload pin,
    engine commit, gang replication) — one helper keeps the invalidation
    semantics (same object identity ⇒ same placed copy) in one place."""

    __slots__ = ("_key", "_val")

    def __init__(self):
        self._key = None
        self._val = None

    def get(self, key_objs: Tuple, place: Callable[[], Any]) -> Any:
        if self._key is None or len(self._key) != len(key_objs) or \
                any(a is not b for a, b in zip(self._key, key_objs)):
            self._val = place()
            self._key = tuple(key_objs)
        return self._val

    def clear(self) -> None:
        self._key = self._val = None


class ClientStepEngine:
    """One compiled scan (and its vmapped block form) per (algorithm,
    device).

    jax.jit owns the executable cache: one entry per distinct (payload
    shapes, state shapes, batch bucket) for the single-client scan, plus one
    per block bucket for the vmapped form — cached across rounds and
    clients.  Executors sharing an algorithm instance *and* a device share
    the engine (and therefore the cache) through :func:`engine_for`; a
    device-pinned engine commits its inputs to that device, so its
    executables compile for — and its outputs stay resident on — exactly
    that device (an uncommitted input would silently drag the computation
    onto the process default device, serializing every executor on it).

    Donation: the vmapped block form donates its freshly-stacked (B, ...)
    batch/mask arrays on accelerator backends (rebuilt per call).  The
    single-client form does NOT donate batches — they may come from the
    executor's device-resident stacked-batch cache and must survive the
    call.
    """

    def __init__(self, algorithm: FLAlgorithm, device=None):
        self.algorithm = algorithm
        self.device = device
        self.n_dispatches = 0       # compiled calls issued (bench metric)
        donate = jax.default_backend() in ("tpu", "gpu")
        kw = dict(donate_argnums=(2, 3)) if donate else {}
        self._run_jit = jax.jit(self._run_one)
        self._run_block_jit = jax.jit(
            jax.vmap(self._run_one, in_axes=(None, 0, 0, 0)), **kw)
        # fused on-device block stack for cached (device-resident) preps:
        # one compiled dispatch per (B, shapes) instead of one eager
        # jnp.stack per pytree leaf per block (eager ops re-trace, and at
        # dispatch-bound block sizes that per-block churn dominates)
        self._stack_jit = jax.jit(
            lambda bats, masks: (jax.tree.map(lambda *xs: jnp.stack(xs),
                                              *bats), jnp.stack(masks)))
        self._payload_cache = PlacedCache()
        self._gang_payload_cache = PlacedCache()

    def _commit(self, tree: Any) -> Any:
        """Commit a pytree to the engine's device (no-op copies for leaves
        already resident there; identity when the engine is unpinned)."""
        if self.device is None:
            return tree
        return jax.device_put(tree, self.device)

    def _commit_payload(self, payload: Dict) -> Dict:
        """Commit the broadcast payload once per payload object: callers
        re-use one payload across every block of a round (and the async
        engine across rounds), so the per-leaf device_put walk — pure host
        overhead at dispatch-bound block sizes — must not repeat per call."""
        if self.device is None:
            return payload
        return self._payload_cache.get(
            (payload,), lambda: jax.device_put(payload, self.device))

    # ------------------------------------------------------------------
    @jax.named_scope("client_step")
    def _run_one(self, payload: Dict, state: Optional[Pytree], batches: Any,
                 mask: jnp.ndarray) -> Tuple[Dict[str, Any], Optional[Pytree]]:
        """The whole local update as one traced program: init carry, scan
        tau steps, finalize.  Shapes only — jit/vmap do the rest.  Where
        the grad_fn returns step stats, the result payload carries their sum
        over the real steps under ``"stats"``, which the executor takes
        before the fold."""
        algo = self.algorithm
        carry = algo.init_carry(payload, state)

        def step(c, xs):
            b, m = xs
            return algo.local_step(c, b, m)

        def epoch(c, _):
            return jax.lax.scan(step, c, (batches, mask))

        # length=0 is a valid no-op scan, matching the eager path's zero
        # local steps for local_epochs=0
        carry, stats = jax.lax.scan(epoch, carry, None,
                                    length=algo.local_epochs)
        out, new_state = algo.finalize(carry, payload, state, batches, mask)
        if not stats:
            return out, new_state
        # (epochs, steps, ...) -> the sum over both
        return dict(out, stats=jax.tree.map(
            lambda s: jnp.sum(s, axis=(0, 1)), stats)), new_state

    # ------------------------------------------------------------------
    def run_client(self, payload: Dict, data: ClientData,
                   state: Optional[Pytree] = None, *,
                   assume_uniform: bool = False,
                   prep: Optional[Tuple[Any, Any]] = None
                   ) -> Tuple[ClientResult, Optional[Pytree]]:
        """Compiled drop-in for ``algorithm.client_update``: one dispatch for
        the whole tau-step local update (eager fallback on ragged batches;
        ``assume_uniform=True`` skips the ragged walk when the caller
        already checked the signature).  ``prep`` supplies a pre-stacked
        (batches, mask) pair — typically device-resident from the
        executor's stacked-batch cache — skipping the host stack."""
        if prep is None:
            prep = stack_batches(data, assume_uniform=assume_uniform)
        if prep is None:
            return self.algorithm.client_update(payload, data, state)
        batches, mask = prep
        self.n_dispatches += 1
        # state may be uncommitted (it then follows the committed payload /
        # batches onto the device) — only payload and host-built batches
        # need explicit placement
        on_device = hasattr(jax.tree.leaves(batches)[0], "sharding") \
            if jax.tree.leaves(batches) else False
        if not on_device:
            batches, mask = self._commit(batches), self._commit(
                jnp.asarray(mask))
        out_payload, new_state = self._run_jit(
            self._commit_payload(payload), state, batches,
            jnp.asarray(mask))
        return (ClientResult(out_payload, self.algorithm.ops(),
                             weight=float(data.n_samples)), new_state)

    def run_block(self, payload: Dict, datas: Sequence[ClientData],
                  states: Optional[Sequence[Pytree]] = None,
                  preps: Optional[Sequence[Tuple[Any, Any]]] = None
                  ) -> Tuple[Dict[str, Any], Optional[List[Pytree]]]:
        """One vmapped compiled scan over a block of B same-signature
        clients (the caller groups by :func:`batch_signature`).  Returns the
        stacked result payload (leading B axis, ready for
        ``LocalAggregator.fold_block``) and the per-client new states.

        The block is padded to the power-of-two bucket with replicas of the
        first client; padded rows are sliced off before returning, so the
        caller never sees them.  ``preps`` supplies per-client pre-stacked
        (batches, mask) pairs (the executor's device-resident cache); the
        block stack then runs on the owning device (``jnp.stack``) instead
        of re-staging O(block data) through the host every round."""
        B = len(datas)
        B_pad = _bucket(B)
        try:
            if preps is None:
                preps = [stack_batches(d, assume_uniform=True)
                         for d in datas]
            preps = list(preps) + [preps[0]] * (B_pad - B)
            first = jax.tree.leaves(preps[0][0])
            on_device = bool(first) and hasattr(first[0], "sharding")
            if on_device:
                batches, mask = self._stack_jit([p[0] for p in preps],
                                                [p[1] for p in preps])
            else:
                batches = jax.tree.map(lambda *xs: np.stack(xs),
                                       *[p[0] for p in preps])
                mask = np.stack([p[1] for p in preps])
        except ValueError as e:
            raise ValueError("ragged or mixed-shape client batches cannot "
                             "be blocked; group by batch_signature() first"
                             ) from e
        sstates = None
        if states is not None:
            padded = list(states) + [states[0]] * (B_pad - B)
            sstates = jax.tree.map(lambda *xs: jnp.stack(xs), *padded)
        if not on_device:
            batches, mask = self._commit(batches), self._commit(
                jnp.asarray(mask))
        self.n_dispatches += 1
        out_payload, new_states = self._run_block_jit(
            self._commit_payload(payload), sstates, batches,
            jnp.asarray(mask))
        if B_pad > B:
            out_payload = jax.tree.map(lambda x: x[:B], out_payload)
        if states is None:
            return out_payload, None
        return out_payload, [jax.tree.map(lambda x: x[i], new_states)
                             for i in range(B)]

    # ------------------------------------------------------------------
    def run_blocks_sharded(self, payload: Dict, preps, states, mesh
                           ) -> List[Tuple[Dict[str, Any], Any]]:
        """One SPMD dispatch running K same-bucket client blocks, one per
        mesh device (DESIGN.md §8 gang dispatch).

        ``preps``: K pairs of (stacked batches pytree (B, ...), mask
        (B, n)), the k-th committed to the k-th mesh device, all with equal
        B and shapes.  ``states``: K stacked state pytrees (or None).  The
        per-device pieces are assembled zero-copy into global ``(K·B, ...)``
        arrays sharded ``P("data")`` over the mesh, and the SAME vmapped
        scan executable runs them — XLA partitions the vmap axis, so the K
        blocks execute *concurrently*, one per device, in a single
        execution (separate single-device dispatches serialize in the CPU
        PJRT client; SPMD executions fan out per-device threads — this is
        where the CPU device-count speedup physically comes from).

        Returns K ``(stacked result payload, stacked new states)`` pairs,
        each left resident on its own device."""
        devices = list(mesh.devices.flat)
        K = len(devices)
        assert len(preps) == K
        row = NamedSharding(mesh, P("data"))

        def assemble(pieces):
            pieces = [jnp.asarray(p) for p in pieces]
            shape = (K * pieces[0].shape[0],) + pieces[0].shape[1:]
            return jax.make_array_from_single_device_arrays(
                shape, row, pieces)

        batches = jax.tree.map(lambda *xs: assemble(xs),
                               *[p[0] for p in preps])
        mask = assemble([p[1] for p in preps])
        sstates = None
        if states is not None:
            sstates = jax.tree.map(lambda *xs: assemble(xs), *states)
        repl = self._gang_payload_cache.get(
            (payload, mesh),
            lambda: jax.device_put(payload, NamedSharding(mesh, P())))
        self.n_dispatches += 1
        out_payload, new_states = self._run_block_jit(repl, sstates,
                                                      batches, mask)

        def split_tree(tree):
            """tree of (K·B, ...) sharded arrays -> K trees of (B, ...)
            single-device arrays, each still resident on its device
            (addressable shards — no gather, no copy)."""
            leaves, treedef = jax.tree.flatten(tree)
            parts = []
            for leaf in leaves:
                by_dev = {s.device.id: s.data
                          for s in leaf.addressable_shards}
                parts.append([by_dev[d.id] for d in devices])
            return [jax.tree.unflatten(treedef, [p[k] for p in parts])
                    for k in range(K)]

        payloads = split_tree(out_payload)
        state_parts = (split_tree(new_states) if new_states is not None
                       else [None] * K)
        return list(zip(payloads, state_parts))

    # ------------------------------------------------------------------
    def compile_count(self) -> int:
        """Executables compiled so far (scan + vmapped scan caches)."""
        total = 0
        for fn in (self._run_jit, self._run_block_jit):
            size = getattr(fn, "_cache_size", None)
            if callable(size):
                total += size()
        return total


def engine_for(algorithm: FLAlgorithm,
               device=None) -> ClientStepEngine:
    """The algorithm instance's engine for ``device`` (executors sharing
    the algorithm *and* the device share one compile cache).

    The cache is keyed on the device id: a multi-device run gets one engine
    — one set of executables — per device, so executors can never thrash a
    shared cache or be handed an executable compiled (and resident) on
    another executor's device."""
    cache = getattr(algorithm, "_step_engines", None)
    if cache is None:
        cache = algorithm._step_engines = {}
    key = getattr(device, "id", None) if device is not None else None
    eng = cache.get(key)
    if eng is None:
        eng = cache[key] = ClientStepEngine(algorithm, device=device)
    return eng
