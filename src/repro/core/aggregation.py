"""Hierarchical (local → global) aggregation with OP-typed parameters
(paper §3.2, §4.2) on a flatten-once flat-buffer layout.

Users declare, per communicated entry, an aggregation OP:

  WEIGHTED_AVG — Σ w_m x_m / Σ w_m        (model params/deltas; FedAvg etc.)
  AVG          — simple mean over clients
  SUM          — Σ x_m                    (counters, control-variate deltas)
  COLLECT      — concatenated per-client values ("Special Params."; cannot be
                 reduced, comm size stays O(s_e · M_p) — paper §4.2)

The decomposition is exact: executors fold their clients into a running
partial (``LocalAggregator``), the server combines the K partials
(``global_aggregate``).  ``flat_aggregate`` is the reference original-FL
aggregation; tests assert bit-level agreement for the reducible OPs.

The fold's inner loop (fp32 ``acc += w · x`` over every model parameter for
every simulated client) is the memory-bound hot-spot of the whole simulator.
``LocalAggregator`` therefore flattens each client's reducible payload ONCE
into a contiguous 1-D buffer per weight group (see ``flat.FlatLayout``),
stages up to ``micro_batch`` (B) client buffers, and folds them with a single
multi-client ``agg_weighted_sum`` call at C=B — one kernel dispatch per
micro-batch instead of one per pytree leaf per client.  ``use_kernel=True``
routes the flush through the Pallas kernel (with buffer donation on the
accumulator when it is not externally visible); ``use_kernel=False`` runs the
same left fold of fp32 multiply-adds in pure jnp.

The partial's wire format is flat too — ``{"sums": {"__flat__": True,
"buffers": {group: (n,) fp32}}, "layout": FlatLayout, ...}`` — so the comm
layer and the delta compressors move one array per partial instead of a
nested dict of leaves; ``global_aggregate`` combines partials with K-1
buffer adds per group and unflattens once at the end.
"""
from __future__ import annotations

import enum
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flat import (FlatLayout, flat_sums, is_compressed_buffer,
                             is_flat_partial)
from repro.core.telemetry import span


class Op(enum.Enum):
    WEIGHTED_AVG = "weighted_avg"
    AVG = "avg"
    SUM = "sum"
    COLLECT = "collect"


@dataclass(frozen=True)
class ClientResult:
    """What one simulated client returns to its executor.

    ``payload`` maps entry name -> pytree; ``ops`` maps entry name -> Op;
    ``weight`` is the client's aggregation weight (typically N_m).
    """
    payload: Dict[str, Any]
    ops: Dict[str, Op]
    weight: float
    metrics: Dict[str, float] = field(default_factory=dict)


def _multiply_sum(acc, rows, w):
    """``acc + Σ_c w_c · rows[c]`` as a left fold of fp32 multiply-adds —
    the same order the kernel folds in.  A VPU fold: a ``w @ D`` dot would
    materialise the (C, n) fp32 operand and, on TPU, round fp32 rows to
    bf16 on the MXU."""
    for c in range(len(rows)):
        acc = acc + w[c] * rows[c].astype(jnp.float32)
    return acc


@jax.jit
@jax.named_scope("fold")
def _flush_jnp(acc, staged, w):
    """Pure-jnp fused micro-batch flush of B staged (n,) buffers."""
    return _multiply_sum(acc, staged, w)


@jax.jit
@jax.named_scope("fold")
def _fold_stacked_jnp(acc, stacked, w):
    """Pure-jnp fold of an already-stacked (B, n) block."""
    return _multiply_sum(acc, stacked, w)


class LocalAggregator:
    """Per-executor running aggregate (``LocalAggregate`` in Algorithm 2).

    Memory is O(s_a) plus the staged micro-batch (at most ``micro_batch``
    client buffers) regardless of how many clients the executor simulates —
    this is the paper's memory claim for sequential training.

    ``micro_batch`` (B) controls how many client delta buffers are staged
    before ONE multi-client fold at C=B; the kernel path pads the final
    flush to exactly B with zero-weight rows so only a single (B, n) kernel
    specialisation is ever compiled per layout.
    """

    def __init__(self, ops: Dict[str, Op], use_kernel: bool = False,
                 micro_batch: int = 16,
                 layout: Optional[FlatLayout] = None,
                 device: Optional[Any] = None):
        self.ops = dict(ops)
        self.use_kernel = use_kernel
        self.micro_batch = max(1, int(micro_batch))
        self.layout = layout
        # owning device (device-pinned executors): accumulators, staged
        # buffers and the folds all live there; the partial ships
        # device-resident
        self.device = device
        self._acc: Optional[Dict[str, jnp.ndarray]] = None
        self._staged: Dict[str, List[jnp.ndarray]] = {}
        self._staged_w: Dict[str, List[float]] = {}
        self._exposed = False     # acc arrays escaped via partial(): no donate
        self._weights: Dict[str, float] = {}
        self._counts: Dict[str, int] = {}
        self._collected: Dict[str, List[Any]] = {}
        self.n_clients = 0

    @span("fold")
    def fold(self, result: ClientResult) -> None:
        self.n_clients += 1
        payload = result.payload
        for name in payload:
            op = self.ops[name]
            if op is Op.COLLECT:
                self._collected.setdefault(name, []).append(
                    (result.weight, payload[name]))
                continue
            w = result.weight if op is Op.WEIGHTED_AVG else 1.0
            self._weights[name] = self._weights.get(name, 0.0) + w
            self._counts[name] = self._counts.get(name, 0) + 1
        self._ensure_acc(payload)
        for g, buf in self.layout.flatten(payload, self.device).items():
            self._staged[g].append(buf)
            self._staged_w[g].append(
                result.weight if g == "weighted" else 1.0)
        if any(len(s) >= self.micro_batch for s in self._staged.values()):
            self._flush()

    def _ensure_acc(self, template_payload: Dict[str, Any]) -> None:
        """Lazily build the layout (from one un-batched template payload)
        and the per-group accumulators / staging buffers."""
        if self.layout is None:
            self.layout = FlatLayout.build(self.ops, template_payload)
        if self._acc is None:
            self._acc = self.layout.zeros(self.device)
            self._staged = {g: [] for g in self._acc}
            self._staged_w = {g: [] for g in self._acc}
            # zero rows that pad the final kernel flush up to B (shared;
            # model-sized, so only built for the kernel path)
            self._pad = {g: jnp.zeros((n,), self.layout.group_dtypes[g])
                         for g, n in self.layout.group_sizes.items()
                         if self.use_kernel}
            if self.device is not None:
                self._pad = {g: jax.device_put(b, self.device)
                             for g, b in self._pad.items()}

    @span("fold")
    def fold_block(self, stacked: Dict[str, Any],
                   weights: List[float]) -> None:
        """Fold a whole vmapped client block at once.

        ``stacked`` maps entry name -> pytree with a leading (B, ...) client
        axis — exactly what ``ClientStepEngine.run_block`` emits — and
        ``weights`` holds the B per-client aggregation weights.  Reducible
        entries flatten to one (B, n) buffer per group
        (``FlatLayout.flatten_batch``) and fold with ONE C=B dispatch
        straight into the accumulator; COLLECT entries are sliced out per
        client, as ``global_aggregate`` expects per-client values."""
        B = len(weights)
        self.n_clients += B
        for name in stacked:
            op = self.ops[name]
            if op is Op.COLLECT:
                rows = stacked[name]
                lst = self._collected.setdefault(name, [])
                for i in range(B):
                    lst.append((weights[i],
                                jax.tree.map(lambda x: x[i], rows)))
                continue
            wtot = float(sum(weights)) if op is Op.WEIGHTED_AVG else float(B)
            self._weights[name] = self._weights.get(name, 0.0) + wtot
            self._counts[name] = self._counts.get(name, 0) + B
        if self.layout is None or self._acc is None:
            self._ensure_acc({name: jax.tree.map(lambda x: x[0], val)
                              for name, val in stacked.items()})
        bufs = self.layout.flatten_batch(stacked, self.device)
        for g, D in bufs.items():
            w = jnp.asarray(weights if g == "weighted" else [1.0] * B,
                            jnp.float32)
            if self.use_kernel:
                from repro.kernels import ops as kops
                self._acc[g] = kops.agg_weighted_sum(
                    self._acc[g], D, w, donate=not self._exposed)
            else:
                self._acc[g] = _fold_stacked_jnp(self._acc[g], D, w)
        self._exposed = False

    @span("fold")
    def _flush(self) -> None:
        """Fold the staged micro-batch: ONE fused C=B dispatch per group."""
        for g, staged in self._staged.items():
            if not staged:
                continue
            t = len(staged)
            w = self._staged_w[g]
            if self.use_kernel:
                from repro.kernels import ops as kops
                B = self.micro_batch
                if t < B:   # zero-weight rows keep the (B, n) shape static
                    staged = staged + [self._pad[g]] * (B - t)
                    w = w + [0.0] * (B - t)
                self._acc[g] = kops.agg_fold_batch(
                    self._acc[g], staged, jnp.asarray(w, jnp.float32),
                    donate=not self._exposed)
            else:
                self._acc[g] = _flush_jnp(
                    self._acc[g], tuple(staged), jnp.asarray(w, jnp.float32))
            self._staged[g] = []
            self._staged_w[g] = []
        self._exposed = False

    @span("fold")
    def partial(self) -> Dict[str, Any]:
        """The G_k message sent to the server: one trip, O(s_a K) total —
        one flat fp32 buffer per group instead of a nested dict of leaves."""
        if any(self._staged.values()):
            self._flush()
        self._exposed = True    # returned arrays must survive further folds
        return {
            "sums": flat_sums(dict(self._acc) if self._acc is not None else {}),
            "layout": self.layout,
            "weights": dict(self._weights),
            "counts": dict(self._counts),
            "collected": {k: list(v) for k, v in self._collected.items()},
            "n_clients": self.n_clients,
        }


# ---------------------------------------------------------------------------
# staleness weighting (async bounded-staleness engine)
# ---------------------------------------------------------------------------

def _colocate(x: Any, like: Any) -> Any:
    """Place ``x`` so it can combine with ``like`` (device-pinned executors
    produce partials committed to different devices; combining them raises
    in jax unless one side moves — a direct D2D copy, no host round-trip)."""
    from repro.core.placement import colocate
    return colocate(x, like)


def merge_partials(acc: Optional[Dict[str, Any]],
                   partial: Dict[str, Any]) -> Dict[str, Any]:
    """Fold one partial into a running partial-of-partials (same wire
    format), so the async engine's server-side buffer stays O(s_a) no matter
    how many chunk partials land between model updates.  ``acc=None`` starts
    the accumulator (the first partial is copied shallowly so later merges
    never mutate an executor's live buffers).  Flat partials merge
    buffer-wise; legacy nested partials merge per-entry."""
    if acc is None:
        out = dict(partial)
        if is_flat_partial(partial):
            # compressed wire buffers (lazy decompress) decode here in one
            # dispatch per group; the accumulator itself stays dense
            from repro.core.compression import densify_buffer
            out["sums"] = flat_sums(
                {g: (densify_buffer(b) if is_compressed_buffer(b) else b)
                 for g, b in partial["sums"]["buffers"].items()})
        else:
            out["sums"] = dict(partial["sums"])
        out["weights"] = dict(partial.get("weights", {}))
        out["counts"] = dict(partial.get("counts", {}))
        out["collected"] = {k: list(v)
                            for k, v in partial.get("collected", {}).items()}
        return out
    if is_flat_partial(acc) != is_flat_partial(partial):
        raise ValueError("cannot merge flat and nested partials")
    if is_flat_partial(acc):
        la, lp = acc.get("layout"), partial.get("layout")
        if la is not None and lp is not None \
                and la.signature() != lp.signature():
            raise ValueError("flat partials built under different layouts")
        from repro.core.compression import densify_buffer, fold_buffer_into
        bufs = acc["sums"]["buffers"]
        for g, b in partial["sums"]["buffers"].items():
            if g not in bufs:
                bufs[g] = densify_buffer(b) if is_compressed_buffer(b) else b
            elif is_compressed_buffer(b):
                # fused decompress-into-fold: segments add straight into the
                # dense accumulator, no per-partial dense intermediate
                bufs[g] = fold_buffer_into(bufs[g], b)
            else:
                bufs[g] = bufs[g] + _colocate(b, bufs[g])
    else:
        sums = acc["sums"]
        for name, v in partial["sums"].items():
            sums[name] = (jax.tree.map(
                lambda x, y: x + _colocate(y, x), sums[name], v)
                if name in sums else v)
    for field_ in ("weights", "counts"):
        dst = acc[field_]
        for k, v in partial.get(field_, {}).items():
            dst[k] = dst.get(k, 0) + v
    for k, v in partial.get("collected", {}).items():
        acc["collected"].setdefault(k, []).extend(v)
    acc["n_clients"] = acc.get("n_clients", 0) + partial.get("n_clients", 0)
    return acc


def tree_reduce_partials(partials: List[Dict[str, Any]],
                         fan_in: int = 8) -> List[Dict[str, Any]]:
    """Hierarchical aggregation tree (executor → group → server): reduce a
    wide partial list level by level, left-folding contiguous groups of
    ``fan_in`` partials with :func:`merge_partials` (the same O(s)
    incremental flat fold the async buffer uses) until at most ``fan_in``
    remain.  The server-side live buffer at any instant is one group
    accumulator — O(fan_in) partials, not O(K) — and the returned list
    feeds the ordinary flat reduce (or the placement collective)
    unchanged.  A list already at or below ``fan_in`` is returned as-is,
    so narrow folds keep the legacy path byte-for-byte.

    Grouping re-associates the float summation relative to the flat
    left-fold, which is why the engines only route through the tree above
    ``fold_fan_in`` (ISSUE pins bit-identity on the exactly-representable
    payloads of tests/test_flat_aggregation.py)."""
    if fan_in < 2:
        raise ValueError(f"fan_in must be >= 2 (got {fan_in})")
    level = list(partials)
    while len(level) > fan_in:
        nxt = []
        for i in range(0, len(level), fan_in):
            acc: Optional[Dict[str, Any]] = None
            for p in level[i:i + fan_in]:
                acc = merge_partials(acc, p)
            nxt.append(acc)
        level = nxt
    return level


def staleness_weight(staleness: float, lam: float) -> float:
    """Bounded-staleness discount γ = 1 / (1 + λ·s): a partial computed
    against a model ``s`` server versions old contributes with weight γ — it
    still moves the model (no work wasted), but cannot drag it back towards
    where it was ``s`` updates ago at full strength."""
    return 1.0 / (1.0 + lam * max(float(staleness), 0.0))


def scale_partial(partial: Dict[str, Any], gamma: float) -> Dict[str, Any]:
    """Scale a partial's *contribution* by ``gamma`` on the wire format.

    Both the numerators (the flat group buffers, or nested sum leaves) and
    the denominators (per-entry weights and counts) scale together, so a
    γ-scaled partial enters WEIGHTED_AVG / AVG entries with relative weight
    γ versus fresh partials, SUM entries are discounted to γ·Σ, and COLLECT
    entries keep their values with γ-scaled client weights.  ``gamma == 1``
    returns the partial unchanged (no copy)."""
    if gamma == 1.0:
        return partial
    out = dict(partial)
    sums = partial.get("sums", {})
    if is_flat_partial(partial):
        from repro.core.compression import scale_buffer
        out["sums"] = flat_sums(
            {g: (scale_buffer(b, gamma) if is_compressed_buffer(b)
                 else b * gamma)
             for g, b in sums["buffers"].items()})
    else:
        out["sums"] = {name: jax.tree.map(lambda x: x * gamma, v)
                       for name, v in sums.items()}
    out["weights"] = {k: v * gamma
                      for k, v in partial.get("weights", {}).items()}
    out["counts"] = {k: v * gamma
                     for k, v in partial.get("counts", {}).items()}
    out["collected"] = {k: [(w * gamma, v) for w, v in lst]
                        for k, lst in partial.get("collected", {}).items()}
    return out


# ---------------------------------------------------------------------------
# global aggregate
# ---------------------------------------------------------------------------

def _sum_buffers(bufs: List[jnp.ndarray]) -> jnp.ndarray:
    total = bufs[0]
    for b in bufs[1:]:
        total = total + _colocate(b, total)
    return total


def reduce_partials(partials: List[Dict[str, Any]], ops: Dict[str, Op],
                    reduce_fn: Optional[Callable[[List[jnp.ndarray]],
                                                 jnp.ndarray]] = None
                    ) -> Dict[str, Any]:
    """The reduction across flat partials, and nothing after it.

    ``reduce_fn`` sums the per-group buffers (K-1 adds by default; one
    sharded collective in ``comm.collective`` and ``core.placement``);
    compressed wire buffers fold in here.  Returns the reduced aggregate:
    the summed group ``buffers``, their ``layout``, each AVG / WEIGHTED_AVG
    entry's ``divisors`` (the count or weight total, as a float) and the
    ``collected`` COLLECT lists.  :func:`expand_aggregate` turns it into
    ``{entry: pytree}``: eagerly in ``global_aggregate``, inside the
    compiled server step in ``ParrotServer.server_update``."""
    if not all(is_flat_partial(p) for p in partials):
        raise ValueError("reduce_partials takes flat partials only")
    reduce_fn = _sum_buffers if reduce_fn is None else reduce_fn
    layout = next((p.get("layout") for p in partials
                   if p.get("layout") is not None), None)
    if layout is not None:
        sig = layout.signature()
        for p in partials:
            other = p.get("layout")
            if other is not None and other.signature() != sig:
                raise ValueError("flat partials built under different layouts")
    totals: Dict[str, jnp.ndarray] = {}
    for g in (layout.group_sizes if layout is not None else {}):
        bufs = [p["sums"]["buffers"][g] for p in partials
                if g in p["sums"]["buffers"]]
        if not bufs:
            continue
        if any(is_compressed_buffer(b) for b in bufs):
            # lazily-compressed wire buffers: order-preserving fused
            # decompress-into-fold (reduce_fn — including the sharded psum —
            # needs dense same-device buffers, so the compressed path folds
            # here instead)
            from repro.core.compression import (densify_buffer,
                                                fold_buffer_into)
            total = (densify_buffer(bufs[0])
                     if is_compressed_buffer(bufs[0]) else bufs[0])
            for b in bufs[1:]:
                total = (fold_buffer_into(total, b)
                         if is_compressed_buffer(b)
                         else total + _colocate(b, total))
            totals[g] = total
        else:
            totals[g] = reduce_fn(bufs)
    divisors: Dict[str, float] = {}
    collected: Dict[str, List[Any]] = {}
    for name, op in ops.items():
        if op is Op.COLLECT:
            coll: List[Any] = []
            for p in partials:
                coll.extend(p["collected"].get(name, []))
            collected[name] = coll
        elif op is Op.AVG:
            n = sum(p["counts"].get(name, 0) for p in partials)
            divisors[name] = float(max(n, 1))
        elif op is Op.WEIGHTED_AVG:
            wtot = sum(p["weights"].get(name, 0.0) for p in partials)
            divisors[name] = float(max(wtot, 1e-12))
    return {"buffers": totals, "layout": layout, "divisors": divisors,
            "collected": collected}


def expand_aggregate(reduced: Dict[str, Any], ops: Dict[str, Op],
                     shaped: bool = True) -> Dict[str, Any]:
    """``{entry: pytree}`` from a reduced aggregate: each entry sliced from
    its group buffer, divided per its OP, unflattened into fp32 leaves (1-D
    pieces of the buffer with ``shaped=False``); COLLECT entries as their
    lists.  Pure jnp on the buffers, so it runs eagerly or traced: the
    divisors may be floats or traced scalars."""
    layout, totals = reduced["layout"], reduced["buffers"]
    out: Dict[str, Any] = {}
    for name, op in ops.items():
        if op is Op.COLLECT:
            out[name] = reduced["collected"][name]
            continue
        span = layout.spans.get(name) if layout is not None else None
        if span is None or span.group not in totals:
            continue
        tree = layout.unflatten_entry(
            name, totals[span.group][span.offset:span.offset + span.size],
            shaped)
        if name in reduced["divisors"]:
            # leaf by leaf, so a compiled caller fuses each leaf's divide
            # into the pass that consumes it
            d = reduced["divisors"][name]
            tree = jax.tree.map(lambda x: x / d, tree)
        out[name] = tree
    return out


def reduce_flat_partials(partials: List[Dict[str, Any]], ops: Dict[str, Op],
                         reduce_fn: Callable[[List[jnp.ndarray]], jnp.ndarray]
                         ) -> Dict[str, Any]:
    """Combine flat partials eagerly: :func:`reduce_partials`, then each
    entry sliced, divided per its OP and unflattened, one eager op at a
    time (the reference the compiled server step is pinned against)."""
    return expand_aggregate(reduce_partials(partials, ops, reduce_fn), ops)


def global_aggregate(partials: List[Dict[str, Any]],
                     ops: Dict[str, Op]) -> Dict[str, Any]:
    """``GlobalAggregate`` in Algorithm 2: combine the K partials (K-1 sums
    at the server instead of M_p-1).  Flat partials combine buffer-wise —
    one add chain per group; legacy nested partials keep the per-entry
    tree-map path (mixed inputs degrade flat ones to nested)."""
    if partials and all(is_flat_partial(p) for p in partials):
        return reduce_flat_partials(partials, ops, _sum_buffers)
    if any(is_flat_partial(p) for p in partials):
        from repro.core.flat import to_nested_sums
        partials = [dict(p, sums=to_nested_sums(p)) if is_flat_partial(p)
                    else p for p in partials]
    out: Dict[str, Any] = {}
    for name, op in ops.items():
        if op is Op.COLLECT:
            coll: List[Any] = []
            for p in partials:
                coll.extend(p["collected"].get(name, []))
            out[name] = coll
            continue
        sums = [p["sums"][name] for p in partials if name in p["sums"]]
        if not sums:
            continue
        total = jax.tree.map(
            lambda *xs: _sum_buffers(list(xs)) if hasattr(xs[0], "sharding")
            else sum(xs), *sums)
        if op is Op.SUM:
            out[name] = total
        elif op is Op.AVG:
            n = sum(p["counts"].get(name, 0) for p in partials)
            out[name] = jax.tree.map(lambda a: a / max(n, 1), total)
        else:  # WEIGHTED_AVG
            wtot = sum(p["weights"].get(name, 0.0) for p in partials)
            out[name] = jax.tree.map(lambda a: a / max(wtot, 1e-12), total)
    return out


def flat_aggregate(results: Iterable[ClientResult],
                   ops: Dict[str, Op]) -> Dict[str, Any]:
    """Reference original-FL aggregation (server folds every client) used to
    verify exactness of the hierarchical scheme.  ``results`` is folded as
    it is consumed, so a generator never holds every client's delta."""
    agg = LocalAggregator(ops)
    for r in results:
        agg.fold(r)
    return global_aggregate([agg.partial()], ops)


def payload_bytes(tree: Any) -> int:
    """Wire size of a payload/partial: arrays at shape x itemsize (flat group
    buffers included), compressed tensors at their achieved nbytes, scalars
    at 8; layout metadata is free."""
    total = 0
    for a in jax.tree.leaves(tree):
        # CompressedTensor carries shape + a *str* dtype: require a real
        # dtype (itemsize) before the dense branch, else fall to nbytes
        if hasattr(a, "shape") and hasattr(getattr(a, "dtype", None),
                                           "itemsize"):
            total += int(np.prod(a.shape)) * a.dtype.itemsize
        elif hasattr(a, "nbytes"):      # CompressedTensor and friends
            total += int(a.nbytes)
        elif isinstance(a, (int, float, bool)):
            total += 8
    return total


def wire_bytes(payload: Any) -> int:
    """Achieved wire size of a payload: a compressed partial (compressors
    stamp ``_wire_bytes`` on the sums they shrank) counts its compressed
    sums plus the uncompressed rest; everything else is ``payload_bytes``.
    This is the size the comm layer accounts AND the size the network model
    prices uploads at (``core/network.py``) — one definition for both."""
    if isinstance(payload, dict) and "_wire_bytes" in payload:
        rest = {k: v for k, v in payload.items()
                if k not in ("sums", "_wire_bytes")}
        return int(payload["_wire_bytes"]) + payload_bytes(rest)
    return payload_bytes(payload)
