"""Flatten-once parameter layout for batched multi-client aggregation.

The aggregation hot loop folds every simulated client's multi-entry delta
(a dict of pytrees) into the executor's O(s_a) fp32 partial.  Folding leaf by
leaf dispatches one kernel per pytree leaf per client — pure dispatch and
padding overhead on the simulator's hottest path.  A :class:`FlatLayout`
computes, once per round, the mapping

    entry name -> (group, offset, size)        per communicated entry
    leaf       -> (offset, size, shape, dtype) per pytree leaf

so a client's whole reducible payload becomes ONE contiguous 1-D buffer per
*weight group*:

  ``weighted`` — entries aggregated as Σ w_m x_m (``Op.WEIGHTED_AVG``)
  ``unit``     — entries aggregated with unit weight (``Op.AVG``/``Op.SUM``)

The two groups exist because a single fold applies one scalar weight per
client; WEIGHTED_AVG entries fold at w_m while AVG/SUM entries fold at 1.0.
``Op.COLLECT`` entries are excluded (they cannot be reduced; they ride the
partial as a per-client list exactly as before).

With the layout in hand, ``LocalAggregator`` stages up to B client buffers
and folds them with a single ``agg_weighted_sum`` kernel dispatch at C=B —
one dispatch per micro-batch instead of leaves x clients — and the global
aggregate / compressors / comm paths all move one array per partial instead
of a nested dict of leaves.

The group buffer dtype is ``jnp.result_type`` over the member leaf dtypes:
an all-bf16 delta stays bf16 on the wire into the fold (halving bytes
moved); mixed bf16/fp32 promotes to fp32.  Accumulators and unflattened
aggregates are always fp32.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

GROUPS = ("weighted", "unit")


@dataclass(frozen=True)
class LeafSpec:
    """One pytree leaf's home in its group buffer."""
    entry: str
    index: int                 # leaf index within the entry's pytree
    offset: int                # into the group buffer
    size: int
    shape: Tuple[int, ...]
    dtype: Any                 # the leaf's original dtype


@dataclass(frozen=True)
class EntrySpan:
    """One entry's contiguous span in its group buffer (leaves of an entry
    are always laid out contiguously, so compressors can treat the entry as
    a single 1-D array)."""
    group: str
    offset: int
    size: int


def _group_of(op: Any) -> str:
    return "weighted" if getattr(op, "name", None) == "WEIGHTED_AVG" else "unit"


class FlatLayout:
    """Leaf names -> offsets/shapes/dtypes, computed once from the
    algorithm's ops plus one template payload."""

    def __init__(self, specs: Dict[str, Tuple[LeafSpec, ...]],
                 spans: Dict[str, EntrySpan],
                 treedefs: Dict[str, Any],
                 group_sizes: Dict[str, int],
                 group_dtypes: Dict[str, Any],
                 entry_order: Dict[str, Tuple[str, ...]]):
        self.specs = specs                  # group -> LeafSpecs in offset order
        self.spans = spans                  # entry  -> EntrySpan
        self.treedefs = treedefs            # entry  -> pytree treedef
        self.group_sizes = group_sizes      # group  -> total element count
        self.group_dtypes = group_dtypes    # group  -> buffer dtype
        self.entry_order = entry_order      # group  -> entry names in order
        # one jit per layout; executables inside it are keyed by the input
        # shardings, so per-device callers (``device=``) get their own
        # executables out of the same cache — a layout shared across
        # device-pinned executors never cross-wires or thrashes
        self._flatten_jit = None
        self._flatten_batch_jit = None

    # ------------------------------------------------------------------
    @classmethod
    def build(cls, ops: Dict[str, Any], payload: Dict[str, Any]) -> "FlatLayout":
        """Compute the layout from the OP registry and a template payload.
        COLLECT entries and entries absent from the payload are skipped."""
        specs: Dict[str, List[LeafSpec]] = {g: [] for g in GROUPS}
        spans: Dict[str, EntrySpan] = {}
        treedefs: Dict[str, Any] = {}
        order: Dict[str, List[str]] = {g: [] for g in GROUPS}
        cursor = {g: 0 for g in GROUPS}
        for name, value in payload.items():
            op = ops.get(name)
            if op is None or getattr(op, "name", None) == "COLLECT":
                continue
            g = _group_of(op)
            leaves, treedef = jax.tree.flatten(value)
            treedefs[name] = treedef
            order[g].append(name)
            start = cursor[g]
            for i, leaf in enumerate(leaves):
                shape = tuple(jnp.shape(leaf))
                size = int(np.prod(shape)) if shape else 1
                specs[g].append(LeafSpec(name, i, cursor[g], size, shape,
                                         jnp.asarray(leaf).dtype))
                cursor[g] += size
            spans[name] = EntrySpan(g, start, cursor[g] - start)
        sizes = {g: cursor[g] for g in GROUPS if cursor[g]}
        dtypes = {g: jnp.result_type(*[s.dtype for s in specs[g]])
                  for g in sizes}
        return cls({g: tuple(specs[g]) for g in sizes}, spans, treedefs,
                   sizes, dtypes, {g: tuple(order[g]) for g in sizes})

    # ------------------------------------------------------------------
    @jax.named_scope("fold")
    def _flatten_impl(self, payload: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
        out: Dict[str, jnp.ndarray] = {}
        for g, entries in self.entry_order.items():
            dtype = self.group_dtypes[g]
            parts = []
            for name in entries:
                for leaf in jax.tree.leaves(payload[name]):
                    parts.append(jnp.ravel(jnp.asarray(leaf)).astype(dtype))
            out[g] = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        return out

    @staticmethod
    def _commit(payload: Dict[str, Any], device) -> Dict[str, Any]:
        """Commit every leaf to ``device`` (no-op for leaves already there)
        so the jitted flatten runs — and its output stays — on that device
        rather than silently landing on the process default device."""
        if device is None:
            return payload
        from jax.sharding import SingleDeviceSharding
        home = SingleDeviceSharding(device)   # cheap equality per leaf
        return jax.tree.map(
            lambda x: x if getattr(x, "sharding", None) == home
            else jax.device_put(x, device), payload)

    def flatten(self, payload: Dict[str, Any],
                device=None) -> Dict[str, jnp.ndarray]:
        """One contiguous 1-D buffer per group from a client payload.

        Jit-compiled once per layout (flatten-once): the whole
        ravel/cast/concat chain fuses into a single dispatch per client
        instead of one op per pytree leaf.  ``device`` commits the inputs
        (and therefore the buffers) to a specific device — the executables
        are cached per sharding inside the one jit."""
        if self._flatten_jit is None:
            self._flatten_jit = jax.jit(self._flatten_impl)
        return self._flatten_jit(self._commit(payload, device))

    def flatten_batch(self, payload: Dict[str, Any],
                      device=None) -> Dict[str, jnp.ndarray]:
        """(B, n) group buffers from a payload with a leading client axis —
        the vmapped-client-engine analogue of ``flatten``: one fused
        dispatch flattens a whole block, and the result folds directly with
        a single C=B kernel call (no per-client unflatten/refold).  The
        batched form is literally ``vmap(_flatten_impl)``, so the two paths
        cannot drift apart."""
        if self._flatten_batch_jit is None:
            self._flatten_batch_jit = jax.jit(jax.vmap(self._flatten_impl))
        return self._flatten_batch_jit(self._commit(payload, device))

    def zeros(self, device=None) -> Dict[str, jnp.ndarray]:
        """Fresh fp32 accumulators, one per group (the O(s_a) partial),
        resident on ``device`` when given."""
        out = {g: jnp.zeros((n,), jnp.float32)
               for g, n in self.group_sizes.items()}
        if device is not None:
            out = {g: jax.device_put(b, device) for g, b in out.items()}
        return out

    def entry_slice(self, name: str, buffers: Dict[str, jnp.ndarray]
                    ) -> jnp.ndarray:
        """The entry's contiguous 1-D segment of its group buffer."""
        span = self.spans[name]
        return buffers[span.group][span.offset:span.offset + span.size]

    def unflatten_entry(self, name: str, segment: jnp.ndarray,
                        shaped: bool = True) -> Any:
        """Rebuild one entry's pytree (fp32 leaves) from its 1-D segment;
        with ``shaped=False`` each leaf stays its 1-D piece of the segment
        (the compiled server step works in this flat order)."""
        span = self.spans[name]
        leaves = []
        for s in self.specs[span.group]:
            if s.entry != name:
                continue
            rel = s.offset - span.offset
            piece = segment[rel:rel + s.size]
            leaves.append(piece.reshape(s.shape) if shaped else piece)
        return jax.tree.unflatten(self.treedefs[name], leaves)

    def unflatten(self, buffers: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
        """entry name -> pytree for every entry present in ``buffers``."""
        return {name: self.unflatten_entry(name, self.entry_slice(name, buffers))
                for name, span in self.spans.items()
                if span.group in buffers}

    def signature(self) -> Tuple:
        """Structural identity: partials folded under equal signatures can be
        combined buffer-wise."""
        return tuple(sorted((name, sp.group, sp.offset, sp.size)
                            for name, sp in self.spans.items()))

    def structure(self) -> Tuple:
        """Everything a program traced over this layout depends on: the
        signature plus every leaf's shape and dtype and every entry's
        treedef.  Equal structures may share a compiled executable."""
        return (self.signature(), tuple(sorted(self.specs.items())),
                tuple(sorted(self.treedefs.items())))

    # the compiled flatten is a cache, not state: a layout that crosses a
    # real (pickling) transport re-jits on first use at the far end
    def __getstate__(self):
        state = dict(self.__dict__)
        state["_flatten_jit"] = None
        state["_flatten_batch_jit"] = None
        return state


# ---------------------------------------------------------------------------
# module-level helpers (the partial wire format)
# ---------------------------------------------------------------------------

def flatten(layout: FlatLayout, payload: Dict[str, Any]) -> Dict[str, jnp.ndarray]:
    return layout.flatten(payload)


def unflatten(layout: FlatLayout, buffers: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    return layout.unflatten(buffers)


def flat_sums(buffers: Dict[str, jnp.ndarray]) -> Dict[str, Any]:
    """The wire form of a flat partial's sums: one array per group."""
    return {"__flat__": True, "buffers": buffers}


def is_flat_sums(sums: Any) -> bool:
    return isinstance(sums, dict) and bool(sums.get("__flat__"))


def is_flat_partial(partial: Dict[str, Any]) -> bool:
    return isinstance(partial, dict) and is_flat_sums(partial.get("sums"))


def is_compressed_buffer(buf: Any) -> bool:
    """A group buffer in compressed wire form (see core/compression.py):
    ``{"__compressed__": True, "segments": [...], "size": n}`` instead of a
    dense 1-D array.  Compiled codecs ship these all the way to the fold."""
    return isinstance(buf, dict) and bool(buf.get("__compressed__"))


def to_nested_sums(partial: Dict[str, Any]) -> Dict[str, Any]:
    """Degrade a flat partial's sums to the legacy {entry: pytree} form
    (interop with hand-built nested partials)."""
    layout: Optional[FlatLayout] = partial.get("layout")
    if layout is None:
        return {}
    buffers = partial["sums"]["buffers"]
    if any(is_compressed_buffer(b) for b in buffers.values()):
        from repro.core.compression import densify_buffer
        buffers = {g: (densify_buffer(b) if is_compressed_buffer(b) else b)
                   for g, b in buffers.items()}
    return layout.unflatten(buffers)
