"""Fused error-feedback top-k sparsification (DESIGN.md §7).

One traced function performs the whole error-feedback cycle for a 1-D
segment, and the group codecs in ``core/compression.py`` jit it into one
dispatch per group buffer:

    residual-add -> |.| top-k select -> gather values -> scatter-zero residual

Selection semantics (the documented tie rule for the whole compression
stack): the k entries with the largest ``|x + residual|`` win; on exact
magnitude ties the LOWER index wins (``jax.lax.top_k``'s stability
guarantee).  Emitted indices are sorted ascending so the wire format is
canonical.

This is XLA's ``top_k`` on every backend: the TPU compiler has no Pallas
lowering for ``top_k``, sort, gather or scatter inside a kernel body.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def topk_with_residual(x: Array, res: Array, k: int
                       ) -> Tuple[Array, Array, Array]:
    """Returns ``(idx, vals, new_residual)`` for ``f = x + res`` in f32."""
    f = jnp.asarray(x, jnp.float32) + jnp.asarray(res, jnp.float32)
    _, top = jax.lax.top_k(jnp.abs(f), k)
    idx = jnp.sort(top).astype(jnp.int32)
    vals = jnp.take(f, idx)
    # idx is unique by construction (top_k indices): the hint lets XLA skip
    # the duplicate-index combine path in the scatter
    new_res = f.at[idx].set(0.0, unique_indices=True)
    return idx, vals, new_res
