"""Hierarchical-aggregation fold kernel: ``acc += Σ_c w_c · delta_c``.

This is Parrot's memory-bound hot loop (LocalAggregate folds every simulated
client's multi-hundred-MB delta into the fp32 partial).  Arithmetic intensity
is ~0.5 FLOP/byte, so the kernel's job is purely to stream HBM→VMEM at line
rate with the multiply-add fused on the VPU — one pass over the deltas, fp32
accumulation regardless of delta dtype (bf16 deltas halve the bytes moved,
which is the §Perf lever for the aggregation benchmark).

The C axis is the multi-client micro-batch: ``LocalAggregator`` flattens each
client's whole reducible payload into ONE contiguous (n,) buffer (see
``core.flat.FlatLayout``), stages B of them, and issues a single C=B call —
amortising dispatch overhead over B clients x all leaves instead of paying it
per leaf per client.  B is static via the (C, n) shape, so a fixed micro-batch
compiles exactly one kernel specialisation per layout.

Tiling: 1-D grid over ceil(n/BLK) element blocks; the (C, BLK) delta tile and
the (BLK,) accumulator tile live in VMEM, the C weights in SMEM as scalars.
The fold is a static loop of C scalar-times-row multiply-adds on the VPU (a
rank-1 ``dot_general`` does not lower for the TPU).  A ragged last block is
masked by Pallas, so the input is never padded or sliced, and on the
compiled (non-interpret) path the accumulator aliases the output
(``input_output_aliases``) so the fold updates it in place.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _agg_kernel(w_ref, acc_ref, delta_ref, o_ref):
    out = acc_ref[...]                                  # (blk,) f32
    for c in range(delta_ref.shape[0]):
        out = out + w_ref[c] * delta_ref[c, :].astype(jnp.float32)
    o_ref[...] = out


def _auto_blk(n: int, C: int, delta_itemsize: int, interpret: bool) -> int:
    """Pick the element-block size.  Interpret mode (CPU validation) has no
    VMEM: one grid step over the whole buffer minimises the per-step
    interpreter overhead.  Compiled TPU fits a ~8MB VMEM budget: the
    double-buffered (C, blk) delta tile with C padded to the dtype's
    sublane tile (8 rows of 32-bit, 16 of 16-bit), the double-buffered f32
    acc/out tiles and the kernel's f32 temporaries; rounded down to the
    1024-element tile of a 1-D f32 array."""
    if interpret:
        return n
    sub = 8 * 4 // delta_itemsize
    rows = -(-C // sub) * sub
    per_elem = 2 * rows * delta_itemsize + 2 * 4 + 2 * 4 + 3 * 4
    blk = (8 * 1024 * 1024 // per_elem) // 1024 * 1024
    return max(1024, blk)


def agg_weighted_sum(acc, deltas, weights, *, blk: int = 0,
                     interpret: bool = True):
    """acc: (n,) fp32; deltas: (C, n); weights: (C,) -> (n,) fp32.

    ``blk=0`` auto-sizes the block (see ``_auto_blk``); pass an explicit
    ``blk`` to pin the tiling (tests sweep it)."""
    (n,) = acc.shape
    C = deltas.shape[0]
    if not blk:
        blk = _auto_blk(n, C, deltas.dtype.itemsize, interpret)
    blk = min(blk, n)
    return pl.pallas_call(
        _agg_kernel,
        grid=(pl.cdiv(n, blk),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((blk,), lambda i: (i,)),
            pl.BlockSpec((C, blk), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.float32),
        input_output_aliases={} if interpret else {1: 0},
        interpret=interpret,
    )(jnp.asarray(weights, jnp.float32), acc, deltas)
