"""Plain reference of the timed path: FedAvg rounds of a configuration's
model, written from the published description and nothing of the program.
The model is the configuration's family's (``families/<family>.py``):
its ``loss`` is built from the primitives here, every matmul at
``Precision.HIGHEST`` in float32.

The round: each sampled client runs plain SGD over its batches from the
round's parameters, keeping its weights in the storage dtype the
configuration states (``w <- dtype(w - lr * g)``, the gradient taken in
float32 at the stored weights); the server averages the clients' updates
weighted by their sample counts in float32 and adds the average to its
parameters in the storage dtype.  With top-k compression the executor's
weighted update sum plus its error-feedback residual is cut to its k
largest magnitudes before the average.

``storage`` and ``operands`` make the control: the same reference with the
weights stored and the matmul operands rounded to a lower precision.
``fault`` plants one of the faults the benchmark must catch.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def mm(spec, a, b, operands):
    """``einsum`` in float32 at HIGHEST; the control's ``operands`` dtype
    rounds both inputs first (None: no rounding)."""
    if operands is not None:
        a = a.astype(operands).astype(F32)
        b = b.astype(operands).astype(F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def rmsnorm(x, g, eps):
    """RMSNorm over the last axis, scaled by ``g``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def sgd_step_fn(loss, lr: float, storage, operands=None):
    """``w -> storage(w - lr * grad)`` for one batch, jitted; ``loss(params,
    inputs, labels, operands)`` is the family's loss at the configuration's
    sizes."""

    def step(w, inputs, labels):
        w32 = jax.tree.map(lambda a: a.astype(F32), w)
        g = jax.grad(lambda p: loss(p, inputs, labels, operands))(w32)
        return jax.tree.map(lambda a, b: (a - lr * b).astype(storage),
                            w32, g)

    return jax.jit(step)


def _axpy(acc, w, p, scale):
    """acc + scale * (w - p), in float32."""
    return jax.tree.map(
        lambda a, x, y: a + scale * (x.astype(F32) - y.astype(F32)),
        acc, w, p)


def _apply(p, acc, wtot, storage):
    return jax.tree.map(lambda x, a: (x.astype(F32) + a / wtot)
                        .astype(storage), p, acc)


def _count_at_least(f, t):
    """How many |f| have a float32 bit pattern >= t (non-negative floats
    order as their bit patterns)."""
    bits = jax.lax.bitcast_convert_type(jnp.abs(f), jnp.int32)
    return jnp.sum((bits >= t).astype(jnp.int32))


@functools.partial(jax.jit, static_argnums=2)
def _select_topk(f, res, k):
    """The k largest |f + res| (ties to the lower index), found by bisection
    on the bit pattern of the k-th magnitude and then on the index of the
    last tie kept.  Returns (kept values, zeros elsewhere; new residual)."""
    f = f + res
    n = f.shape[0]

    def by_value(_, lohi):
        lo, hi = lohi
        mid = lo + (hi - lo) // 2
        ok = _count_at_least(f, mid) >= k
        return jnp.where(ok, mid, lo), jnp.where(ok, hi, mid)

    t, _ = jax.lax.fori_loop(0, 32, by_value,
                             (jnp.int32(0), jnp.int32(0x7F800001)))
    bits = jax.lax.bitcast_convert_type(jnp.abs(f), jnp.int32)
    need = k - jnp.sum((bits > t).astype(jnp.int32))
    idx = jnp.arange(n, dtype=jnp.int32)

    def by_index(_, lohi):
        lo, hi = lohi            # count(ties below lo) < need <= below hi
        mid = lo + (hi - lo) // 2
        below = jnp.sum(((bits == t) & (idx < mid)).astype(jnp.int32))
        ok = below >= need
        return jnp.where(ok, lo, mid), jnp.where(ok, mid, hi)

    _, last = jax.lax.fori_loop(0, 32, by_index,
                                (jnp.int32(0), jnp.int32(n)))
    sel = (bits > t) | ((bits == t) & (idx < last))
    return jnp.where(sel, f, 0.0), jnp.where(sel, 0.0, f)


def _topk(acc, res, fraction):
    """Top-k of the weighted update sum plus the residual, over all leaves
    in leaf order, and the new residual: what was not sent."""
    leaves, treedef = jax.tree.flatten(acc)
    shapes = [a.shape for a in leaves]
    f = jnp.concatenate([a.reshape(-1) for a in leaves])
    del leaves, acc
    if res is None:
        res = jnp.zeros_like(f)
    kept, res = _select_topk(f, res, max(1, int(f.shape[0] * fraction)))
    del f
    out, off = [], 0
    for shape in shapes:
        size = int(np.prod(shape))
        out.append(kept[off:off + size].reshape(shape))
        off += size
    return jax.tree.unflatten(treedef, out), res


def run_rounds(loss, params0, data: dict, cohorts: list, lr: float,
               samples: dict, *, storage=None, operands=None,
               topk: Optional[float] = None, fault: Optional[str] = None,
               keep=()):
    """FedAvg rounds of ``loss`` (``sgd_step_fn``'s) from ``params0`` over
    ``cohorts`` (one client list per round); ``data`` maps client -> list
    of (inputs, labels) device batches and ``samples`` client -> sample
    count.  Returns {round: params} for the rounds in ``keep`` (1-based).

    ``fault``: ``"half"`` folds only the first half of each cohort and
    averages over it; ``"negate"`` sends the first client's update with its
    sign flipped."""
    if storage is None:
        storage = jax.tree.leaves(params0)[0].dtype
    step = sgd_step_fn(loss, lr, storage, operands)
    apply = jax.jit(_apply, static_argnums=(3,))
    # the running sum is model-sized in float32: update it in place where
    # the backend can
    axpy = jax.jit(_axpy, donate_argnums=() if jax.default_backend() == "cpu"
                   else (0,))
    p = jax.tree.map(lambda a: a.astype(storage), params0)
    res, out = None, {}
    for r, cohort in enumerate(cohorts, start=1):
        if fault == "half":
            cohort = cohort[:max(1, len(cohort) // 2)]
        acc = jax.tree.map(lambda a: jnp.zeros(a.shape, F32), p)
        wtot = 0.0
        for i, c in enumerate(cohort):
            w = p
            for inputs, labels in data[c]:
                w = step(w, inputs, labels)
            n = float(samples[c])
            sign = -1.0 if (fault == "negate" and i == 0) else 1.0
            acc = axpy(acc, w, p, jnp.float32(sign * n))
            wtot += n
            del w
        if topk is not None:
            acc, res = _topk(acc, res, topk)
        p = apply(p, acc, jnp.float32(wtot), jnp.dtype(storage))
        del acc
        if r in keep:
            out[r] = p
    return out
