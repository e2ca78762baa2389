"""Plain reference of the timed path: FedAvg rounds of a dense decoder LM,
written from the configuration's published description and nothing of the
program.

The model: token embedding, then per layer RMSNorm -> causal GQA attention
with half-split RoPE (and q/k/v biases where the configuration has them)
-> residual -> RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm, and the
LM head (the embedding, transposed, when tied).  The loss is the mean token
cross-entropy.  Every matmul runs at ``Precision.HIGHEST`` in float32.

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

from perfbench.modelcfg import Dims

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST


def _mm(spec, a, b, operands):
    if operands is not None:
        a = a.astype(operands).astype(F32)
        b = b.astype(operands).astype(F32)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, S, N, hd); rotates the two halves of the head dimension."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = jnp.arange(S, dtype=F32)[:, None] * inv           # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(m: Dims, operands, x, p):
    B, S, _ = x.shape
    h = _rmsnorm(x, p["norm1"]["g"], m.eps)
    a = p["attn"]

    def proj(w):
        y = _mm("bsd,dnk->bsnk", h, w["w"], operands)
        return y + w["b"] if "b" in w else y

    q, k, v = proj(a["wq"]), proj(a["wk"]), proj(a["wv"])
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    groups = m.heads // m.kv_heads
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    s = _mm("bqnk,bsnk->bnqs", q, k, operands) / np.sqrt(m.head_dim)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    allowed = ki <= qi
    if m.window:
        allowed &= ki > qi - m.window
    s = jnp.where(allowed, s, -jnp.inf)
    o = _mm("bnqs,bsnk->bqnk", jax.nn.softmax(s, axis=-1), v, operands)
    x = x + _mm("bqnk,nkd->bqd", o, a["wo"]["w"], operands)
    h = _rmsnorm(x, p["norm2"]["g"], m.eps)
    f = p["ffn"]
    u = jax.nn.silu(_mm("bsd,df->bsf", h, f["wg"]["w"], operands)) \
        * _mm("bsd,df->bsf", h, f["wi"]["w"], operands)
    return x + _mm("bsf,fd->bsd", u, f["wo"]["w"], operands)


def loss(m: Dims, params, inputs, labels, operands=None):
    """Mean token cross-entropy; ``params`` in float32.  Layers are
    recomputed in the backward pass (``jax.checkpoint``) so the reference
    fits beside nothing else on one chip."""
    x = params["embed"]["w"][inputs]
    layer = jax.checkpoint(lambda x, p: _layer(m, operands, x, p))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                        params["blocks"][0])
    x = _rmsnorm(x, params["final_norm"]["g"], m.eps)
    head = (params["embed"]["w"].T if m.tied else params["lm_head"]["w"])
    logits = _mm("bsd,dv->bsv", x, head, operands)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)


def sgd_step_fn(m: Dims, lr: float, storage, operands=None):
    """``w -> storage(w - lr * grad)`` for one batch, jitted."""

    def step(w, inputs, labels):
        w32 = jax.tree.map(lambda a: a.astype(F32), w)
        g = jax.grad(lambda p: loss(m, p, inputs, labels, operands))(w32)
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


def run_rounds(m: Dims, params0, data: dict, cohorts: list, lr: float,
               samples: dict, *, storage=None, operands=None,
               topk: Optional[float] = None, fault: Optional[str] = None,
               keep=()):
    """FedAvg rounds from ``params0`` over ``cohorts`` (one client list per
    round); ``data`` maps client -> list of (inputs, labels) device batches
    and ``samples`` client -> sample count.  Returns {round: params} for
    the rounds in ``keep`` (1-based).

    ``fault``: ``"half"`` folds only the first half of each cohort and
    averages over it; ``"negate"`` sends the first client's update with its
    sign flipped."""
    storage = params0["embed"]["w"].dtype if storage is None else storage
    step = sgd_step_fn(m, lr, storage, operands)
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
