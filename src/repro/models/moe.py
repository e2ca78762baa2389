"""Mixture-of-Experts FFN with top-k routing and fixed expert capacity.

Two dispatch implementations, selected by ``MoEConfig.dispatch_impl``:

- ``gshard_einsum``: the classic GShard one-hot dispatch/combine einsums over
  token groups.  SPMD-safe under GSPMD partitioning at 512 devices (only
  einsums + cumsums — no data-dependent gathers), so it is the baseline used
  for the dry-run.  Its FLOP overhead is O(group * E * capacity * d) per group
  which is visible in the roofline "useful FLOPs" ratio — the perf hillclimb
  replaces it for top-1 models.
- ``gather``: index-based dispatch (argsort by expert, fixed-capacity gather /
  scatter-add).  ~E*capacity/ (k*S) times cheaper in FLOPs; used after the
  §Perf iteration validated its collective behaviour.

Experts are SwiGLU.  An auxiliary load-balancing loss (Switch-style) is
returned alongside the output.

``dropless`` is DeepSeekMoE (arXiv:2401.06066) with DeepSeek-V2's routing:
a float32 softmax router over all ``router_width`` experts, greedy top-k
weights as they come (not renormalised, scaled by 1), shared experts applied
to every token, and the routed experts this model holds (``first_held`` ..
``first_held + n_experts - 1``; one chip's share under expert parallelism)
computed for exactly the (token, expert) pairs routed to them: no token is
dropped at any imbalance, and pairs routed to experts held elsewhere add
nothing here.  The pairs are sorted by held expert into a buffer of static
size (enough at any imbalance) and run through grouped matmuls (the Pallas
megablox ``gmm`` / ``tgmm`` kernels), whose work follows the rows routed, not
the buffer.  Its balance loss is DeepSeek-V2's expert-level loss per sequence.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm as _mb_gmm
from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm as _mb_tgmm

from repro.models import layers


def moe_init(key, cfg) -> dict:
    m = cfg.moe
    if m.dispatch_impl == "dropless":
        return _dropless_init(key, cfg)
    d, f, E = cfg.d_model, cfg.d_ff, m.n_experts
    dtype = jnp.dtype(cfg.dtype)
    kr, k1, k2, k3 = jax.random.split(key, 4)
    import numpy as np
    scale = 1.0 / np.sqrt(d)
    return {
        "router": (jax.random.normal(kr, (d, E), jnp.float32) * 0.02).astype(dtype),
        "wi": (jax.random.normal(k1, (E, d, f), jnp.float32) * scale).astype(dtype),
        "wg": (jax.random.normal(k2, (E, d, f), jnp.float32) * scale).astype(dtype),
        "wo": (jax.random.normal(k3, (E, f, d), jnp.float32) / np.sqrt(f)).astype(dtype),
    }


def _routing(params, xg, m):
    """xg: (G, S, d) grouped tokens -> gating info.

    Returns (probs (G,S,E) fp32, topk_prob (G,S,k), topk_idx (G,S,k), aux_loss).
    """
    # matmul in model dtype: upcasting xg here would promote the whole
    # residual cotangent to f32 (observed: 2x backward activation memory)
    logits = (xg @ params["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # (G,S,E)
    topk_prob, topk_idx = jax.lax.top_k(probs, m.top_k)          # (G,S,k)
    # normalise combine weights over the selected experts
    topk_prob = topk_prob / jnp.maximum(
        jnp.sum(topk_prob, axis=-1, keepdims=True), 1e-9)
    # Switch-style aux loss: E * sum_e (fraction routed to e * mean prob e)
    E = probs.shape[-1]
    sel = jax.nn.one_hot(topk_idx[..., 0], E, dtype=jnp.float32)  # top-1 counts
    frac = jnp.mean(sel, axis=(0, 1))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = E * jnp.sum(frac * mean_prob)
    return probs, topk_prob, topk_idx, aux


def _expert_ffn(params, h):
    """h: (E, C, d) -> (E, C, d) via per-expert SwiGLU (grouped einsums).

    Weights pass through an explicit ZeRO gather point (constrain) so the
    contraction dims are replicated at use: forward all-gathers the weight
    shards once per layer; backward reduce-scatters the weight grads — no
    partial-sum all-reduce of the (E, C, d/f) activation buffers."""
    from repro.sharding.specs import constrain
    wi = constrain(params["wi"], "moe_weight")
    wg = constrain(params["wg"], "moe_weight")
    wo = constrain(params["wo"], "moe_weight_row")
    up = jnp.einsum("ecd,edf->ecf", h, wi)
    gate = jnp.einsum("ecd,edf->ecf", h, wg)
    act = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", act, wo)


def _moe_gshard(params, xg, m):
    """GShard einsum dispatch.  xg: (G, S, d)."""
    G, S, d = xg.shape
    E, k = m.n_experts, m.top_k
    C = max(1, int(m.capacity_factor * S * k / E))
    probs, topk_prob, topk_idx, aux = _routing(params, xg, m)

    # position of each (token, k) assignment within its expert's buffer
    onehot_e = jax.nn.one_hot(topk_idx, E, dtype=jnp.int32)      # (G,S,k,E)
    flat = onehot_e.reshape(G, S * k, E)
    pos = jnp.cumsum(flat, axis=1) - 1                           # (G,S*k,E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(G, S, k)          # (G,S,k)
    # one_hot of an out-of-range index is all-zero, so capacity overflow
    # (pos >= C) drops the token with no extra masking.
    onehot_c = jax.nn.one_hot(pos, C, dtype=xg.dtype)            # (G,S,k,C)
    oe = onehot_e.astype(xg.dtype)
    # dispatch tensor (G,S,E,C): 1 where token s fills slot (e,c)
    disp = jnp.einsum("gske,gskc->gsec", oe, onehot_c)
    comb = jnp.einsum("gsk,gske,gskc->gsec",
                      topk_prob.astype(xg.dtype), oe, onehot_c)

    h = jnp.einsum("gsec,gsd->gecd", disp, xg)                   # (G,E,C,d)
    out_e = jax.vmap(lambda hh: _expert_ffn(params, hh))(h)      # (G,E,C,d)
    out = jnp.einsum("gsec,gecd->gsd", comb, out_e)
    return out, aux


def _moe_gather(params, xg, m):
    """Index-based dispatch: argsort tokens by expert, fixed-capacity buffers.

    FLOPs: only the expert matmuls (plus O(S k log) sort) — no O(S*E*C*d)
    dispatch einsum.  Uses gather/scatter-add which GSPMD lowers with the
    tokens replicated along the model axis (validated in the dry-run).
    """
    G, S, d = xg.shape
    E, k = m.n_experts, m.top_k
    C = max(1, int(m.capacity_factor * S * k / E))
    probs, topk_prob, topk_idx, aux = _routing(params, xg, m)

    def per_group(x, idx, w):
        # x: (S,d); idx,w: (S,k)
        fi = idx.reshape(-1)                                     # (S*k,)
        fw = w.reshape(-1)
        order = jnp.argsort(fi)                                  # stable
        fi_s, fw_s = fi[order], fw[order]
        tok_s = order // k                                       # source token
        # slot within expert = rank within its expert segment
        seg_start = jnp.searchsorted(fi_s, jnp.arange(E))        # (E,)
        slot = jnp.arange(S * k) - seg_start[fi_s]
        keep = slot < C
        buf_idx = jnp.where(keep, fi_s * C + slot, E * C)        # overflow row
        buf = jnp.zeros((E * C + 1, d), x.dtype).at[buf_idx].set(x[tok_s])
        out_e = _expert_ffn(params, buf[:E * C].reshape(E, C, d))
        flat_out = out_e.reshape(E * C, d)
        gathered = jnp.where(keep[:, None],
                             flat_out[jnp.where(keep, buf_idx, 0)], 0.0)
        y = jnp.zeros((S, d), x.dtype).at[tok_s].add(
            gathered * fw_s[:, None].astype(x.dtype))
        return y

    out = jax.vmap(per_group)(xg, topk_idx, topk_prob)
    return out, aux


def moe_ffn(params, x, cfg):
    """x: (B, S, d) -> (out (B,S,d), aux_loss scalar)."""
    m = cfg.moe
    B, S, d = x.shape
    T = B * S
    gs = min(m.group_size, T)
    # group along batch-row boundaries where possible so the (B@dp, S@model)
    # sharding survives the reshape (see chunked_xent for the failure mode);
    # rows are split (S % gs == 0) or batched together (gs % S == 0)
    if S % gs == 0 or gs % S == 0:
        pad = 0
        xg = x.reshape(T // gs, gs, d)
    else:
        pad = (-T) % gs
        xf = x.reshape(T, d)
        if pad:  # pad to a whole number of groups (dropped after)
            xf = jnp.concatenate([xf, jnp.zeros((pad, d), x.dtype)])
        xg = xf.reshape((T + pad) // gs, gs, d)
    from repro.sharding.specs import constrain
    xg = constrain(xg, "moe_group")
    if m.dispatch_impl == "gather":
        out, aux = _moe_gather(params, xg, m)
    else:
        out, aux = _moe_gshard(params, xg, m)
    if pad == 0:
        return out.reshape(B, S, d), aux
    out = out.reshape(T + pad, d)[:T]
    return out.reshape(B, S, d), aux


# ---------------------------------------------------------------------------
# DeepSeekMoE: shared experts + dropless held experts
# ---------------------------------------------------------------------------

def _dropless_init(key, cfg) -> dict:
    m = cfg.moe
    d, fe, E = cfg.d_model, m.d_expert or cfg.d_ff, m.n_experts
    dtype = jnp.dtype(cfg.dtype)
    kr, k1, k2, k3, ks = jax.random.split(key, 5)

    def normal(k, shape, fan_in):
        return (jax.random.normal(k, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    p = {"router": normal(kr, (d, m.router_width), d),
         "wi": normal(k1, (E, d, fe), d),
         "wg": normal(k2, (E, d, fe), d),
         "wo": normal(k3, (E, fe, d), fe)}
    if m.n_shared:
        p["shared"] = layers.swiglu_init(ks, d, m.n_shared * fe, dtype)
    return p


def route(router, x, top_k: int):
    """Float32 softmax router over every expert and greedy top-k; the
    weights are the picked probabilities as they are.
    x: (T, d) -> (probs (T,E), weights (T,k), expert ids (T,k))."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    return probs, w, idx


def seq_balance_loss(probs, idx, B: int, E: int):
    """DeepSeek-V2's expert-level balance loss, per sequence: E/(S k) x the
    times each expert is picked, times its mean probability over the
    sequence, summed over experts and averaged over sequences."""
    T, k = idx.shape
    S = T // B
    picks = jnp.sum(jax.nn.one_hot(idx.reshape(B, S * k), E,
                                   dtype=jnp.float32), axis=1)
    ce = picks * (E / (S * k))
    return jnp.mean(jnp.sum(ce * jnp.mean(probs.reshape(B, S, E), axis=1),
                            axis=-1))


# The grouped products' tiles: rows in tiles of 256 (the buffer is whole
# tiles); the whole contraction and, in the forward products, the whole
# output width in one tile where a weight tile fits in 8 MiB of VMEM; the
# weight gradient's float32 accumulator 512 wide.  On one v5e at
# DeepSeek-V2-Lite's widths this ran the experts' forward and backward in a
# third of ``jax.lax.ragged_dot``'s time.
_ROW_TILE = 256
_TILE_BYTES = 8 << 20


def _gmm(x, w, gs, transpose_rhs=False):
    """Rows of x by their group's matrix of w (G, k, n), or of its
    transpose; rows in no group are left unwritten."""
    from repro.kernels.ops import _use_interpret
    k = x.shape[1]
    n = w.shape[1] if transpose_rhs else w.shape[2]
    size = x.dtype.itemsize
    tn = n if k * n * size <= _TILE_BYTES else 512
    tk = k if k * tn * size <= _TILE_BYTES else 512
    return _mb_gmm(x, w, gs, x.dtype, (_ROW_TILE, tk, tn),
                   transpose_rhs=transpose_rhs, interpret=_use_interpret())


def _tgmm(x, dy, gs):
    """Each group's rows of x (m, k) times its rows of dy (m, n): (G, k, n)."""
    from repro.kernels.ops import _use_interpret
    k = x.shape[1]
    tn = min(dy.shape[1], 512)
    tk = k if k * tn * 4 <= _TILE_BYTES else 512
    return _mb_tgmm(x.swapaxes(0, 1), dy, gs, x.dtype, (_ROW_TILE, tk, tn),
                    interpret=_use_interpret())


def _by_block(fn, axis_size, in_batched, x, y, gs, w_arg):
    """A batch of B grouped products as one: the B row buffers end to end,
    each client's groups its own, and one more group per client takes the
    rows past its routed ones (with a zero matrix where the matrices are an
    input, ``w_arg``).  Returns (the B stacked results, that extra group's
    index per client)."""
    x, y, gs = (a if b else jnp.broadcast_to(a, (axis_size,) + a.shape)
                for a, b in zip((x, y, gs), in_batched))
    B, R, G = axis_size, x.shape[1], gs.shape[1]
    rest = (R - jnp.sum(gs, axis=1)).astype(gs.dtype)
    gs = jnp.concatenate([gs, rest[:, None]], axis=1).reshape(-1)
    if w_arg is not None:
        y = jnp.concatenate(
            [y, jnp.zeros((B, 1) + y.shape[2:], y.dtype)], axis=1)
        y = y.reshape((B * (G + 1),) + y.shape[2:])
    else:
        y = y.reshape((B * R,) + y.shape[2:])
    return fn(x.reshape((B * R,) + x.shape[2:]), y, gs), B, G


@jax.custom_batching.custom_vmap
def _rows_by_w(x, w, gs):
    return _gmm(x, w, gs)


@_rows_by_w.def_vmap
def _rows_by_w_vmap(axis_size, in_batched, x, w, gs):
    out, B, _ = _by_block(_gmm, axis_size, in_batched, x, w, gs, w_arg=1)
    return out.reshape((B, -1) + out.shape[1:]), True


@jax.custom_batching.custom_vmap
def _rows_by_wt(dy, w, gs):
    return _gmm(dy, w, gs, transpose_rhs=True)


@_rows_by_wt.def_vmap
def _rows_by_wt_vmap(axis_size, in_batched, dy, w, gs):
    out, B, _ = _by_block(
        lambda a, b, g: _gmm(a, b, g, transpose_rhs=True),
        axis_size, in_batched, dy, w, gs, w_arg=1)
    return out.reshape((B, -1) + out.shape[1:]), True


@jax.custom_batching.custom_vmap
def _per_group(x, dy, gs):
    return _tgmm(x, dy, gs)


@_per_group.def_vmap
def _per_group_vmap(axis_size, in_batched, x, dy, gs):
    out, B, G = _by_block(_tgmm, axis_size, in_batched, x, dy, gs,
                          w_arg=None)
    return out.reshape((B, G + 1) + out.shape[1:])[:, :G], True


def _routed(y, group_sizes):
    """y with the rows past the groups' sizes, which no group computes,
    set to zero (selected, never multiplied)."""
    real = jnp.arange(y.shape[0]) < jnp.sum(group_sizes)
    return jnp.where(real[:, None], y, jnp.zeros((), y.dtype))


@jax.custom_vjp
def _grouped(x, w, group_sizes):
    """Rows of x (sorted by group) times their group's matrix of w; rows
    past the groups' sizes are zero.  The gradient and the vmap of the
    client block are written out, as grouped products of the same kind."""
    return _routed(_rows_by_w(x, w, group_sizes), group_sizes)


def _grouped_fwd(x, w, group_sizes):
    return _grouped(x, w, group_sizes), (x, w, group_sizes)


def _grouped_bwd(res, dy):
    x, w, group_sizes = res
    dy = dy.astype(x.dtype)
    return (_routed(_rows_by_wt(dy, w, group_sizes), group_sizes),
            _per_group(x, dy, group_sizes).astype(w.dtype), None)


_grouped.defvjp(_grouped_fwd, _grouped_bwd)


@jax.custom_vjp
def _permute(a, perm, inv):
    """Rows of ``a`` in the order ``perm``, a zero row where ``perm`` points
    past ``a``; ``inv`` maps each row of ``a`` to its place in the result the
    same way.  The gradient moves rows back by ``inv``: a gather, where a
    plain gather's gradient would be a scatter-add."""
    return jnp.take(a, perm, axis=0, mode="fill", fill_value=0)


def _permute_fwd(a, perm, inv):
    return _permute(a, perm, inv), (perm, inv)


def _permute_bwd(res, g):
    perm, inv = res
    return jnp.take(g, inv, axis=0, mode="fill", fill_value=0), None, None


_permute.defvjp(_permute_fwd, _permute_bwd)


def dropless_moe(params, x, cfg):
    """x: (B, S, d) -> (out (B,S,d), balance loss, rows per held expert
    (held,) int32)."""
    m = cfg.moe
    B, S, d = x.shape
    T, k, held, E = B * S, m.top_k, m.held, m.router_width
    xf = x.reshape(T, d)
    probs, w, idx = route(params["router"], xf, k)
    aux = seq_balance_loss(probs, idx, B, E)

    # (token, pick) pairs sorted by held expert; the sentinel `held` sorts
    # pairs routed elsewhere last.  One zero row closes each held expert's
    # group (Z rows past the P pairs, sorted in with them), so no group is
    # ever empty: the grouped matmuls skip an empty group, which would make
    # a step's time depend on how many experts the router leaves out, not
    # only on the rows routed.  The first R sorted rows are the buffer, in
    # whole row tiles, enough for every held pair at any imbalance (a token
    # routes to at most min(k, held) held experts) and the closing rows.
    # Tokens go out and results come back by gathers: a row past the pairs,
    # or past the buffer, is a zero row.  The grouped matmuls compute the
    # groups' rows and give zero rows past them.
    loc = idx - m.first_held
    key = jnp.where((loc >= 0) & (loc < held), loc, held).reshape(-1)
    rows = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0,
                   dtype=jnp.int32)
    P = T * k
    R = -(-(T * min(k, held) + held) // _ROW_TILE) * _ROW_TILE
    Z = max(held, R - P)
    key = jnp.concatenate([key, jnp.minimum(jnp.arange(Z, dtype=key.dtype),
                                            held)])
    order = jnp.argsort(key, stable=True)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(P + Z, dtype=order.dtype))
    xs = _permute(jnp.repeat(xf, k, axis=0), order[:R], inv[:P])
    gs = rows + 1
    h = jax.nn.silu(_grouped(xs, params["wg"], gs)) \
        * _grouped(xs, params["wi"], gs)
    y = _grouped(h, params["wo"], gs)
    y = _permute(y, inv[:P], order[:R]).reshape(T, k, d)  # (token, pick)
    # picks of experts held elsewhere come back as zero rows
    out = jnp.einsum("tk,tkd->td", w, y.astype(jnp.float32)).astype(x.dtype)
    if m.n_shared:
        out = out + layers.swiglu(params["shared"], xf)
    return out.reshape(B, S, d), aux, rows
