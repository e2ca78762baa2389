"""Decoder stack: block composition over heterogeneous block kinds.

Layers are grouped into a repeating *unit* (e.g. ``("dense",)`` for
transformers, ``("mlstm", "slstm")`` for xLSTM) and the stack is evaluated as
``lax.scan`` over ``n_layers / len(unit)`` repetitions with stacked params —
this keeps HLO size and compile time flat in depth (MaxText-style) and is what
makes 64-layer dry-runs tractable.  ``cfg.remat`` wraps each unit in
``jax.checkpoint`` so only unit-boundary activations are saved.

Block kinds:
  dense   — RMSNorm → GQA attention → residual → RMSNorm → SwiGLU/MoE → residual
  lead    — a dense block with a SwiGLU of d_ff in an MoE model: the
            ``first_dense_layers`` blocks that run before the scanned stack,
            stacked under a params key of their own (``lead=True``)
  hybrid  — parallel attention + mamba(SSD) heads fused by averaging (Hymba)
  mlstm   — RMSNorm → mLSTM mixer → residual (xLSTM, no FFN)
  slstm   — RMSNorm → sLSTM mixer → residual
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention, layers, moe, ssm
from repro.sharding.specs import constrain


def unit_pattern(cfg) -> Tuple[str, ...]:
    if cfg.family == "ssm":
        pat = tuple((cfg.xlstm.pattern if cfg.xlstm else ("mlstm", "slstm")))
        return pat
    if cfg.family == "hybrid":
        return ("hybrid",)
    return ("dense",)


def n_rep(cfg) -> int:
    pat = unit_pattern(cfg)
    n = cfg.n_layers - cfg.first_dense_layers
    assert n % len(pat) == 0
    return n // len(pat)


def _pattern(cfg, lead: bool) -> Tuple[Tuple[str, ...], int]:
    """(unit pattern, repetitions) of the scanned stack, or of the leading
    dense blocks."""
    if lead:
        return ("lead",), cfg.first_dense_layers
    return unit_pattern(cfg), n_rep(cfg)


# ---------------------------------------------------------------------------
# single block
# ---------------------------------------------------------------------------

def block_init(key, cfg, kind: str) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    d = cfg.d_model
    ks = jax.random.split(key, 4)
    p = {"norm1": layers.rmsnorm_init(d, dtype)}
    if kind in ("dense", "hybrid", "lead"):
        p["attn"] = attention.attn_init(ks[0], cfg)
        if kind == "hybrid":
            p["mamba"] = ssm.mamba_init(ks[1], cfg)
        if cfg.d_ff > 0:
            p["norm2"] = layers.rmsnorm_init(d, dtype)
            if cfg.moe is not None and kind == "dense":
                p["ffn"] = moe.moe_init(ks[2], cfg)
            else:
                p["ffn"] = layers.swiglu_init(ks[2], d, cfg.d_ff, dtype)
    elif kind == "mlstm":
        p["mixer"] = ssm.mlstm_init(ks[0], cfg)
    elif kind == "slstm":
        p["mixer"] = ssm.slstm_init(ks[0], cfg)
    else:
        raise ValueError(kind)
    return p


def block_cache(cfg, kind: str, batch: int, seq_len: int, dtype) -> dict:
    """Decode cache/state pytree for one block."""
    c = {}
    if kind in ("dense", "hybrid", "lead"):
        c["attn"] = attention.init_cache(cfg, batch, seq_len, dtype)
    if kind == "hybrid":
        c["mamba"] = ssm.mamba_init_state(cfg, batch, dtype)
    if kind == "mlstm":
        c["mixer"] = ssm.mlstm_init_state(cfg, batch, dtype)
    if kind == "slstm":
        c["mixer"] = ssm.slstm_init_state(cfg, batch, dtype)
    return c


def block_apply(p, x, cfg, kind: str, *, positions, cache=None,
                cache_index=None, decode: bool = False):
    """Returns (x_out, new_cache, aux, stats); ``stats`` holds a dropless
    MoE block's rows per held expert (``moe_expert_rows``), else nothing."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = {}
    stats = {}
    if kind in ("dense", "hybrid", "lead"):
        # each sublayer's scope holds its norm, so the profiler's op names
        # split a block's device time between them
        with jax.named_scope("attn"):
            h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
            attn_cache = cache.get("attn") if cache else None
            a_out, new_attn = attention.attention(
                p["attn"], h, cfg, positions=positions, cache=attn_cache,
                cache_index=cache_index)
        if kind == "hybrid":
            with jax.named_scope("ssm"):
                if decode:
                    m_out, new_m = ssm.mamba_step(p["mamba"], h,
                                                  cache["mamba"], cfg)
                    new_cache["mamba"] = new_m
                else:
                    m_out, (conv_st, h_st) = ssm.mamba_apply(p["mamba"], h,
                                                             cfg)
                    if cache is not None:
                        # prefill: seed the decode state from the scan tail
                        new_cache["mamba"] = {"conv": _conv_tail(p, h, cfg),
                                              "h": h_st}
            mixed = (a_out + m_out) * 0.5
        else:
            mixed = a_out
        if new_attn is not None:
            new_cache["attn"] = new_attn
        x = x + mixed
        if cfg.d_ff > 0:
            sparse = cfg.moe is not None and kind == "dense"
            with jax.named_scope("moe" if sparse else "mlp"):
                h2 = layers.rmsnorm(p["norm2"], x, cfg.norm_eps)
                if sparse and cfg.moe.dispatch_impl == "dropless":
                    f_out, aux, rows = moe.dropless_moe(p["ffn"], h2, cfg)
                    stats["moe_expert_rows"] = rows
                elif sparse:
                    f_out, aux = moe.moe_ffn(p["ffn"], h2, cfg)
                else:
                    f_out = layers.swiglu(p["ffn"], h2)
                x = x + f_out
    elif kind == "mlstm":
        with jax.named_scope("ssm"):
            h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
            if decode:
                m_out, st = ssm.mlstm_step(p["mixer"], h, cache["mixer"], cfg)
                new_cache["mixer"] = st
            else:
                m_out, h_final = ssm.mlstm_apply(p["mixer"], h, cfg)
                if cache is not None:
                    new_cache["mixer"] = {"h": h_final}
            x = x + m_out
    elif kind == "slstm":
        with jax.named_scope("ssm"):
            h = layers.rmsnorm(p["norm1"], x, cfg.norm_eps)
            if decode:
                m_out, st = ssm.slstm_step(p["mixer"], h, cache["mixer"], cfg)
                new_cache["mixer"] = st
            else:
                m_out, st = ssm.slstm_apply(p["mixer"], h, cfg)
                if cache is not None:
                    new_cache["mixer"] = st
            x = x + m_out
    return x, new_cache, aux, stats


def _conv_tail(p, h, cfg):
    """Streaming conv state after a prefill pass: last (K-1) pre-conv inputs.

    The mamba conv operates on the in_proj output, so recompute that tail."""
    u = layers.dense(p["mamba"]["in_proj"], h[:, -(cfg.ssm.d_conv - 1):, :])
    xs, _ = jnp.split(u, 2, axis=-1)
    return xs


# ---------------------------------------------------------------------------
# stack
# ---------------------------------------------------------------------------

def stack_init(key, cfg, lead: bool = False) -> Tuple[dict, ...]:
    pat, reps = _pattern(cfg, lead)
    out = []
    for i, kind in enumerate(pat):
        keys = jax.random.split(jax.random.fold_in(key, i), reps)
        out.append(jax.vmap(lambda k: block_init(k, cfg, kind))(keys))
    return tuple(out)


def stack_cache(cfg, batch: int, seq_len: int, dtype, lead: bool = False):
    pat, reps = _pattern(cfg, lead)
    out = []
    for kind in pat:
        c = block_cache(cfg, kind, batch, seq_len, dtype)
        out.append(jax.tree.map(
            lambda a: jnp.tile(a[None], (reps,) + (1,) * a.ndim), c))
    return tuple(out)


def model_cache(cfg, batch: int, seq_len: int, dtype):
    """Decode caches of the whole model: the stack's, and with leading dense
    blocks ``{"lead": ..., "stack": ...}``."""
    c = stack_cache(cfg, batch, seq_len, dtype)
    if not cfg.first_dense_layers:
        return c
    return {"lead": stack_cache(cfg, batch, seq_len, dtype, lead=True),
            "stack": c}


def stack_apply(params, x, cfg, *, positions, caches=None, cache_index=None,
                decode: bool = False, lead: bool = False,
                with_stats: bool = False):
    """params/caches: tuple over pattern positions of stacked pytrees (the
    leading dense blocks' with ``lead``).

    Returns (x, new_caches, aux_total), and with ``with_stats`` the blocks'
    stats too: a tuple over pattern positions, each leaf stacked over the
    repetitions.
    """
    pat, reps = _pattern(cfg, lead)
    has_cache = caches is not None

    def unit(x, unit_params, unit_caches):
        x = constrain(x, "residual")
        aux = jnp.zeros((), jnp.float32)
        new_caches, stats = [], []
        for i, kind in enumerate(pat):
            c = unit_caches[i] if has_cache else None
            x, nc, a, st = block_apply(unit_params[i], x, cfg, kind,
                                       positions=positions, cache=c,
                                       cache_index=cache_index, decode=decode)
            new_caches.append(nc)
            stats.append(st)
            aux = aux + a
        return x, tuple(new_caches), aux, tuple(stats)

    if cfg.remat:
        unit = jax.checkpoint(unit)

    if not cfg.scan_layers:
        aux_tot = jnp.zeros((), jnp.float32)
        new_all, st_all = [], []
        for r in range(reps):
            up = jax.tree.map(lambda a: a[r], params)
            uc = jax.tree.map(lambda a: a[r], caches) if has_cache else None
            x, nc, a, st = unit(x, up, uc)
            new_all.append(nc)
            st_all.append(st)
            aux_tot = aux_tot + a
        new_caches = (jax.tree.map(lambda *xs: jnp.stack(xs), *new_all)
                      if has_cache else None)
        stats = jax.tree.map(lambda *xs: jnp.stack(xs), *st_all)
    else:
        def body(carry, xs):
            x, aux_tot = carry
            if has_cache:
                up, uc = xs
            else:
                up, uc = xs, None
            x, nc, a, st = unit(x, up, uc)
            return (x, aux_tot + a), (nc if has_cache else None, st)

        xs = (params, caches) if has_cache else params
        (x, aux_tot), (new_caches, stats) = jax.lax.scan(
            body, (x, jnp.zeros((), jnp.float32)), xs)
    if with_stats:
        return x, new_caches, aux_tot, stats
    return x, new_caches, aux_tot
