"""Attention: GQA with dense / chunked-online-softmax (flash-style) impls.

The ``chunked`` implementation is the pure-jnp expression of the same
online-softmax algorithm as the Pallas flash kernel (``kernels/flash_attention``)
— it is both the memory-efficient path used when lowering the dry-run and the
oracle against which the kernel is validated.

KV caches are ring buffers: ``{"k": (B,Smax,KV,hd), "v": ..., "pos": (Smax,)}``
where ``pos[s]`` is the absolute position stored in slot ``s`` (-1 = empty).
For full-attention archs Smax == seq_len and the ring never wraps; for
sliding-window archs Smax == window and old entries are overwritten — this is
what makes ``long_500k`` decode O(window) instead of O(context).

Multi-head latent attention (``cfg.mla``, DeepSeek-V2) runs in the expanded
form: keys and values are up-projected from the latent and the heads go
through the same attention as GQA, with a value head narrower than the
query's.  Its cache holds the normed latent and the roped key per position
(``{"ckv": (B,Smax,r), "kpe": (B,Smax,rope), "pos"}``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers
from repro.sharding.specs import constrain, tp_padded_heads

NEG_INF = -1e30


def attn_init(key, cfg) -> dict:
    """Projection weights keep an explicit head axis — (d, H, hd) — so the
    head dim is shardable over the "model" mesh axis even when H is not a
    multiple of it (GSPMD pad-shards), with no reshape to break propagation."""
    if cfg.mla is not None:
        return mla_init(key, cfg)
    d, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    dtype = jnp.dtype(cfg.dtype)
    kq, kk, kv, ko = jax.random.split(key, 4)
    import numpy as np
    scale = 1.0 / np.sqrt(d)

    def proj(k, n_heads):
        p = {"w": (jax.random.normal(k, (d, n_heads, hd), jnp.float32)
                   * scale).astype(dtype)}
        if cfg.qkv_bias:
            p["b"] = jnp.zeros((n_heads, hd), dtype)
        return p

    return {
        "wq": proj(kq, H),
        "wk": proj(kk, KV),
        "wv": proj(kv, KV),
        "wo": {"w": (jax.random.normal(ko, (H, hd, d), jnp.float32)
                     / np.sqrt(H * hd)).astype(dtype)},
    }


def _proj_heads(p, x):
    """x: (B,S,d) @ (d,Hn,hd) -> (B,S,Hn,hd)."""
    y = jnp.einsum("bsd,dhk->bshk", x, p["w"])
    if "b" in p:
        y = y + p["b"]
    return y


def init_cache(cfg, batch: int, seq_len: int, dtype) -> dict:
    smax = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    if cfg.mla is not None:
        a = cfg.mla
        return {"ckv": jnp.zeros((batch, smax, a.kv_lora_rank), dtype),
                "kpe": jnp.zeros((batch, smax, a.qk_rope_head_dim), dtype),
                "pos": jnp.full((smax,), -1, jnp.int32)}
    KV, hd = cfg.n_kv_heads, cfg.hd
    return {
        "k": jnp.zeros((batch, smax, KV, hd), dtype),
        "v": jnp.zeros((batch, smax, KV, hd), dtype),
        "pos": jnp.full((smax,), -1, jnp.int32),
    }


def _split_heads(x, n):
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


def _repeat_kv(k: jnp.ndarray, groups: int) -> jnp.ndarray:
    """(B, S, KV, hd) -> (B, S, KV*groups, hd)."""
    if groups == 1:
        return k
    return jnp.repeat(k, groups, axis=2)


def dense_attention(q, k, v, *, causal: bool, window: int = 0,
                    q_offset: int = 0, scale=None) -> jnp.ndarray:
    """Reference attention.  q, k: (B,Sq|Skv,H,hd); v: (B,Skv,H,hd_v).
    ``scale`` defaults to hd**-0.5."""
    B, Sq, H, hd = q.shape
    Skv = k.shape[1]
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    qpos = jnp.arange(Sq) + q_offset
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > (qpos[:, None] - window)
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
    return out


def chunked_attention(q, k, v, *, causal: bool, window: int = 0,
                      chunk: int = 512, q_offset: int = 0,
                      scale=None) -> jnp.ndarray:
    """Online-softmax attention scanning over KV chunks (flash-style).

    Never materialises the (Sq, Skv) score matrix; peak transient is
    (B, H, Sq, chunk).  Matches ``dense_attention`` to fp32 accuracy.
    The value head (``v``'s last axis) may differ from the query's;
    ``scale`` defaults to hd**-0.5.
    """
    B, Sq, H, hd = q.shape
    hv = v.shape[-1]
    Skv = k.shape[1]
    if Skv % chunk:
        chunk = Skv  # degenerate fallback for tiny shapes
    n_chunks = Skv // chunk
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(hd))
    qf = q.astype(jnp.float32) * scale
    qpos = (jnp.arange(Sq) + q_offset)[:, None]          # (Sq, 1)

    kc = k.reshape(B, n_chunks, chunk, H, hd)
    vc = v.reshape(B, n_chunks, chunk, H, hv)

    def body(carry, inp):
        m, l, acc = carry                                # (B,H,Sq), (B,H,Sq), (B,Sq,H,hv)
        kb, vb, idx = inp                                # (B,chunk,H,hd)
        kpos = idx * chunk + jnp.arange(chunk)[None, :]  # (1, chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        # additive f32 mask of shape (Sq, chunk) only — a broadcast boolean
        # where() tempts XLA into hoisting a stacked (n_chunks,B,H,Sq,chunk)
        # predicate out of the scan (observed on the dry-run: 469 MB/device)
        mask = jnp.ones((Sq, chunk), bool)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > (qpos - window)
        s = s + jnp.where(mask, 0.0, NEG_INF)[None, None]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])                # (B,H,Sq,chunk)
        corr = jnp.exp(m - m_new)                        # (B,H,Sq)
        l_new = l * corr + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, vb.astype(jnp.float32))
        acc_new = acc * corr.transpose(0, 2, 1)[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, H, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    acc0 = jnp.zeros((B, Sq, H, hv), jnp.float32)
    idxs = jnp.arange(n_chunks)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), idxs))
    l = jnp.maximum(l, 1e-30)
    out = acc / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def _cache_attend(q, kk, vv, kpos, qpos, window: int = 0, scale=None):
    """Attend new-token queries over the ring buffer's keys and values
    (decode path); ``kpos`` (Smax,) are the slots' positions."""
    valid = (kpos[None, :] >= 0) & (kpos[None, :] <= qpos[:, None])
    if window:
        valid &= kpos[None, :] > (qpos[:, None] - window)
    if scale is None:
        scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                   kk.astype(jnp.float32))
    s = jnp.where(valid[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / l), vv.astype(jnp.float32))
    return out.astype(q.dtype)


def attention(params, x, cfg, *, positions, cache=None, cache_index=None,
              impl: Optional[str] = None):
    """Full GQA attention layer.

    x: (B, S, d).  Three modes:
      - training (cache is None): causal self-attention over S.
      - prefill (cache given, S > 1): causal self-attention, cache filled.
      - decode (cache given, S == 1): attend over the ring-buffer cache.

    Returns (out, new_cache).
    """
    if cfg.mla is not None:
        return mla_attention(params, x, cfg, positions=positions,
                             cache=cache, cache_index=cache_index)
    B, S, _ = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    wq, wo = params["wq"], params["wo"]
    Hp = tp_padded_heads(H, KV) if cache is None else H
    if Hp != H:
        # zero-pad query heads to the TP multiple (exact: padded wo rows are
        # zero, so phantom heads contribute nothing)
        wq = {k_: jnp.pad(v_, [(0, 0)] * (v_.ndim - 2)
                          + [(0, Hp - H), (0, 0)])
              for k_, v_ in wq.items()}
        wo = {"w": jnp.pad(wo["w"], [(0, Hp - H), (0, 0), (0, 0)])}
        H = Hp
    from repro.sharding.specs import head_tp_active
    kv_kind = "kv_heads" if head_tp_active(H) else "heads"
    q = constrain(_proj_heads(wq, x), "heads")               # (B,S,H,hd)
    k = constrain(_proj_heads(params["wk"], x), kv_kind)
    v = constrain(_proj_heads(params["wv"], x), kv_kind)
    q = layers.apply_rope(q, positions, cfg.rope_theta)
    k = layers.apply_rope(k, positions, cfg.rope_theta)
    groups = H // KV

    new_cache = None
    if cache is not None and S == 1:
        smax = cache["k"].shape[1]
        slot = cache_index % smax
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), slot, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), slot, axis=1)
        cpos = jax.lax.dynamic_update_slice_in_dim(
            cache["pos"], positions.reshape(1).astype(jnp.int32), slot, axis=0)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
        out = _cache_attend(q, _repeat_kv(ck, groups), _repeat_kv(cv, groups),
                            cpos, positions.reshape(1), cfg.sliding_window)
    else:
        kk = _repeat_kv(k, groups)
        vv = _repeat_kv(v, groups)
        use = impl or cfg.attention_impl
        if use == "dense":
            out = dense_attention(q, kk, vv, causal=True,
                                  window=cfg.sliding_window)
        elif use == "pallas":
            from repro.kernels import ops as kops
            out = kops.flash_attention(q, kk, vv, causal=True,
                                       window=cfg.sliding_window)
        else:  # chunked reference (used for dry-run lowering)
            out = chunked_attention(q, kk, vv, causal=True,
                                    window=cfg.sliding_window,
                                    chunk=min(cfg.attn_chunk, x.shape[1]))
        if cache is not None:  # prefill: write the (possibly windowed) tail
            smax = cache["k"].shape[1]
            ktail = k[:, -smax:].astype(cache["k"].dtype)
            vtail = v[:, -smax:].astype(cache["v"].dtype)
            tailpos = positions[-smax:].astype(jnp.int32)
            if smax == S:
                # full cache, prefill from position 0: slots are identity
                new_cache = {"k": ktail, "v": vtail, "pos": tailpos}
            else:
                # sliding window: store the tail at its ring slots
                slot = tailpos % smax
                ck = cache["k"].at[:, slot].set(ktail)
                cv = cache["v"].at[:, slot].set(vtail)
                cpos = cache["pos"].at[slot].set(tailpos)
                new_cache = {"k": ck, "v": cv, "pos": cpos}
    out = jnp.einsum("bshk,hkd->bsd", out, wo["w"])
    return out, new_cache


# ---------------------------------------------------------------------------
# multi-head latent attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def mla_init(key, cfg) -> dict:
    """q (d, H, nope+rope); kv_a (d, r+rope): the latent and the shared rope
    key; the latent's RMSNorm; kv_b (r, H, nope+v): each head's key and
    value from the latent; o (H, v, d)."""
    a, d, H = cfg.mla, cfg.d_model, cfg.n_heads
    dtype = jnp.dtype(cfg.dtype)
    r, nope, rope, hv = (a.kv_lora_rank, a.qk_nope_head_dim,
                         a.qk_rope_head_dim, a.v_head_dim)
    kq, ka, kb, ko = jax.random.split(key, 4)

    def normal(k, shape, fan_in):
        return {"w": (jax.random.normal(k, shape, jnp.float32)
                      * fan_in ** -0.5).astype(dtype)}

    return {"wq": normal(kq, (d, H, nope + rope), d),
            "wkv_a": normal(ka, (d, r + rope), d),
            "kv_norm": layers.rmsnorm_init(r, dtype),
            "wkv_b": normal(kb, (r, H, nope + hv), r),
            "wo": normal(ko, (H, hv, d), H * hv)}


def mla_rope(cfg):
    """(inverse frequencies of the rope dims, cos/sin scale)."""
    a = cfg.mla
    rs = a.rope_scaling
    if rs is None:
        return layers.rope_freqs(a.qk_rope_head_dim, cfg.rope_theta), 1.0
    inv = jnp.asarray(layers.yarn_inv_freq(a.qk_rope_head_dim,
                                           cfg.rope_theta, rs))
    return inv, (layers.yarn_get_mscale(rs.factor, rs.mscale)
                 / layers.yarn_get_mscale(rs.factor, rs.mscale_all_dim))


def mla_softmax_scale(cfg) -> float:
    """qk_head_dim**-0.5, times YaRN's mscale(factor, mscale_all_dim)**2."""
    a = cfg.mla
    scale = a.qk_head_dim ** -0.5
    rs = a.rope_scaling
    if rs is not None and rs.mscale_all_dim:
        m = layers.yarn_get_mscale(rs.factor, rs.mscale_all_dim)
        scale *= m * m
    return scale


def _mla_expand(params, ckv, kpe, cfg):
    """Latent (B,S,r) and roped key (B,S,rope) -> per-head keys
    (B,S,H,nope+rope) and values (B,S,H,v)."""
    nope = cfg.mla.qk_nope_head_dim
    kv = jnp.einsum("bsr,rhk->bshk", ckv, params["wkv_b"]["w"])
    k_nope, v = kv[..., :nope], kv[..., nope:]
    k_pe = jnp.broadcast_to(kpe[:, :, None, :],
                            k_nope.shape[:3] + (kpe.shape[-1],))
    return jnp.concatenate([k_nope, k_pe], axis=-1), v


def mla_attention(params, x, cfg, *, positions, cache=None,
                  cache_index=None):
    """MLA over x (B, S, d); the modes and return value of ``attention``."""
    a = cfg.mla
    B, S, _ = x.shape
    r = a.kv_lora_rank
    inv_freq, mscale = mla_rope(cfg)
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"]["w"])
    q_pe = layers.apply_rope_pairs(q[..., a.qk_nope_head_dim:], positions,
                                   inv_freq, mscale)
    q = jnp.concatenate([q[..., :a.qk_nope_head_dim], q_pe], axis=-1)
    kv_a = x @ params["wkv_a"]["w"]
    ckv = layers.rmsnorm(params["kv_norm"], kv_a[..., :r], cfg.norm_eps)
    kpe = layers.apply_rope_pairs(kv_a[..., None, r:], positions, inv_freq,
                                  mscale)[:, :, 0]
    scale = mla_softmax_scale(cfg)

    new_cache = None
    if cache is not None and S == 1:
        smax = cache["ckv"].shape[1]
        slot = cache_index % smax
        new_cache = {
            "ckv": jax.lax.dynamic_update_slice_in_dim(
                cache["ckv"], ckv.astype(cache["ckv"].dtype), slot, axis=1),
            "kpe": jax.lax.dynamic_update_slice_in_dim(
                cache["kpe"], kpe.astype(cache["kpe"].dtype), slot, axis=1),
            "pos": jax.lax.dynamic_update_slice_in_dim(
                cache["pos"], positions.reshape(1).astype(jnp.int32), slot,
                axis=0)}
        k, v = _mla_expand(params, new_cache["ckv"].astype(x.dtype),
                           new_cache["kpe"].astype(x.dtype), cfg)
        out = _cache_attend(q, k, v, new_cache["pos"], positions.reshape(1),
                            scale=scale)
    else:
        k, v = _mla_expand(params, ckv, kpe, cfg)
        out = chunked_attention(q, k, v, causal=True,
                                chunk=min(cfg.attn_chunk, S), scale=scale)
        if cache is not None:  # prefill from position 0
            smax = cache["ckv"].shape[1]
            tailpos = positions[-smax:].astype(jnp.int32)
            slot = tailpos % smax
            new_cache = {
                "ckv": cache["ckv"].at[:, slot].set(
                    ckv[:, -smax:].astype(cache["ckv"].dtype)),
                "kpe": cache["kpe"].at[:, slot].set(
                    kpe[:, -smax:].astype(cache["kpe"].dtype)),
                "pos": cache["pos"].at[slot].set(tailpos)}
    out = jnp.einsum("bshk,hkd->bsd", out, params["wo"]["w"])
    return out, new_cache
