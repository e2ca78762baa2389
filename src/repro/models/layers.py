"""Basic neural-net layers (pure functional, pytree params).

All layers follow the same convention: ``init_*(key, ...) -> params dict`` and
a pure apply function.  Parameters are stored in the dtype given by the model
config; math runs in that dtype with fp32 accumulation where it matters.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

Params = dict


def _dtype(name: str):
    return jnp.dtype(name)


def dense_init(key, d_in: int, d_out: int, dtype, bias: bool = False,
               scale: float | None = None) -> Params:
    scale = (1.0 / np.sqrt(d_in)) if scale is None else scale
    w = jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def dense(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    y = x @ p["w"]
    if "b" in p:
        y = y + p["b"]
    return y


def embedding_init(key, vocab: int, d: int, dtype) -> Params:
    w = jax.random.normal(key, (vocab, d), jnp.float32) * 0.02
    return {"w": w.astype(dtype)}


def embed(p: Params, ids: jnp.ndarray) -> jnp.ndarray:
    return jnp.take(p["w"], ids, axis=0)


def rmsnorm_init(d: int, dtype) -> Params:
    return {"g": jnp.ones((d,), dtype)}


def rmsnorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32)).astype(x.dtype)


def layernorm_init(d: int, dtype) -> Params:
    return {"g": jnp.ones((d,), dtype), "b": jnp.zeros((d,), dtype)}


def layernorm(p: Params, x: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)).astype(x.dtype)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                       # (hd//2,)
    angles = positions[..., None].astype(jnp.float32) * freqs   # (..., S, hd//2)
    cos = jnp.cos(angles)[..., None, :]                 # (..., S, 1, hd//2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature factor (``yarn_get_mscale`` of the
    published DeepSeek-V2 modelling code)."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def yarn_inv_freq(dim: int, theta: float, rs) -> np.ndarray:
    """YaRN's rope frequencies (arXiv:2309.00071) for a ``dim``-wide rope
    head under scaling ``rs`` (a ``YaRNScaling``): the base frequencies
    where a dimension turns more than ``beta_fast`` times over the original
    context, the frequencies divided by ``factor`` where it turns fewer
    than ``beta_slow`` times, and a linear ramp between."""
    def corr(rot):
        return (dim * math.log(rs.original_max_position_embeddings
                               / (rot * 2 * math.pi))) / (2 * math.log(theta))

    low = max(math.floor(corr(rs.beta_fast)), 0)
    high = min(math.ceil(corr(rs.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    base = 1.0 / theta ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    return (base / rs.factor * ramp + base * (1.0 - ramp)).astype(np.float32)


def apply_rope_pairs(x: jnp.ndarray, positions: jnp.ndarray, inv_freq,
                     mscale: float = 1.0) -> jnp.ndarray:
    """Rotate each pair (2i, 2i+1) of the last axis by position x
    ``inv_freq[i]``, with cos and sin scaled by ``mscale``.
    x: (..., S, H, hd); positions broadcastable to (..., S)."""
    angles = positions[..., None].astype(jnp.float32) * inv_freq
    cos = (jnp.cos(angles) * mscale)[..., None, :]      # (..., S, 1, hd//2)
    sin = (jnp.sin(angles) * mscale)[..., None, :]
    xp = x.astype(jnp.float32).reshape(x.shape[:-1] + (-1, 2))
    x1, x2 = xp[..., 0], xp[..., 1]
    out = jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.reshape(x.shape).astype(x.dtype)


# ---------------------------------------------------------------------------
# SwiGLU MLP
# ---------------------------------------------------------------------------

def swiglu_init(key, d: int, f: int, dtype) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d, f, dtype),
        "wg": dense_init(k2, d, f, dtype),
        "wo": dense_init(k3, f, d, dtype),
    }


def swiglu(p: Params, x: jnp.ndarray) -> jnp.ndarray:
    h = jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x)
    return dense(p["wo"], h)
