"""Seeded weights for a configuration, made by the benchmark in the
program's parameter layout, so the program and the reference start from
the same numbers and the reference takes nothing the program made.

Layout (leading layer axis on every block leaf; the program scans it):

  embed/w (V, d); final_norm/g (d,); lm_head/w (d, V) when untied
  blocks[0]/norm1/g, norm2/g (L, d)
  blocks[0]/attn/wq/w (L, d, H, hd), wk/w, wv/w (L, d, KV, hd)
                 wq/b (L, H, hd), wk/b, wv/b (L, KV, hd) with qkv bias
                 wo/w (L, H, hd, d)
  blocks[0]/ffn/wi/w, wg/w (L, d, F), wo/w (L, F, d)

Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1 and
biases 0, all drawn in float32 and stored in the configuration's dtype.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from perfbench.modelcfg import Dims

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
          "float16": jnp.float16}


def seed_key(seed: int):
    """A PRNG key that keeps all 64 bits of ``seed`` (``jax.random.key``
    alone drops the high word)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0xFFFFFFFF), seed >> 32)


def shapes(m: Dims) -> dict:
    """name path -> (shape, init) with init in {"normal:<fan_in>", "embed",
    "ones", "zeros"}."""
    L, d, H, KV, hd, F, V = (m.layers, m.d, m.heads, m.kv_heads, m.head_dim,
                             m.ffn, m.vocab)
    out = {
        ("embed", "w"): ((V, d), "embed"),
        ("final_norm", "g"): ((d,), "ones"),
        ("blocks", "norm1", "g"): ((L, d), "ones"),
        ("blocks", "norm2", "g"): ((L, d), "ones"),
        ("blocks", "attn", "wq", "w"): ((L, d, H, hd), f"normal:{d}"),
        ("blocks", "attn", "wk", "w"): ((L, d, KV, hd), f"normal:{d}"),
        ("blocks", "attn", "wv", "w"): ((L, d, KV, hd), f"normal:{d}"),
        ("blocks", "attn", "wo", "w"): ((L, H, hd, d), f"normal:{H * hd}"),
        ("blocks", "ffn", "wi", "w"): ((L, d, F), f"normal:{d}"),
        ("blocks", "ffn", "wg", "w"): ((L, d, F), f"normal:{d}"),
        ("blocks", "ffn", "wo", "w"): ((L, F, d), f"normal:{F}"),
    }
    if m.qkv_bias:
        out[("blocks", "attn", "wq", "b")] = ((L, H, hd), "zeros")
        out[("blocks", "attn", "wk", "b")] = ((L, KV, hd), "zeros")
        out[("blocks", "attn", "wv", "b")] = ((L, KV, hd), "zeros")
    if not m.tied:
        out[("lm_head", "w")] = ((d, V), f"normal:{d}")
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = val
    tree["blocks"] = (tree["blocks"],)      # one unit in the layer pattern
    return tree


def make(m: Dims, key, dtype=None) -> dict:
    """The weight tree from ``key``; trace it under ``jax.jit`` so the
    weights are drawn on the device in one call."""
    dtype = DTYPES[m.dtype] if dtype is None else dtype
    flat = {}
    for i, (path, (shape, init)) in enumerate(sorted(shapes(m).items())):
        k = jax.random.fold_in(key, i)
        if init == "ones":
            a = jnp.ones(shape, jnp.float32)
        elif init == "zeros":
            a = jnp.zeros(shape, jnp.float32)
        elif init == "embed":
            a = jax.random.normal(k, shape, jnp.float32) * 0.02
        else:
            fan_in = int(init.split(":")[1])
            a = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        flat[path] = a.astype(dtype)
    return _nest(flat)


def make_on_device(m: Dims, seed: int) -> dict:
    return jax.jit(lambda k: make(m, k))(seed_key(seed))
