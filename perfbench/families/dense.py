"""The dense decoder family: a configuration file's sizes, the program's
settings for them, the benchmark's seeded weights in the program's layout,
the plain reference loss, and the counts the metrics divide by.

A family module provides, for a configuration dict under its published
``config.json`` key names:

  dims(cfgd)                       frozen sizes, with at least ``vocab``
                                   and ``dtype``
  program_fields(m)                the program ``ModelConfig`` fields the
                                   sizes set
  make(m, key)                     the weight tree from ``key``, traced
                                   under ``jax.jit``
  loss(m, params, inputs, labels, operands=None)
                                   the reference's mean token
                                   cross-entropy, float32 at HIGHEST
  n_params(m), train_flops_per_token(m, seq_len)

The model: token embedding, then per layer RMSNorm -> causal GQA attention
with half-split RoPE (and q/k/v biases where the configuration has them)
-> residual -> RMSNorm -> SwiGLU MLP -> residual, a final RMSNorm, and the
LM head (the embedding, transposed, when tied).

Weight layout (leading layer axis on every block leaf; the program scans
it):

  embed/w (V, d); final_norm/g (d,); lm_head/w (d, V) when untied
  blocks[0]/norm1/g, norm2/g (L, d)
  blocks[0]/attn/wq/w (L, d, H, hd), wk/w, wv/w (L, d, KV, hd)
                 wq/b (L, H, hd), wk/b, wv/b (L, KV, hd) with qkv bias
                 wo/w (L, H, hd, d)
  blocks[0]/ffn/wi/w, wg/w (L, d, F), wo/w (L, F, d)

Matrices are N(0, 1/fan_in), the embedding N(0, 0.02^2), norm scales 1 and
biases 0, all drawn in float32 and stored in the configuration's dtype.

Nothing here imports the program: the counts are the benchmark's yardstick,
worked out from the configuration's shapes alone.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import F32, mm, rmsnorm
from perfbench.weights import DTYPES


@dataclass(frozen=True)
class Dims:
    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    tied: bool
    qkv_bias: bool
    window: int
    rope_theta: float
    eps: float
    dtype: str


def dims(cfg: dict) -> Dims:
    """The shapes of a configuration dict (published key names)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    window = cfg.get("sliding_window") or 0
    if not cfg.get("use_sliding_window", True):
        window = 0
    return Dims(layers=cfg["num_hidden_layers"], d=d, heads=h,
                kv_heads=cfg["num_key_value_heads"],
                head_dim=cfg.get("head_dim") or d // h,
                ffn=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                tied=bool(cfg["tie_word_embeddings"]),
                qkv_bias=bool(cfg["attention_bias"]), window=int(window),
                rope_theta=float(cfg["rope_theta"]),
                eps=float(cfg["rms_norm_eps"]), dtype=cfg["torch_dtype"])


def program_fields(m: Dims) -> dict:
    """The program's ``ModelConfig`` fields these sizes set."""
    return dict(n_layers=m.layers, d_model=m.d, n_heads=m.heads,
                n_kv_heads=m.kv_heads, d_ff=m.ffn, vocab_size=m.vocab,
                head_dim=m.head_dim, qkv_bias=m.qkv_bias,
                rope_theta=m.rope_theta, norm_eps=m.eps,
                tie_embeddings=m.tied, sliding_window=m.window,
                dtype=m.dtype)


# ------------------------------------------------------------------ counts

def matmul_params(m: Dims) -> int:
    """Weights that enter a matrix multiplication: the attention and MLP
    projections of every layer and the LM head (tied or not).  The input
    embedding is a lookup, and biases and norm scales are elementwise."""
    attn = m.d * m.heads * m.head_dim * 2 + m.d * m.kv_heads * m.head_dim * 2
    mlp = 3 * m.d * m.ffn
    return m.layers * (attn + mlp) + m.d * m.vocab


def n_params(m: Dims) -> int:
    """Every parameter: the flat payload a client ships each round."""
    attn = m.d * m.heads * m.head_dim * 2 + m.d * m.kv_heads * m.head_dim * 2
    if m.qkv_bias:
        attn += (m.heads + 2 * m.kv_heads) * m.head_dim
    layer = attn + 3 * m.d * m.ffn + 2 * m.d
    head = 0 if m.tied else m.d * m.vocab
    return m.vocab * m.d + head + m.layers * layer + m.d


def train_flops_per_token(m: Dims, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per matmul
    weight, plus causal attention (scores and values, 4 FLOPs per head
    dimension per attended key forward, 3x for the backward pass, over the
    (S+1)/2 keys a causal query attends on average, the window permitting).
    Recomputation under remat is not counted."""
    keys = (seq_len + 1) / 2.0
    if m.window and m.window < seq_len:
        w = m.window
        # queries past the window attend exactly w keys
        keys = (w * (w + 1) / 2.0 + (seq_len - w) * w) / seq_len
    attn = 12.0 * m.layers * m.heads * m.head_dim * keys
    return 6.0 * matmul_params(m) + attn


# ----------------------------------------------------------------- weights

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


# --------------------------------------------------------------- reference

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
    h = rmsnorm(x, p["norm1"]["g"], m.eps)
    a = p["attn"]

    def proj(w):
        y = mm("bsd,dnk->bsnk", h, w["w"], operands)
        return y + w["b"] if "b" in w else y

    q, k, v = proj(a["wq"]), proj(a["wk"]), proj(a["wv"])
    q, k = _rope(q, m.rope_theta), _rope(k, m.rope_theta)
    groups = m.heads // m.kv_heads
    k = jnp.repeat(k, groups, axis=2)
    v = jnp.repeat(v, groups, axis=2)
    s = mm("bqnk,bsnk->bnqs", q, k, operands) / np.sqrt(m.head_dim)
    qi, ki = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    allowed = ki <= qi
    if m.window:
        allowed &= ki > qi - m.window
    s = jnp.where(allowed, s, -jnp.inf)
    o = mm("bnqs,bsnk->bqnk", jax.nn.softmax(s, axis=-1), v, operands)
    x = x + mm("bqnk,nkd->bqd", o, a["wo"]["w"], operands)
    h = rmsnorm(x, p["norm2"]["g"], m.eps)
    f = p["ffn"]
    u = jax.nn.silu(mm("bsd,df->bsf", h, f["wg"]["w"], operands)) \
        * mm("bsd,df->bsf", h, f["wi"]["w"], operands)
    return x + mm("bsf,fd->bsd", u, f["wo"]["w"], operands)


def loss(m: Dims, params, inputs, labels, operands=None):
    """Mean token cross-entropy; ``params`` in float32.  Layers are
    recomputed in the backward pass (``jax.checkpoint``) so the reference
    fits beside nothing else on one chip."""
    x = params["embed"]["w"][inputs]
    layer = jax.checkpoint(lambda x, p: _layer(m, operands, x, p))
    x, _ = jax.lax.scan(lambda x, p: (layer(x, p), None), x,
                        params["blocks"][0])
    x = rmsnorm(x, params["final_norm"]["g"], m.eps)
    head = (params["embed"]["w"].T if m.tied else params["lm_head"]["w"])
    logits = mm("bsd,dv->bsv", x, head, operands)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold)
