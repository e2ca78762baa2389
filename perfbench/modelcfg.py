"""Sizes of a configuration file (``configs/<name>.json``), read under the
published ``config.json`` key names, and the arithmetic the benchmark needs
from them: parameter counts, model FLOPs per token and fold bytes.

Nothing here imports the program: the counts are the benchmark's yardstick,
worked out from the configuration's shapes alone.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.abspath(__file__))


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


def load_config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        return json.load(f)


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


def fold_bytes(m: Dims, clients: int, delta_bytes: int = 2) -> int:
    """HBM bytes one executor's local fold of ``clients`` deltas needs: each
    delta read once in its own dtype, the fp32 accumulator read and written
    once.  The same count whatever implements the fold."""
    n = n_params(m)
    return clients * n * delta_bytes + 2 * 4 * n
