"""Top-level language model: embedding → decoder stack → head → loss.

Entry points (all pure; shapes fixed per (arch × input shape) cell):

  init_params(key, cfg)                         -> params pytree
  forward(params, inputs, cfg, ...)             -> hidden states
  loss_and_aux(params, batch, cfg)              -> scalar loss (chunked xent)
  loss_and_stats(params, batch, cfg)            -> (loss, block stats)
  make_train_step(cfg, lr)                      -> jit-able SGD client step
  make_prefill_step(cfg, batch, seq)            -> serve prefill
  make_decode_step(cfg, batch, seq)             -> serve one-token decode

``input_kind == "embeddings"`` (audio/vlm stubs) feeds precomputed frontend
embeddings of shape (B, S, d_model) instead of token ids; the label side is
always token ids.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import layers, transformer
from repro.sharding.specs import constrain


def init_params(key, cfg) -> dict:
    dtype = jnp.dtype(cfg.dtype)
    ke, kb, kh = jax.random.split(key, 3)
    p = {
        "embed": layers.embedding_init(ke, cfg.vocab_size, cfg.d_model, dtype),
        "blocks": transformer.stack_init(kb, cfg),
        "final_norm": layers.rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = layers.dense_init(kh, cfg.d_model, cfg.vocab_size, dtype)
    if cfg.first_dense_layers:
        p["lead_blocks"] = transformer.stack_init(
            jax.random.fold_in(kb, 1 << 16), cfg, lead=True)
    return p


@jax.named_scope("embed")
def _embed_inputs(params, inputs, cfg):
    if cfg.input_kind == "embeddings":
        return inputs.astype(jnp.dtype(cfg.dtype))
    return layers.embed(params["embed"], inputs)


def _head(params, h, cfg):
    if cfg.tie_embeddings:
        return h @ params["embed"]["w"].T
    return layers.dense(params["lm_head"], h)


def forward(params, inputs, cfg, *, positions=None, caches=None,
            cache_index=None, decode=False, with_stats=False):
    """inputs: (B,S) ids or (B,S,d) embeddings -> (hidden (B,S,d), caches,
    aux), and with ``with_stats`` the blocks' stats (``{name: (layers,
    ...)}``; ``moe_expert_rows``: rows per held expert of each dropless MoE
    layer)."""
    x = constrain(_embed_inputs(params, inputs, cfg), "residual")
    B, S = x.shape[0], x.shape[1]
    if positions is None:
        positions = jnp.arange(S)
    kw = dict(positions=positions, cache_index=cache_index, decode=decode)
    new_lead = None
    if cfg.first_dense_layers:
        lead_caches = None
        if caches is not None:
            lead_caches, caches = caches["lead"], caches["stack"]
        x, new_lead, _ = transformer.stack_apply(
            params["lead_blocks"], x, cfg, caches=lead_caches, lead=True,
            **kw)
    x, new_caches, aux, st = transformer.stack_apply(
        params["blocks"], x, cfg, caches=caches, with_stats=True, **kw)
    if new_lead is not None:
        new_caches = {"lead": new_lead, "stack": new_caches}
    x = layers.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    if not with_stats:
        return x, new_caches, aux
    return x, new_caches, aux, {k: v for s in st for k, v in s.items()}


def _xent(logits, labels):
    """Mean token cross-entropy, fp32.  logits: (T,V); labels: (T,)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.sum(lse - gold)


def chunked_xent(params, h, labels, cfg):
    """Cross entropy without materialising the full (B, S, V) logits tensor.

    Scans over *sequence* chunks — (nc, B, S/nc, d) — never merging the
    batch and sequence dims, so the (B@dp, S@model) input sharding survives
    the reshape (merging them forces GSPMD into involuntary full
    rematerialisation: a 25.8 GB/device replicated copy on grok-1).  The
    backward pass recomputes each chunk's logits (jax.checkpoint), bounding
    peak memory at (B, S/nc, V/tp) — essential for the 202k-vocab
    llama4-scout cell.
    """
    B, S, d = h.shape
    T = B * S
    chunk_tokens = cfg.logit_chunk or T
    # smallest sequence split nc | S with B * (S/nc) <= logit_chunk
    nc = 1
    while nc < S and (B * (S // nc) > chunk_tokens or S % nc):
        nc += 1
    Sc = S // nc

    @jax.checkpoint
    @jax.named_scope("head_loss")
    def one(hc, lc):
        # undo sequence parallelism before the vocab-parallel head: batch
        # over dp, seq replicated, V over model -> no partial-sum all-reduce
        hc = constrain(hc, "loss_chunk")
        logits = _head(params, hc, cfg)
        return _xent(logits.reshape(-1, logits.shape[-1]), lc.reshape(-1))

    if nc == 1:
        return one(h, labels) / T

    hs = jnp.moveaxis(h.reshape(B, nc, Sc, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, Sc), 1, 0)

    def body(tot, xs):
        hc, lc = xs
        return tot + one(hc, lc), None

    tot, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ls))
    return tot / T


def loss_and_stats(params, batch, cfg):
    """(loss, the blocks' stats as ``forward`` gives them)."""
    h, _, aux, stats = forward(params, batch["inputs"], cfg, with_stats=True)
    loss = chunked_xent(params, h, batch["labels"], cfg)
    if cfg.moe is not None:
        loss = loss + cfg.moe.aux_loss_weight * aux
    return loss, stats


def loss_and_aux(params, batch, cfg):
    """batch: {"inputs": (B,S)[ids]|(B,S,d)[embeds], "labels": (B,S)}."""
    return loss_and_stats(params, batch, cfg)[0]


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------

def make_train_step(cfg, lr: float = 0.05, micro_batches: int = 0):
    """Plain-SGD client local step (the FL inner loop; see core/algorithms
    for the federated wrappers that add proximal terms / control variates).

    ``micro_batches`` > 1 enables gradient accumulation: the global batch is
    scanned in k slices, dividing peak activation memory by ~k at the cost of
    k sequential sub-steps (fp32 accumulator).  Required to fit the biggest
    train cells (grok-1-314b) in 16 GB/chip.
    """
    micro = micro_batches or getattr(cfg, "train_microbatches", 1) or 1

    def train_step(params, batch):
        if micro <= 1:
            loss, grads = jax.value_and_grad(loss_and_aux)(params, batch, cfg)
        else:
            B = jax.tree.leaves(batch)[0].shape[0]
            assert B % micro == 0, (B, micro)
            mb = jax.tree.map(
                lambda a: a.reshape((micro, B // micro) + a.shape[1:]), batch)

            def body(acc, mbatch):
                l, g = jax.value_and_grad(loss_and_aux)(params, mbatch, cfg)
                acc_l, acc_g = acc
                return (acc_l + l,
                        jax.tree.map(lambda a, b: a + b.astype(jnp.float32),
                                     acc_g, g)), None

            zero = (jnp.zeros((), jnp.float32),
                    jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                                 params))
            (loss, grads), _ = jax.lax.scan(body, zero, mb)
            loss = loss / micro
            grads = jax.tree.map(lambda g: g / micro, grads)
        new_params = jax.tree.map(
            lambda p, g: (p.astype(jnp.float32)
                          - lr * g.astype(jnp.float32)).astype(p.dtype),
            params, grads)
        return new_params, {"loss": loss}

    return train_step


def make_prefill_step(cfg, batch: int, seq_len: int, cache_len: int = 0):
    """Full-sequence forward that fills the decode caches.

    ``cache_len`` (>= seq_len) sizes the cache; defaults to seq_len (the
    dry-run convention: decode attends over a cache of exactly seq_len).
    """
    cache_len = cache_len or seq_len

    def prefill_step(params, inputs):
        dtype = jnp.dtype(cfg.dtype)
        caches = transformer.model_cache(cfg, batch, cache_len, dtype)
        h, new_caches, _ = forward(params, inputs, cfg, caches=caches,
                                   cache_index=0)
        logits = _head(params, h[:, -1:], cfg)
        return logits, new_caches

    return prefill_step


def make_decode_step(cfg):
    """One-token decode against existing caches.

    inputs: token ids (B,1) or embeddings (B,1,d); ``pos``: scalar int32
    (current absolute position).  Returns (logits (B,1,V), new caches).
    """

    def decode_step(params, inputs, caches, pos):
        positions = pos[None] if pos.ndim == 0 else pos
        h, new_caches, _ = forward(params, inputs, cfg, positions=positions,
                                   caches=caches, cache_index=pos, decode=True)
        logits = _head(params, h, cfg)
        return logits, new_caches

    return decode_step
