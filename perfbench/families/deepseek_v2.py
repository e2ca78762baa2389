"""The DeepSeek-V2 family (arXiv:2405.04434; the published
``modeling_deepseek.py`` of deepseek-ai/DeepSeek-V2-Lite): a configuration
file's sizes, the program's settings for them, the benchmark's seeded
weights in the program's layout, the plain reference loss, and the counts
the metrics divide by.  ``families/dense.py`` lists what a family module
provides; this one adds ``moe_train_flops_per_token``.

The model: token embedding; ``first_k_dense_replace`` dense layers, then
DeepSeekMoE layers, each RMSNorm -> multi-head latent attention ->
residual -> RMSNorm -> MLP -> residual; a final RMSNorm and an untied head.

Attention (no query compression): q = h Wq, per head qk_nope + qk_rope
wide; [c, k_pe] = h Wkv_a with c the kv_lora_rank latent, RMSNorm'd; per
head [k_nope, v] = c Wkv_b; the rope parts of q and the one shared k_pe
are rotated with YaRN frequencies; keys are [k_nope, k_pe], scores are
scaled by (qk_nope + qk_rope)^-0.5 x mscale(factor, mscale_all_dim)^2;
the value head is v_head_dim wide; o = attn Wo.

DeepSeekMoE: a float32 softmax over the router's published width, greedy
top-k weights as they come (DeepSeek-V2-Lite's norm_topk_prob false and
routed_scaling_factor 1, the only settings modelled); the shared experts as one SwiGLU of n_shared x
moe_intermediate_size on every token; the routed SwiGLU experts weighted by
their gate.  The expert-level balance loss per sequence (``seq_aux``):
aux_loss_alpha x the mean over sequences of sum_e (E / (S k) x picks of e)
x (e's mean probability), summed over the MoE layers.

One chip's share (the configuration's ``deployment``): the file's
``n_routed_experts`` experts from ``first_held_expert`` are held here; the
router keeps the published width (``published.n_routed_experts``) and its
top-k.  Picks of experts held elsewhere add nothing, here as in the
program, and that partial result goes on to the next layer.

Departures from the published code:
  - rope: the published code de-interleaves the rope dims of q and k and
    rotates halves; the reference does exactly that, the program rotates
    the pairs (2i, 2i+1) in place, the same scores;
  - the latent's RMSNorm multiplies by its scale in float32 before the
    cast (published: casts, then scales);
  - routed experts are computed for every token and multiplied by their
    gate (zero where not picked), not gathered per expert: the same sum;
  - weights are drawn N(0, 1/fan_in) (embedding N(0, 0.02^2), norm scales
    1), not the published initialisation.

Weight layout (leading layer axis on every block leaf; the program scans
it; ``Lm`` MoE layers, ``Ld`` leading dense layers, ``q`` = nope + rope):

  embed/w (V, d); final_norm/g (d,); lm_head/w (d, V)
  {lead_blocks, blocks}[0]/norm1/g, norm2/g (L, d)
  {lead_blocks, blocks}[0]/attn/wq/w (L, d, H, q), wkv_a/w (L, d, r+rope),
      kv_norm/g (L, r), wkv_b/w (L, r, H, nope+v), wo/w (L, H, v, d)
  lead_blocks[0]/ffn/wi/w, wg/w (Ld, d, F), wo/w (Ld, F, d)
  blocks[0]/ffn/router (Lm, d, E), wi, wg (Lm, held, d, Fe),
      wo (Lm, held, Fe, d), shared/wi/w, wg/w (Lm, d, n_shared Fe),
      shared/wo/w (Lm, n_shared Fe, d)

Nothing here imports the program but ``program_fields``, which states the
program's settings: the counts and the reference are worked out from the
configuration alone.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import F32, mm, rmsnorm
from perfbench.weights import DTYPES


@dataclass(frozen=True)
class Dims:
    layers: int               # every layer, the leading dense ones included
    dense_layers: int
    d: int
    heads: int
    kv_rank: int
    nope: int
    rope: int
    v: int
    ffn: int                  # the leading layers' MLP width
    expert_ffn: int
    router: int               # the router's width: every routed expert
    held: int                 # routed experts held here
    first_held: int
    top_k: int
    shared: int
    aux_alpha: float
    vocab: int
    eps: float
    rope_theta: float
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    #  mscale, mscale_all_dim), or None without rope scaling
    yarn: Optional[Tuple[float, int, float, float, float, float]]
    dtype: str

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers

    @property
    def qk(self) -> int:
        return self.nope + self.rope


def dims(cfg: dict) -> Dims:
    """The shapes of a configuration dict (published key names)."""
    rs = cfg.get("rope_scaling")
    yarn = None
    if rs:
        if rs.get("type") != "yarn":
            raise ValueError(f"rope_scaling {rs!r}: only yarn is modelled")
        yarn = (float(rs["factor"]), int(rs["original_max_position_embeddings"]),
                float(rs.get("beta_fast", 32)), float(rs.get("beta_slow", 1)),
                float(rs.get("mscale", 1)), float(rs.get("mscale_all_dim", 0)))
    if cfg.get("q_lora_rank") is not None:
        raise ValueError("query compression (q_lora_rank) is not modelled")
    if cfg.get("scoring_func", "softmax") != "softmax" \
            or cfg.get("topk_method", "greedy") != "greedy":
        raise ValueError("only softmax scoring with greedy top-k is modelled")
    if cfg.get("norm_topk_prob") or cfg.get("routed_scaling_factor", 1) != 1 \
            or not cfg.get("seq_aux", True):
        raise ValueError("only top-k weights as they come (norm_topk_prob "
                         "false, routed_scaling_factor 1) with the "
                         "per-sequence balance loss (seq_aux) are modelled")
    if cfg.get("moe_layer_freq", 1) != 1:
        raise ValueError("only an MoE layer after every leading dense one")
    if cfg.get("tie_word_embeddings"):
        raise ValueError("DeepSeek-V2's head is untied")
    published = cfg.get("published", {})
    return Dims(
        layers=cfg["num_hidden_layers"],
        dense_layers=cfg["first_k_dense_replace"],
        d=cfg["hidden_size"], heads=cfg["num_attention_heads"],
        kv_rank=cfg["kv_lora_rank"], nope=cfg["qk_nope_head_dim"],
        rope=cfg["qk_rope_head_dim"], v=cfg["v_head_dim"],
        ffn=cfg["intermediate_size"],
        expert_ffn=cfg["moe_intermediate_size"],
        router=published.get("n_routed_experts", cfg["n_routed_experts"]),
        held=cfg["n_routed_experts"],
        first_held=int(cfg.get("first_held_expert", 0)),
        top_k=cfg["num_experts_per_tok"], shared=cfg["n_shared_experts"],
        aux_alpha=float(cfg["aux_loss_alpha"]),
        vocab=cfg["vocab_size"], eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]), yarn=yarn,
        dtype=cfg["torch_dtype"])


def program_fields(m: Dims) -> dict:
    """The program's ``ModelConfig`` fields these sizes set."""
    from repro.configs.base import MLAConfig, MoEConfig, YaRNScaling
    rs = None
    if m.yarn is not None:
        f, orig, fast, slow, ms, msa = m.yarn
        rs = YaRNScaling(factor=f, original_max_position_embeddings=orig,
                         beta_fast=fast, beta_slow=slow, mscale=ms,
                         mscale_all_dim=msa)
    return dict(
        n_layers=m.layers, first_dense_layers=m.dense_layers, d_model=m.d,
        n_heads=m.heads, n_kv_heads=m.heads, d_ff=m.ffn, vocab_size=m.vocab,
        head_dim=0, qkv_bias=False, rope_theta=m.rope_theta, norm_eps=m.eps,
        tie_embeddings=False, sliding_window=0, dtype=m.dtype,
        mla=MLAConfig(kv_lora_rank=m.kv_rank, qk_nope_head_dim=m.nope,
                      qk_rope_head_dim=m.rope, v_head_dim=m.v,
                      rope_scaling=rs),
        moe=MoEConfig(n_experts=m.held, router_experts=m.router,
                      first_held=m.first_held, top_k=m.top_k,
                      d_expert=m.expert_ffn, n_shared=m.shared,
                      aux_loss_weight=m.aux_alpha,
                      dispatch_impl="dropless"))


# ------------------------------------------------------------------ counts

def _attn_weights(m: Dims) -> int:
    """The matmul weights of one MLA layer."""
    return (m.d * m.heads * m.qk + m.d * (m.kv_rank + m.rope)
            + m.kv_rank * m.heads * (m.nope + m.v) + m.heads * m.v * m.d)


def _moe_weights_per_token(m: Dims) -> float:
    """Matmul weights one token meets in one MoE layer: the router, the
    shared experts, and top_k x held / router of one routed expert (the
    picks that land on the experts held here, if routing is even)."""
    expert = 3 * m.d * m.expert_ffn
    return (m.d * m.router + m.shared * expert
            + m.top_k * m.held / m.router * expert)


def n_params(m: Dims) -> int:
    """Every parameter: the flat payload a client ships each round."""
    attn = _attn_weights(m) + m.kv_rank
    dense = attn + 3 * m.d * m.ffn + 2 * m.d
    expert = 3 * m.d * m.expert_ffn
    moe = (attn + m.d * m.router + (m.held + m.shared) * expert + 2 * m.d)
    return (2 * m.vocab * m.d + m.d + m.dense_layers * dense
            + m.moe_layers * moe)


def moe_train_flops_per_token(m: Dims) -> float:
    """Model FLOPs of one trained token in the MoE layers (router, shared
    experts and the routed share held here), forward and backward: 6 per
    matmul weight met; no recomputation."""
    return 6.0 * m.moe_layers * _moe_weights_per_token(m)


def train_flops_per_token(m: Dims, seq_len: int) -> float:
    """Model FLOPs of one trained token, forward and backward: 6 per matmul
    weight met (every MLA projection, the dense MLPs, the MoE layers as
    ``moe_train_flops_per_token`` counts them, the head), plus causal
    attention: scores over a qk-wide q.k and values over v, 2 FLOPs a
    multiply-add forward and 2x that backward, over the (S+1)/2 keys a
    query attends on average.  Recomputation under remat is not counted."""
    keys = (seq_len + 1) / 2.0
    attn = 6.0 * m.layers * m.heads * (m.qk + m.v) * keys
    dense = 6.0 * m.dense_layers * 3 * m.d * m.ffn
    return (6.0 * (m.layers * _attn_weights(m) + m.d * m.vocab) + dense
            + moe_train_flops_per_token(m) + attn)


# ----------------------------------------------------------------- weights

def _block_shapes(m: Dims, L: int, moe: bool) -> dict:
    d, H = m.d, m.heads
    out = {
        ("norm1", "g"): ((L, d), "ones"),
        ("norm2", "g"): ((L, d), "ones"),
        ("attn", "wq", "w"): ((L, d, H, m.qk), f"normal:{d}"),
        ("attn", "wkv_a", "w"): ((L, d, m.kv_rank + m.rope), f"normal:{d}"),
        ("attn", "kv_norm", "g"): ((L, m.kv_rank), "ones"),
        ("attn", "wkv_b", "w"): ((L, m.kv_rank, H, m.nope + m.v),
                                 f"normal:{m.kv_rank}"),
        ("attn", "wo", "w"): ((L, H, m.v, d), f"normal:{H * m.v}"),
    }
    if not moe:
        out[("ffn", "wi", "w")] = ((L, d, m.ffn), f"normal:{d}")
        out[("ffn", "wg", "w")] = ((L, d, m.ffn), f"normal:{d}")
        out[("ffn", "wo", "w")] = ((L, m.ffn, d), f"normal:{m.ffn}")
        return out
    fe, fs = m.expert_ffn, m.shared * m.expert_ffn
    out[("ffn", "router")] = ((L, d, m.router), f"normal:{d}")
    out[("ffn", "wi")] = ((L, m.held, d, fe), f"normal:{d}")
    out[("ffn", "wg")] = ((L, m.held, d, fe), f"normal:{d}")
    out[("ffn", "wo")] = ((L, m.held, fe, d), f"normal:{fe}")
    if m.shared:
        out[("ffn", "shared", "wi", "w")] = ((L, d, fs), f"normal:{d}")
        out[("ffn", "shared", "wg", "w")] = ((L, d, fs), f"normal:{d}")
        out[("ffn", "shared", "wo", "w")] = ((L, fs, d), f"normal:{fs}")
    return out


def shapes(m: Dims) -> dict:
    """name path -> (shape, init) with init in {"normal:<fan_in>", "embed",
    "ones"}."""
    out = {("embed", "w"): ((m.vocab, m.d), "embed"),
           ("final_norm", "g"): ((m.d,), "ones"),
           ("lm_head", "w"): ((m.d, m.vocab), f"normal:{m.d}")}
    for key, L, moe in (("lead_blocks", m.dense_layers, False),
                        ("blocks", m.moe_layers, True)):
        if L:
            for path, v in _block_shapes(m, L, moe).items():
                out[(key,) + path] = v
    return out


def _nest(flat: dict) -> dict:
    tree: dict = {}
    for path, val in flat.items():
        node = tree
        for p in path[:-1]:
            node = node.setdefault(p, {})
        node[path[-1]] = val
    for key in ("lead_blocks", "blocks"):
        if key in tree:
            tree[key] = (tree[key],)    # one unit in the layer pattern
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
        elif init == "embed":
            a = jax.random.normal(k, shape, jnp.float32) * 0.02
        else:
            fan_in = int(init.split(":")[1])
            a = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        flat[path] = a.astype(dtype)
    return _nest(flat)


# --------------------------------------------------------------- reference

def yarn_get_mscale(scale: float, mscale: float = 1.0) -> float:
    if scale <= 1:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0


def rope_inv_freq(m: Dims) -> np.ndarray:
    """``DeepseekV2YarnRotaryEmbedding``'s inverse frequencies (the plain
    ``1 / theta^(2i/dim)`` without scaling), float32."""
    dim, base = m.rope, m.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    if m.yarn is None:
        return extra
    factor, orig, fast, slow, _, _ = m.yarn
    inter = 1.0 / (factor * base ** (np.arange(0, dim, 2, dtype=np.float32)
                                     / dim))

    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) \
            / (2 * math.log(base))

    low = max(math.floor(find_dim(fast)), 0)
    high = min(math.ceil(find_dim(slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def softmax_scale(m: Dims) -> float:
    scale = m.qk ** -0.5
    if m.yarn is not None and m.yarn[5]:
        ms = yarn_get_mscale(m.yarn[0], m.yarn[5])
        scale = scale * ms * ms
    return scale


def _rope(m: Dims, x):
    """x: (B, S, N, rope), as ``apply_rotary_pos_emb`` of the published
    code: de-interleave the pairs, then rotate the halves."""
    B, S, N, D = x.shape
    x = x.reshape(B, S, N, D // 2, 2).swapaxes(-1, -2).reshape(B, S, N, D)
    freqs = jnp.arange(S, dtype=F32)[:, None] * jnp.asarray(rope_inv_freq(m))
    emb = jnp.concatenate([freqs, freqs], axis=-1)[:, None, :]   # (S, 1, D)
    ms = 1.0
    if m.yarn is not None:
        ms = (yarn_get_mscale(m.yarn[0], m.yarn[4])
              / yarn_get_mscale(m.yarn[0], m.yarn[5]))
    cos, sin = jnp.cos(emb) * ms, jnp.sin(emb) * ms
    rot = jnp.concatenate([-x[..., D // 2:], x[..., :D // 2]], axis=-1)
    return x * cos + rot * sin


def _attention(m: Dims, operands, h, a):
    B, S, _ = h.shape
    q = mm("bsd,dhk->bshk", h, a["wq"]["w"], operands)
    kv = mm("bsd,dr->bsr", h, a["wkv_a"]["w"], operands)
    c = rmsnorm(kv[..., :m.kv_rank], a["kv_norm"]["g"], m.eps)
    k_pe = _rope(m, kv[..., None, m.kv_rank:])                  # (B,S,1,rope)
    kvb = mm("bsr,rhk->bshk", c, a["wkv_b"]["w"], operands)
    k = jnp.concatenate([kvb[..., :m.nope],
                         jnp.broadcast_to(k_pe, (B, S, m.heads, m.rope))], -1)
    v = kvb[..., m.nope:]
    q = jnp.concatenate([q[..., :m.nope], _rope(m, q[..., m.nope:])], -1)
    s = mm("bqnk,bsnk->bnqs", q, k, operands) * softmax_scale(m)
    causal = jnp.arange(S)[None, :] <= jnp.arange(S)[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    o = mm("bnqs,bsnk->bqnk", jax.nn.softmax(s, axis=-1), v, operands)
    return mm("bqnk,nkd->bqd", o, a["wo"]["w"], operands)


def _swiglu(operands, h, wg, wi, wo):
    u = jax.nn.silu(mm("...d,df->...f", h, wg, operands)) \
        * mm("...d,df->...f", h, wi, operands)
    return mm("...f,fd->...d", u, wo, operands)


def _moe(m: Dims, operands, h, f):
    """(output, balance loss) of one MoE layer; h: (B, S, d)."""
    B, S, _ = h.shape
    probs = jax.nn.softmax(mm("bsd,de->bse", h, f["router"], operands), -1)
    w, idx = jax.lax.top_k(probs, m.top_k)                       # (B,S,k)
    pick = jax.nn.one_hot(idx, m.router, dtype=F32)              # (B,S,k,E)
    gate = jnp.einsum("bsk,bske->bse", w, pick)
    ce = jnp.sum(pick, axis=(1, 2)) * (m.router / (S * m.top_k))  # (B,E)
    aux = jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    held = gate[..., m.first_held:m.first_held + m.held]          # (B,S,held)

    def expert(wg, wi, wo):
        return _swiglu(operands, h, wg, wi, wo)

    ys = jax.vmap(expert)(f["wg"], f["wi"], f["wo"])             # (held,B,S,d)
    out = jnp.einsum("bse,ebsd->bsd", held, ys)
    if m.shared:
        s = f["shared"]
        out = out + _swiglu(operands, h, s["wg"]["w"], s["wi"]["w"],
                            s["wo"]["w"])
    return out, aux


def _layer(m: Dims, operands, moe: bool, carry, p):
    x, aux = carry
    x = x + _attention(m, operands, rmsnorm(x, p["norm1"]["g"], m.eps),
                       p["attn"])
    h = rmsnorm(x, p["norm2"]["g"], m.eps)
    f = p["ffn"]
    if moe:
        y, a = _moe(m, operands, h, f)
        return x + y, aux + a
    return x + _swiglu(operands, h, f["wg"]["w"], f["wi"]["w"],
                       f["wo"]["w"]), aux


def loss(m: Dims, params, inputs, labels, operands=None):
    """Mean token cross-entropy plus aux_loss_alpha x the balance losses;
    ``params`` in float32.  Layers are recomputed in the backward pass
    (``jax.checkpoint``) so the reference fits beside nothing else on one
    chip."""
    carry = (params["embed"]["w"][inputs], jnp.zeros((), F32))
    for key, moe in (("lead_blocks", False), ("blocks", True)):
        if key in params:
            layer = jax.checkpoint(
                lambda c, p, moe=moe: _layer(m, operands, moe, c, p))
            carry, _ = jax.lax.scan(lambda c, p: (layer(c, p), None), carry,
                                    params[key][0])
    x, aux = carry
    x = rmsnorm(x, params["final_norm"]["g"], m.eps)
    logits = mm("bsd,dv->bsv", x, params["lm_head"]["w"], operands)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - gold) + m.aux_alpha * aux
