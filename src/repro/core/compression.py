"""Compiled delta compression for the executor→server partials (DESIGN.md §7).

The hierarchical scheme already cuts comm from O(s_a·M_p) to O(s_a·K);
compression attacks the remaining s_a factor on the reducible entries:

- ``TopKCompressor``: per-executor top-|k| magnitude sparsification with
  error feedback (the residual is added to the next round's partial, so the
  scheme stays unbiased in the long run).
- ``Int8Compressor``: per-entry symmetric int8 quantisation (4x over fp32).
- ``PowerSGDCompressor``: low-rank factorisation by one step of warm-started
  power iteration per round (wire = P + Q instead of the dense buffer).

All three operate on the FLAT partial wire format: an entry occupies one
contiguous span of its group buffer (``core.flat.FlatLayout``), so the span
table of a group is STATIC and each compressor can process every targeted
span of a group buffer in ONE jitted dispatch (``compiled=True``, the
default through ``make_compressor``):

- compress: residual-add → select/quantise/factorise → residual update runs
  as one executable per (group size, span plan); the top-k path calls the
  fused ``kernels/topk_compress`` building block (XLA ``top_k``) per span.  The
  error-feedback state lives DEVICE-RESIDENT in the compressor, keyed per
  (sender, group) — no host round-trip.
- decompress is LAZY: ``decompress_partial`` leaves the buffers in
  compressed wire form and the fold sites (``merge_partials`` /
  ``reduce_flat_partials`` / ``scale_partial``) consume them through the
  stateless ``densify_buffer`` / ``fold_buffer_into`` / ``scale_buffer``
  jits below, scatter-adding segments straight into the accumulator so the
  server never materialises an intermediate dense fp32 copy per partial.

Tie rule (top-k, both paths): the k entries of largest ``|x + residual|``
win; exact magnitude ties go to the LOWER index (``lax.top_k`` stability /
stable argsort in the eager reference) and indices ship sorted ascending —
compiled and eager wire bytes are bit-identical.

Eager per-segment compress/decompress (``compiled=False``, the pre-compiled
behaviour) is kept as the reference path, as is the legacy nested
{entry: pytree} partial form (per-leaf).  Compressors expose
``state_dict``/``load_state_dict`` so the checkpoint blob carries residuals
and PowerSGD warm starts across a resume (``checkpoint/manager.py``).
"""
from __future__ import annotations

import math
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flat import flat_sums, is_compressed_buffer, is_flat_sums
from repro.core.telemetry import span


@dataclass
class CompressedTensor:
    kind: str
    shape: tuple
    dtype: str
    data: Dict[str, np.ndarray]

    @property
    def nbytes(self) -> int:
        return sum(int(a.nbytes) for a in self.data.values())


def _ct_flatten(c: "CompressedTensor"):
    keys = tuple(sorted(c.data))
    return tuple(c.data[k] for k in keys), (c.kind, c.shape, c.dtype, keys)


def _ct_unflatten(aux, children):
    kind, shape, dtype, keys = aux
    return CompressedTensor(kind, shape, dtype, dict(zip(keys, children)))


# Registered as a pytree node so compressed partials survive generic tree
# plumbing: the engines' state_dict host-transfer (in-flight compressed
# CommEvents), jax.block_until_ready over wire partials, payload-byte
# accounting (the data arrays sum to exactly .nbytes).
jax.tree_util.register_pytree_node(CompressedTensor, _ct_flatten,
                                   _ct_unflatten)


_codec_dispatches = 0


def codec_dispatch_count() -> int:
    """Group-level codec dispatches so far (one per jitted compress /
    densify / fold / scale call on a group buffer) — pins the compiled
    path at O(groups), not O(segments), per round."""
    return _codec_dispatches


def reset_codec_dispatch_count() -> None:
    global _codec_dispatches
    _codec_dispatches = 0


def _bump() -> None:
    global _codec_dispatches
    _codec_dispatches += 1


def _colocate(x: Any, like: Any) -> Any:
    from repro.core.placement import colocate
    return colocate(x, like)


# ---------------------------------------------------------------------------
# stateless compressed-buffer consumers (the fused decompress-into-fold)
# ---------------------------------------------------------------------------
#
# A compressed group buffer is {"__compressed__": True, "segments": [...],
# "size": n} with ordered ("raw", array) | ("comp", CompressedTensor)
# segments covering [0, n).  The helpers below walk that structure ONCE to
# build a static signature, then run one cached jit over the flattened
# segment arrays.

def _segments_sig(segments) -> tuple:
    sig: List[tuple] = []
    for kind, x in segments:
        if kind == "raw":
            sig.append(("raw", int(np.prod(np.shape(x)))))
        elif x.kind == "topk":
            sig.append(("topk", int(np.prod(x.shape)),
                        int(np.shape(x.data["idx"])[0])))
        elif x.kind == "int8":
            sig.append(("int8", int(np.prod(x.shape))))
        elif x.kind == "powersgd":
            sig.append(("powersgd", int(np.prod(x.shape)),
                        tuple(int(d) for d in np.shape(x.data["p"])),
                        tuple(int(d) for d in np.shape(x.data["q"]))))
        else:
            raise ValueError(f"unknown compressed kind: {x.kind}")
    return tuple(sig)


def _segments_parts(segments) -> tuple:
    parts: List[Any] = []
    for kind, x in segments:
        if kind == "raw":
            parts.append(jnp.asarray(x, jnp.float32))
        elif x.kind == "topk":
            parts += [jnp.asarray(x.data["idx"], jnp.int32),
                      jnp.asarray(x.data["vals"], jnp.float32)]
        elif x.kind == "int8":
            parts += [jnp.asarray(x.data["q"], jnp.int8),
                      jnp.asarray(x.data["scale"], jnp.float32)]
        else:  # powersgd
            parts += [jnp.asarray(x.data["p"], jnp.float32),
                      jnp.asarray(x.data["q"], jnp.float32)]
    return tuple(parts)


def _walk(sig, parts, out, off, combine):
    """Shared decode walk: ``combine(out, off, n, dense_segment)`` applies a
    dense f32 segment; topk segments go through the sparse fast path."""
    i = 0
    for s in sig:
        n = s[1]
        if s[0] == "raw":
            if n:
                out = combine(out, off, n, parts[i])
            i += 1
        elif s[0] == "topk":
            idx, vals = parts[i], parts[i + 1]
            i += 2
            if n and s[2]:
                out = out.at[off + idx].add(vals)
        elif s[0] == "int8":
            q, scale = parts[i], parts[i + 1]
            i += 2
            if n:
                out = combine(out, off, n, q.astype(jnp.float32) * scale)
        else:  # powersgd
            p, q = parts[i], parts[i + 1]
            i += 2
            out = combine(out, off, n, (p @ q.T).reshape(-1)[:n])
        off += n
    return out


_DENSIFY_CACHE: Dict[tuple, Any] = {}
_FOLD_CACHE: Dict[tuple, Any] = {}
_SCALE_CACHE: Dict[tuple, Any] = {}


def _densify_fn(size: int, sig: tuple):
    fn = _DENSIFY_CACHE.get((size, sig))
    if fn is None:
        def run(parts):
            def set_seg(out, off, n, seg):
                return jax.lax.dynamic_update_slice(out, seg, (off,))
            return _walk(sig, parts, jnp.zeros((size,), jnp.float32), 0,
                         set_seg)
        fn = jax.jit(jax.named_scope("codec")(run))
        _DENSIFY_CACHE[(size, sig)] = fn
    return fn


def _fold_fn(size: int, sig: tuple):
    fn = _FOLD_CACHE.get((size, sig))
    if fn is None:
        def run(acc, parts):
            def add_seg(out, off, n, seg):
                cur = jax.lax.dynamic_slice(out, (off,), (n,))
                return jax.lax.dynamic_update_slice(out, cur + seg, (off,))
            return _walk(sig, parts, acc.astype(jnp.float32), 0, add_seg)
        fn = jax.jit(jax.named_scope("codec")(run))
        _FOLD_CACHE[(size, sig)] = fn
    return fn


def _scale_fn(sig: tuple):
    fn = _SCALE_CACHE.get(sig)
    if fn is None:
        def run(parts, gamma):
            out = []
            i = 0
            for s in sig:
                if s[0] == "raw":
                    out.append(parts[i] * gamma)
                    i += 1
                elif s[0] == "topk":
                    out += [parts[i], parts[i + 1] * gamma]
                    i += 2
                elif s[0] == "int8":
                    out += [parts[i], parts[i + 1] * gamma]
                    i += 2
                else:  # powersgd: P carries the scale, Q stays orthonormal-ish
                    out += [parts[i] * gamma, parts[i + 1]]
                    i += 2
            return tuple(out)
        fn = jax.jit(jax.named_scope("codec")(run))
        _SCALE_CACHE[sig] = fn
    return fn


def densify_buffer(buf: Dict[str, Any]) -> jnp.ndarray:
    """Decode a compressed group buffer to its dense (n,) fp32 form in one
    dispatch (bit-identical to the eager per-segment concatenation)."""
    segs = buf["segments"]
    _bump()
    return _densify_fn(int(buf["size"]), _segments_sig(segs))(
        _segments_parts(segs))


def fold_buffer_into(acc: Any, buf: Dict[str, Any]) -> jnp.ndarray:
    """Fused decompress-into-fold: add a compressed group buffer straight
    into the dense accumulator — raw/int8/low-rank segments add as slices,
    top-k segments scatter-add — with no intermediate dense copy."""
    segs = buf["segments"]
    sig = _segments_sig(segs)
    acc_j = jnp.asarray(acc, jnp.float32)
    parts = tuple(_colocate(p, acc_j) for p in _segments_parts(segs))
    _bump()
    return _fold_fn(int(acc_j.shape[0]), sig)(acc_j, parts)


def scale_buffer(buf: Dict[str, Any], gamma: float) -> Dict[str, Any]:
    """Scale a compressed group buffer by ``gamma`` WITHOUT decoding it
    (async staleness discounts): raw segments and top-k values scale
    directly, int8 folds gamma into the scale, PowerSGD into P."""
    segs = buf["segments"]
    sig = _segments_sig(segs)
    _bump()
    new = _scale_fn(sig)(_segments_parts(segs), jnp.float32(gamma))
    out_segs: List[Tuple[str, Any]] = []
    i = 0
    for (kind, x), s in zip(segs, sig):
        if kind == "raw":
            out_segs.append(("raw", new[i]))
            i += 1
        elif x.kind == "topk":
            out_segs.append(("comp", CompressedTensor(
                "topk", x.shape, x.dtype,
                {"idx": new[i], "vals": new[i + 1]})))
            i += 2
        elif x.kind == "int8":
            out_segs.append(("comp", CompressedTensor(
                "int8", x.shape, x.dtype,
                {"q": new[i], "scale": new[i + 1]})))
            i += 2
        else:
            out_segs.append(("comp", CompressedTensor(
                "powersgd", x.shape, x.dtype,
                {"p": new[i], "q": new[i + 1]})))
            i += 2
    return {"__compressed__": True, "segments": out_segs,
            "size": int(buf["size"])}


# ---------------------------------------------------------------------------
# compressors
# ---------------------------------------------------------------------------

class PartialCompressor:
    """Shared compress/decompress plumbing over the flat partial format.

    Subclasses provide ``_compress(a, key) -> CompressedTensor`` and
    ``_decompress(c) -> np.ndarray`` (the eager reference), and — when
    ``compiled`` — ``_group_compress(group, buf, plan, prefix)`` processing
    a whole group buffer in one dispatch.  ``entries`` names the target
    entries (everything else rides raw)."""

    entries: Tuple[str, ...] = ("delta",)
    compiled: bool = False

    # --- subclass hooks ---------------------------------------------------
    def _compress(self, a: np.ndarray, key: str) -> CompressedTensor:
        raise NotImplementedError

    def _decompress(self, c: CompressedTensor) -> np.ndarray:
        raise NotImplementedError

    def _group_compress(self, group: str, buf: Any, plan: tuple,
                        prefix: str) -> Dict[str, Any]:
        raise NotImplementedError

    # --- checkpointable state --------------------------------------------
    def state_dict(self) -> Optional[Dict[str, Any]]:
        return None

    def load_state_dict(self, state: Optional[Dict[str, Any]]) -> None:
        pass

    # --- flat path --------------------------------------------------------
    def _span_plans(self, layout) -> Dict[str, tuple]:
        """Per-group STATIC segment plan: ordered ("raw"|"comp", off, size,
        entry|None) tuples covering [0, group_size) — the comp spans are the
        targeted entries, everything between rides raw.  Static per layout,
        so it doubles as the jit cache key for the group codecs."""
        spans_by_group: Dict[str, List[Tuple[int, int, str]]] = {}
        for name in self.entries:
            span = layout.spans.get(name)
            if span is not None:
                spans_by_group.setdefault(span.group, []).append(
                    (span.offset, span.size, name))
        plans: Dict[str, tuple] = {}
        for g, spans in spans_by_group.items():
            total = int(layout.group_sizes[g])
            plan: List[tuple] = []
            cursor = 0
            for off, size, name in sorted(spans):
                if off > cursor:             # untargeted entries ride raw
                    plan.append(("raw", cursor, off - cursor, None))
                plan.append(("comp", off, size, name))
                cursor = off + size
            if cursor < total:
                plan.append(("raw", cursor, total - cursor, None))
            plans[g] = tuple(plan)
        return plans

    def _compress_flat(self, sums: Dict, layout, prefix: str = "") -> Dict:
        buffers = dict(sums["buffers"])
        if layout is None:
            return flat_sums(buffers)
        for g, plan in self._span_plans(layout).items():
            buf = buffers.get(g)
            if buf is None or isinstance(buf, dict):
                continue
            if self.compiled:
                buffers[g] = self._group_compress(g, buf, plan, prefix)
                continue
            arr = np.asarray(buf, np.float32)
            segments: List[Tuple[str, Any]] = []
            for kind, off, sz, name in plan:
                if kind == "raw":
                    segments.append(("raw", arr[off:off + sz]))
                else:
                    segments.append(
                        ("comp", self._compress(arr[off:off + sz],
                                                f"{prefix}{g}/{name}")))
            buffers[g] = {"__compressed__": True, "segments": segments,
                          "size": int(arr.size)}
        return flat_sums(buffers)

    def _decompress_flat(self, sums: Dict) -> Dict:
        buffers = {}
        for g, buf in sums["buffers"].items():
            if is_compressed_buffer(buf):
                pieces = [np.asarray(x, np.float32) if kind == "raw"
                          else self._decompress(x).reshape(-1)
                          for kind, x in buf["segments"]]
                buffers[g] = jnp.asarray(
                    pieces[0] if len(pieces) == 1 else np.concatenate(pieces))
            else:
                buffers[g] = buf
        return flat_sums(buffers)

    # --- legacy nested path ----------------------------------------------
    def _compress_nested(self, sums: Dict, prefix: str = "") -> Dict:
        out = dict(sums)
        for name in self.entries:
            if name not in out:
                continue
            leaves, treedef = jax.tree.flatten(out[name])
            comp = [self._compress(np.asarray(l), f"{prefix}{name}/{i}")
                    for i, l in enumerate(leaves)]
            out[name] = {"__compressed__": True, "treedef": treedef,
                         "leaves": comp}
        return out

    def _decompress_nested(self, sums: Dict) -> Dict:
        out = dict(sums)
        for name, v in list(out.items()):
            if isinstance(v, dict) and v.get("__compressed__") \
                    and "leaves" in v:
                leaves = [jnp.asarray(self._decompress(c))
                          for c in v["leaves"]]
                out[name] = jax.tree.unflatten(v["treedef"], leaves)
        return out

    # --- public API -------------------------------------------------------
    @span("codec")
    def compress_partial(self, partial: Dict,
                         key: Optional[str] = None) -> Dict:
        """``key`` namespaces stateful compressor state (error-feedback
        residuals, PowerSGD warm starts): the server passes the sending
        executor's id, so each executor carries its OWN state stream —
        residuals are only meaningful per sender, and per-executor streams
        make the compressed values independent of the cross-executor
        compression order (the network path compresses at dispatch time,
        the comm-free path at fold time; per-executor state makes both
        identical)."""
        out = dict(partial)
        sums = partial["sums"]
        prefix = "" if key is None else f"{key}/"
        out["sums"] = (self._compress_flat(sums, partial.get("layout"),
                                           prefix)
                       if is_flat_sums(sums)
                       else self._compress_nested(sums, prefix))
        out["_wire_bytes"] = _wire_bytes(out["sums"])
        return out

    @span("codec")
    def decompress_partial(self, partial: Dict) -> Dict:
        out = dict(partial)
        sums = partial["sums"]
        if is_flat_sums(sums):
            # compiled codecs decompress LAZILY: the buffers stay in
            # compressed wire form and ride to the fold, which consumes the
            # segments straight into the accumulator (densify_buffer /
            # fold_buffer_into above) — no dense per-partial intermediate.
            out["sums"] = sums if self.compiled else \
                self._decompress_flat(sums)
        else:
            out["sums"] = self._decompress_nested(sums)
        return out


_TOPK_GROUP_CACHE: Dict[tuple, Any] = {}


def _topk_group_fn(n: int, plan: tuple, ks: tuple):
    """One executable per (group size, span plan, k vector): for every
    targeted span, residual-add → fused top-k (kernels/topk_compress) →
    residual scatter-zero; raw spans slice through untouched."""
    key = (n, plan, ks)
    fn = _TOPK_GROUP_CACHE.get(key)
    if fn is None:
        from repro.kernels import topk_compress as tkc

        def run(arr, res):
            outs = []
            new_res = res
            for (kind, off, sz), k in zip(plan, ks):
                if kind == "raw":
                    outs.append(jax.lax.dynamic_slice(arr, (off,), (sz,)))
                    continue
                if k <= 0:
                    outs.append((jnp.zeros((0,), jnp.int32),
                                 jnp.zeros((0,), jnp.float32)))
                    continue
                x = jax.lax.dynamic_slice(arr, (off,), (sz,))
                r = jax.lax.dynamic_slice(res, (off,), (sz,))
                idx, vals, seg_res = tkc.topk_with_residual(x, r, k)
                new_res = jax.lax.dynamic_update_slice(new_res, seg_res,
                                                       (off,))
                outs.append((idx, vals))
            return outs, new_res

        fn = jax.jit(jax.named_scope("codec")(run))
        _TOPK_GROUP_CACHE[key] = fn
    return fn


class TopKCompressor(PartialCompressor):
    """Magnitude top-k with per-sender error feedback.

    ``compiled=True`` (the ``make_compressor`` default) holds the residual
    as one DEVICE-RESIDENT (n,) array per (sender, group) and compresses
    every targeted span of a group buffer in one dispatch; ``compiled=False``
    is the eager per-span numpy reference (host residual dict).  Both obey
    the same tie rule (largest |x+res|, ties to the lower index, indices
    ascending) so their wire bytes are bit-identical."""

    def __init__(self, fraction: float = 0.01, entries: tuple = ("delta",),
                 compiled: bool = False):
        self.fraction = float(fraction)
        self.entries = tuple(entries)
        self.compiled = bool(compiled)
        # eager: span-keyed host residuals; compiled: group-keyed
        # device-resident residuals
        self._residual: Dict[str, Any] = {}

    def _k_of(self, n: int) -> int:
        return max(1, int(n * self.fraction)) if n else 0

    def state_dict(self) -> Dict[str, Any]:
        return {"kind": "topk",
                "residual": {k: np.asarray(v)
                             for k, v in self._residual.items()}}

    def load_state_dict(self, state: Optional[Dict[str, Any]]) -> None:
        self._residual = {} if not state else \
            {k: np.asarray(v) for k, v in state.get("residual", {}).items()}

    # --- eager reference --------------------------------------------------
    def _compress_array(self, a: np.ndarray, key: str) -> CompressedTensor:
        flat = np.asarray(a, np.float32).reshape(-1)
        res = self._residual.get(key)
        if res is not None and np.shape(res) == flat.shape:
            flat = flat + np.asarray(res, np.float32)
        k = self._k_of(flat.size)
        # stable sort on -|f|: largest magnitudes first, ties -> lower index
        # (the lax.top_k rule the fused kernel uses)
        order = np.argsort(-np.abs(flat), kind="stable")[:k]
        idx = np.sort(order).astype(np.int32)
        vals = flat[idx]
        new_res = flat.copy()
        new_res[idx] = 0.0                      # error feedback residual
        self._residual[key] = new_res
        return CompressedTensor("topk", tuple(np.shape(a)),
                                str(np.asarray(a).dtype),
                                {"idx": idx, "vals": vals})

    def _decompress_array(self, c: CompressedTensor) -> np.ndarray:
        flat = np.zeros(int(np.prod(c.shape)), np.float32)
        flat[np.asarray(c.data["idx"])] = np.asarray(c.data["vals"])
        return flat.reshape(c.shape)

    _compress = _compress_array
    _decompress = _decompress_array

    # --- compiled group path ---------------------------------------------
    def _group_compress(self, g: str, buf: Any, plan: tuple,
                        prefix: str) -> Dict[str, Any]:
        arr = jnp.asarray(buf, jnp.float32).reshape(-1)
        n = int(arr.shape[0])
        skey = f"{prefix}{g}"
        res = self._residual.get(skey)
        if res is None or tuple(np.shape(res)) != (n,):
            res = jnp.zeros((n,), jnp.float32)
        res = _colocate(jnp.asarray(res, jnp.float32), arr)
        shape_plan = tuple((kind, off, sz) for kind, off, sz, _ in plan)
        ks = tuple(self._k_of(sz) if kind == "comp" else 0
                   for kind, off, sz, _ in plan)
        _bump()
        outs, new_res = _topk_group_fn(n, shape_plan, ks)(arr, res)
        self._residual[skey] = new_res     # stays device-resident
        segments: List[Tuple[str, Any]] = []
        i = 0
        for kind, off, sz, _name in plan:
            if kind == "raw":
                segments.append(("raw", outs[i]))
            else:
                idx, vals = outs[i]
                segments.append(("comp", CompressedTensor(
                    "topk", (sz,), "float32", {"idx": idx, "vals": vals})))
            i += 1
        return {"__compressed__": True, "segments": segments, "size": n}


@jax.jit
@jax.named_scope("codec")
def _int8_quantize(f: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    scale = jnp.maximum(jnp.max(jnp.abs(f)) / 127.0, 1e-12)
    q = jnp.clip(jnp.round(f / scale), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


@jax.jit
@jax.named_scope("codec")
def _int8_dequantize(q: jnp.ndarray, scale: jnp.ndarray) -> jnp.ndarray:
    return q.astype(jnp.float32) * scale


_INT8_GROUP_CACHE: Dict[tuple, Any] = {}


def _int8_group_fn(n: int, plan: tuple):
    key = (n, plan)
    fn = _INT8_GROUP_CACHE.get(key)
    if fn is None:
        def run(arr):
            outs = []
            for kind, off, sz in plan:
                x = jax.lax.dynamic_slice(arr, (off,), (sz,))
                if kind == "raw":
                    outs.append(x)
                elif sz == 0:
                    outs.append((jnp.zeros((0,), jnp.int8),
                                 jnp.float32(1.0)))
                else:
                    # same ops as _int8_quantize, fused across the group
                    scale = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-12)
                    q = jnp.clip(jnp.round(x / scale), -127, 127) \
                        .astype(jnp.int8)
                    outs.append((q, scale.astype(jnp.float32)))
            return outs

        fn = jax.jit(jax.named_scope("codec")(run))
        _INT8_GROUP_CACHE[key] = fn
    return fn


class Int8Compressor(PartialCompressor):
    """Symmetric per-entry int8 quantisation with fp32 scale.

    ``compiled=True`` quantises every targeted span of a group buffer in one
    jitted dispatch and decompresses lazily into the fold; ``compiled=False``
    keeps the PR 5 one-jit-per-segment behaviour (the reference)."""

    def __init__(self, entries: tuple = ("delta",), compiled: bool = False):
        self.entries = tuple(entries)
        self.compiled = bool(compiled)

    def _compress_array(self, a: np.ndarray) -> CompressedTensor:
        if np.size(a) == 0:
            return CompressedTensor("int8", tuple(np.shape(a)),
                                    str(np.asarray(a).dtype),
                                    {"q": np.zeros(np.shape(a), np.int8),
                                     "scale": np.float32(1.0)})
        q, scale = _int8_quantize(jnp.asarray(a, jnp.float32))
        return CompressedTensor("int8", tuple(np.shape(a)),
                                str(getattr(a, "dtype", q.dtype)),
                                {"q": q, "scale": scale})

    def _decompress_array(self, c: CompressedTensor) -> np.ndarray:
        if np.size(c.data["q"]) == 0:
            return np.zeros(c.shape, np.float32)
        return _int8_dequantize(c.data["q"], c.data["scale"])

    def _compress(self, a: np.ndarray, key: str) -> CompressedTensor:
        return self._compress_array(a)

    def _decompress(self, c: CompressedTensor) -> np.ndarray:
        return self._decompress_array(c)

    def _group_compress(self, g: str, buf: Any, plan: tuple,
                        prefix: str) -> Dict[str, Any]:
        arr = jnp.asarray(buf, jnp.float32).reshape(-1)
        n = int(arr.shape[0])
        shape_plan = tuple((kind, off, sz) for kind, off, sz, _ in plan)
        _bump()
        outs = _int8_group_fn(n, shape_plan)(arr)
        segments: List[Tuple[str, Any]] = []
        for (kind, off, sz, _name), out in zip(plan, outs):
            if kind == "raw":
                segments.append(("raw", out))
            else:
                q, scale = out
                segments.append(("comp", CompressedTensor(
                    "int8", (sz,), "float32", {"q": q, "scale": scale})))
        return {"__compressed__": True, "segments": segments, "size": n}


def _psgd_shape(n: int, rank: int) -> Tuple[int, int, int]:
    """Near-square (rows, cols) factorisation of a flat span plus the
    effective rank (clipped so P/Q stay skinny)."""
    cols = max(1, int(math.ceil(math.sqrt(max(n, 1)))))
    rows = -(-n // cols)
    r = max(1, min(int(rank), rows, cols))
    return rows, cols, r


_PSGD_GROUP_CACHE: Dict[tuple, Any] = {}


def _psgd_group_fn(n: int, plan: tuple, shapes: tuple):
    """One power-iteration step per targeted span, batched over the group:
    M = reshape(x + res); P = orth(M @ Q); Q' = Mᵀ P; residual = x+res −
    unravel(P Q'ᵀ).  Q' warm-starts the next round (subspace iteration:
    repeated rounds converge Q toward the top singular subspace)."""
    key = (n, plan, shapes)
    fn = _PSGD_GROUP_CACHE.get(key)
    if fn is None:
        def run(arr, states):
            outs = []
            new_states = []
            si = 0
            for kind, off, sz in plan:
                seg = jax.lax.dynamic_slice(arr, (off,), (sz,))
                if kind == "raw":
                    outs.append(seg)
                    continue
                rows, cols, _r = shapes[si]
                q0, res = states[si]
                si += 1
                f = seg + res
                m = f if rows * cols == sz else \
                    jnp.pad(f, (0, rows * cols - sz))
                m = m.reshape(rows, cols)
                p = jnp.linalg.qr(m @ q0)[0]       # orthonormalise P
                q1 = m.T @ p
                approx = (p @ q1.T).reshape(-1)[:sz]
                outs.append((p, q1))
                new_states.append((q1, f - approx))
            return outs, new_states

        fn = jax.jit(jax.named_scope("codec")(run))
        _PSGD_GROUP_CACHE[key] = fn
    return fn


class PowerSGDCompressor(PartialCompressor):
    """PowerSGD-style low-rank compression of the flat group buffers.

    Each targeted span reshapes to a near-square (rows, cols) matrix M of
    the residual-corrected update; one warm-started power-iteration step
    gives ``P = orth(M Q)`` (rows×r) and ``Q' = Mᵀ P`` (cols×r), and the
    wire carries P and Q' — O((rows+cols)·r) instead of O(rows·cols).  The
    decoded update is ``P Q'ᵀ``; the approximation error feeds back into the
    next round's residual, and Q' warm-starts the next iteration so the
    factors track the top singular subspace across rounds.  State (Q, res)
    is keyed per (sender, group, entry) like the top-k residuals.  Always
    compiled: every span of a group runs in one jitted dispatch."""

    def __init__(self, rank: int = 4, entries: tuple = ("delta",),
                 seed: int = 0):
        self.rank = int(max(1, rank))
        self.entries = tuple(entries)
        self.seed = int(seed)
        self.compiled = True
        self._state: Dict[str, Dict[str, Any]] = {}

    def _init_q(self, skey: str, cols: int, r: int) -> jnp.ndarray:
        # deterministic per span-key: a resume-from-scratch re-derives the
        # identical init, and distinct senders/entries decorrelate
        k = jax.random.PRNGKey((zlib.crc32(skey.encode()) ^ self.seed)
                               & 0x7FFFFFFF)
        return jax.random.normal(k, (cols, r), jnp.float32)

    def state_dict(self) -> Dict[str, Any]:
        return {"kind": "powersgd",
                "state": {k: {"q": np.asarray(v["q"]),
                              "res": np.asarray(v["res"])}
                          for k, v in self._state.items()}}

    def load_state_dict(self, state: Optional[Dict[str, Any]]) -> None:
        self._state = {} if not state else \
            {k: {"q": np.asarray(v["q"]), "res": np.asarray(v["res"])}
             for k, v in state.get("state", {}).items()}

    # --- eager per-array reference (legacy nested path) -------------------
    def _compress(self, a: np.ndarray, key: str) -> CompressedTensor:
        flat = jnp.asarray(a, jnp.float32).reshape(-1)
        n = int(flat.shape[0])
        if n == 0:
            return CompressedTensor("powersgd", tuple(np.shape(a)),
                                    "float32",
                                    {"p": np.zeros((0, 1), np.float32),
                                     "q": np.zeros((0, 1), np.float32)})
        rows, cols, r = _psgd_shape(n, self.rank)
        st = self._state.get(key)
        if st is None or tuple(np.shape(st["q"])) != (cols, r):
            st = {"q": self._init_q(key, cols, r),
                  "res": jnp.zeros((n,), jnp.float32)}
        fn = _psgd_group_fn(n, (("comp", 0, n),), ((rows, cols, r),))
        _bump()
        outs, new_states = fn(flat, ((jnp.asarray(st["q"], jnp.float32),
                                      jnp.asarray(st["res"], jnp.float32)),))
        p, q = outs[0]
        self._state[key] = {"q": new_states[0][0], "res": new_states[0][1]}
        return CompressedTensor("powersgd", tuple(np.shape(a)), "float32",
                                {"p": p, "q": q})

    def _decompress(self, c: CompressedTensor) -> np.ndarray:
        p = np.asarray(c.data["p"], np.float32)
        q = np.asarray(c.data["q"], np.float32)
        n = int(np.prod(c.shape))
        return (p @ q.T).reshape(-1)[:n].reshape(c.shape)

    # --- compiled group path ---------------------------------------------
    def _group_compress(self, g: str, buf: Any, plan: tuple,
                        prefix: str) -> Dict[str, Any]:
        arr = jnp.asarray(buf, jnp.float32).reshape(-1)
        n = int(arr.shape[0])
        # degrade empty targeted spans to raw: nothing to factorise
        plan = tuple(("raw", off, sz, None) if kind == "comp" and sz == 0
                     else (kind, off, sz, name)
                     for kind, off, sz, name in plan)
        shapes: List[tuple] = []
        states: List[tuple] = []
        for kind, off, sz, name in plan:
            if kind != "comp":
                continue
            rows, cols, r = _psgd_shape(sz, self.rank)
            shapes.append((rows, cols, r))
            skey = f"{prefix}{g}/{name}"
            st = self._state.get(skey)
            if st is None or tuple(np.shape(st["q"])) != (cols, r):
                st = {"q": self._init_q(skey, cols, r),
                      "res": jnp.zeros((sz,), jnp.float32)}
            states.append((_colocate(jnp.asarray(st["q"], jnp.float32), arr),
                           _colocate(jnp.asarray(st["res"], jnp.float32),
                                     arr)))
        shape_plan = tuple((kind, off, sz) for kind, off, sz, _ in plan)
        _bump()
        outs, new_states = _psgd_group_fn(n, shape_plan, tuple(shapes))(
            arr, tuple(states))
        segments: List[Tuple[str, Any]] = []
        i = 0
        si = 0
        for kind, off, sz, name in plan:
            if kind == "raw":
                segments.append(("raw", outs[i]))
            else:
                p, q = outs[i]
                self._state[f"{prefix}{g}/{name}"] = \
                    {"q": new_states[si][0], "res": new_states[si][1]}
                si += 1
                segments.append(("comp", CompressedTensor(
                    "powersgd", (sz,), "float32", {"p": p, "q": q})))
            i += 1
        return {"__compressed__": True, "segments": segments, "size": n}


def _wire_bytes(sums: Dict) -> int:
    if is_flat_sums(sums):
        tot = 0
        for buf in sums["buffers"].values():
            if is_compressed_buffer(buf):
                tot += sum(int(x.nbytes) for _, x in buf["segments"])
            else:
                # flat buffers are normally fp32 arrays, but hand-built
                # partials may carry python lists/scalars — bill those at
                # the fp32 default like the nested path below
                tot += int(np.prod(np.shape(buf))) * int(np.dtype(
                    getattr(buf, "dtype", np.float32)).itemsize)
        return tot
    tot = 0
    for v in sums.values():
        if isinstance(v, dict) and v.get("__compressed__"):
            tot += sum(c.nbytes for c in v["leaves"])
        else:
            # uncompressed leaves ship at their REAL itemsize: a flat 4
            # over-billed bf16/fp16 payloads 2x (python scalars keep the
            # historical 4-byte accounting)
            tot += sum(int(np.prod(np.shape(l)))
                       * np.dtype(getattr(l, "dtype", np.float32)).itemsize
                       for l in jax.tree.leaves(v))
    return tot


def make_compressor(kind: str, arg: Optional[float] = None, *,
                    entries: tuple = ("delta",),
                    rank: Optional[int] = None,
                    compiled: bool = True, seed: int = 0):
    """Build a compressor by name.

    ``arg`` keeps its historical meaning (top-k fraction, default 0.01; for
    "powersgd" it doubles as the rank when ``rank=`` is not given).
    ``entries=`` targets extra reducible entries beyond "delta" (e.g.
    SCAFFOLD's control variates: ``entries=("delta", "delta_c")``).
    ``compiled=False`` selects the eager per-segment reference paths for
    topk/int8 (PowerSGD is only implemented compiled)."""
    if not kind or kind == "none":
        return None
    if kind == "topk":
        return TopKCompressor(fraction=0.01 if arg is None else float(arg),
                              entries=entries, compiled=compiled)
    if kind == "int8":
        return Int8Compressor(entries=entries, compiled=compiled)
    if kind == "powersgd":
        r = int(rank if rank is not None else (arg if arg else 4))
        return PowerSGDCompressor(rank=r, entries=entries, seed=seed)
    raise ValueError(kind)
