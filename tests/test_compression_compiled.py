"""Compiled codec equivalence suite (DESIGN.md §7): the one-dispatch group
codecs match their eager references bit for bit (tie rule: largest
|x+residual|, exact ties to the LOWER index, indices ascending), the fused
decompress-into-fold equals densify-then-add, PowerSGD round-trip error
shrinks with rank, and codec dispatches stay O(groups) per partial."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.aggregation import (Op, merge_partials, scale_partial,
                                    wire_bytes)
from repro.core.compression import (CompressedTensor, Int8Compressor,
                                    PowerSGDCompressor, TopKCompressor,
                                    _wire_bytes, codec_dispatch_count,
                                    densify_buffer, fold_buffer_into,
                                    make_compressor,
                                    reset_codec_dispatch_count, scale_buffer)
from repro.core.flat import FlatLayout, flat_sums, is_compressed_buffer
from repro.kernels import ops as kops
from repro.kernels import topk_compress as tkc

RNG = np.random.default_rng(7)

# "skip" sits between the targeted "delta" and "aux" spans so every plan
# exercises comp -> raw -> comp segment interleaving; "cnt" lives in the
# unit group (SUM) to give compress a second group buffer
OPS = {"delta": Op.WEIGHTED_AVG, "skip": Op.WEIGHTED_AVG,
       "aux": Op.WEIGHTED_AVG, "cnt": Op.SUM}


def _payload(seed=0):
    r = np.random.default_rng(seed)
    return {"delta": {"w": jnp.asarray(r.normal(size=(40, 7)), jnp.float32),
                      "b": jnp.asarray(r.normal(size=(7,)), jnp.float32)},
            "skip": jnp.asarray(r.normal(size=(33,)), jnp.float32),
            "aux": jnp.asarray(r.normal(size=(55,)), jnp.float32),
            "cnt": jnp.asarray(r.normal(size=(5,)), jnp.float32)}


LAYOUT = FlatLayout.build(OPS, _payload())


def _partial(seed=0):
    bufs = LAYOUT.flatten(_payload(seed))
    return {"sums": flat_sums(dict(bufs)), "layout": LAYOUT,
            "weights": {k: 1.0 for k in OPS},
            "counts": {k: 1 for k in OPS},
            "collected": {}, "n_clients": 1}


def _np(x):
    return np.asarray(x)


# ---------------------------------------------------------------------------
# fused top-k
# ---------------------------------------------------------------------------

def test_fused_topk_wrapper_single_dispatch_semantics():
    r = np.random.default_rng(4)
    x = jnp.asarray(r.normal(size=(128,)), jnp.float32)
    res = jnp.zeros((128,), jnp.float32)
    idx, vals, new_res = kops.fused_topk(x, res, k=16)
    # idx ascending, residual zeroed exactly at idx, untouched elsewhere
    assert np.all(np.diff(_np(idx)) > 0)
    assert np.array_equal(_np(vals), _np(x)[_np(idx)])
    assert np.all(_np(new_res)[_np(idx)] == 0.0)
    mask = np.ones(128, bool)
    mask[_np(idx)] = False
    assert np.array_equal(_np(new_res)[mask], _np(x)[mask])


def test_topk_tie_semantics_lower_index_wins():
    """Documented tie rule: equal |value| -> the LOWER index is selected
    (lax.top_k stability; the eager reference uses a stable argsort)."""
    x = jnp.asarray([2.0, -2.0, 2.0, 1.0], jnp.float32)
    idx, vals, _ = tkc.topk_with_residual(x, jnp.zeros(4), 2)
    assert list(_np(idx)) == [0, 1]
    assert list(_np(vals)) == [2.0, -2.0]
    # eager compressor agrees
    c = TopKCompressor(fraction=0.5, compiled=False)
    ct = c._compress_array(np.asarray(x), "t")
    assert list(ct.data["idx"]) == [0, 1]
    assert list(ct.data["vals"]) == [2.0, -2.0]


# ---------------------------------------------------------------------------
# compiled vs eager group codecs
# ---------------------------------------------------------------------------

def test_compiled_topk_matches_eager_bit_for_bit():
    """Three rounds of residual accrual: the one-dispatch group codec and
    the per-span eager reference must emit identical wire bytes (indices,
    values, raw segments) AND identical decoded buffers every round."""
    eager = TopKCompressor(0.25, entries=("delta", "aux"), compiled=False)
    comp = make_compressor("topk", 0.25, entries=("delta", "aux"))
    assert comp.compiled
    for rnd in range(3):
        pe = eager.compress_partial(_partial(rnd), key="exec0")
        pc = comp.compress_partial(_partial(rnd), key="exec0")
        assert pe["_wire_bytes"] == pc["_wire_bytes"]
        for g, be in pe["sums"]["buffers"].items():
            bc = pc["sums"]["buffers"][g]
            if not is_compressed_buffer(be):
                assert np.array_equal(_np(be), _np(bc))
                continue
            for (ke, xe), (kc, xc) in zip(be["segments"], bc["segments"]):
                assert ke == kc
                if ke == "raw":
                    assert np.array_equal(_np(xe), _np(xc))
                else:
                    assert np.array_equal(_np(xe.data["idx"]),
                                          _np(xc.data["idx"]))
                    assert np.array_equal(_np(xe.data["vals"]),
                                          _np(xc.data["vals"]))
        de = eager.decompress_partial(pe)["sums"]["buffers"]
        dc = comp.decompress_partial(pc)["sums"]["buffers"]
        for g in de:
            dcb = densify_buffer(dc[g]) if is_compressed_buffer(dc[g]) \
                else dc[g]
            assert np.array_equal(_np(de[g]), _np(dcb))


def test_compiled_int8_matches_eager_bit_for_bit():
    eager = Int8Compressor(entries=("delta", "aux"), compiled=False)
    comp = make_compressor("int8", entries=("delta", "aux"))
    pe = eager.compress_partial(_partial(5))
    pc = comp.compress_partial(_partial(5))
    assert pe["_wire_bytes"] == pc["_wire_bytes"]
    de = eager.decompress_partial(pe)["sums"]["buffers"]["weighted"]
    dc = densify_buffer(
        comp.decompress_partial(pc)["sums"]["buffers"]["weighted"])
    assert np.array_equal(_np(de), _np(dc))


def test_compiled_decompress_is_lazy():
    comp = make_compressor("topk", 0.25)
    wire = comp.compress_partial(_partial(1), key="e")
    out = comp.decompress_partial(wire)
    assert is_compressed_buffer(out["sums"]["buffers"]["weighted"])


# ---------------------------------------------------------------------------
# fused decompress-into-fold / scale
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["topk", "int8", "powersgd"])
def test_fold_buffer_into_matches_densify_add(kind):
    comp = make_compressor(kind, 0.25, rank=3)
    buf = comp.compress_partial(_partial(2), key="e")["sums"]["buffers"][
        "weighted"]
    acc = jnp.asarray(RNG.normal(size=(int(buf["size"]),)), jnp.float32)
    got = _np(fold_buffer_into(acc, buf))
    want = _np(acc + densify_buffer(buf))
    if kind == "topk":
        # scatter-add has no multiply: bitwise equal to densify-then-add
        assert np.array_equal(got, want)
    else:
        # int8/powersgd decode multiplies inside the fold jit; XLA may
        # contract the mul+add into an FMA (single rounding), so agreement
        # is to the ulp, not the bit
        assert np.allclose(got, want, rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("kind", ["topk", "int8", "powersgd"])
def test_scale_buffer_matches_dense_scale(kind):
    comp = make_compressor(kind, 0.25, rank=3)
    buf = comp.compress_partial(_partial(3), key="e")["sums"]["buffers"][
        "weighted"]
    got = densify_buffer(scale_buffer(buf, 0.25))
    assert np.allclose(_np(got), 0.25 * _np(densify_buffer(buf)),
                       rtol=1e-6, atol=1e-7)


def test_merge_and_scale_partial_consume_compressed_wire():
    """The async fold path end-to-end on compressed partials: gamma-scale,
    merge-into-None (densify) and merge-into-acc (fused fold) agree with
    the dense reference."""
    comp = make_compressor("topk", 0.25)
    w1 = comp.compress_partial(_partial(10), key="e0")
    w2 = comp.compress_partial(_partial(11), key="e1")
    dense1 = {g: (densify_buffer(b) if is_compressed_buffer(b) else b)
              for g, b in w1["sums"]["buffers"].items()}
    dense2 = {g: (densify_buffer(b) if is_compressed_buffer(b) else b)
              for g, b in w2["sums"]["buffers"].items()}
    acc = merge_partials(None, scale_partial(w1, 0.5))
    acc = merge_partials(acc, w2)
    for g in dense1:
        want = 0.5 * _np(dense1[g]) + _np(dense2[g])
        assert np.allclose(_np(acc["sums"]["buffers"][g]), want,
                           rtol=1e-6, atol=1e-6)
    assert acc["n_clients"] == 2


# ---------------------------------------------------------------------------
# dispatch accounting: O(groups), not O(segments)
# ---------------------------------------------------------------------------

def test_codec_dispatches_are_per_group_not_per_segment():
    comp = make_compressor("topk", 0.25, entries=("delta", "aux", "cnt"))
    comp.compress_partial(_partial(0), key="warm")   # compile outside count
    reset_codec_dispatch_count()
    wire = comp.compress_partial(_partial(1), key="warm")
    # 3 targeted entries across 2 groups (weighted: delta+aux; unit: cnt)
    # -> exactly 2 compress dispatches
    assert codec_dispatch_count() == 2
    reset_codec_dispatch_count()
    for b in wire["sums"]["buffers"].values():
        if is_compressed_buffer(b):
            densify_buffer(b)
    assert codec_dispatch_count() == 2               # one decode per group
    reset_codec_dispatch_count()
    acc = merge_partials(None, wire)                 # densify per group
    merge_partials(acc, comp.compress_partial(_partial(2), key="warm2"))
    # 2 densify + 2 compress + 2 fused folds
    assert codec_dispatch_count() == 6


# ---------------------------------------------------------------------------
# PowerSGD
# ---------------------------------------------------------------------------

def test_powersgd_roundtrip_error_decreases_with_rank():
    bufs = LAYOUT.flatten(_payload(42))
    target = _np(bufs["weighted"])
    errs = []
    for r in (1, 4, 16):
        comp = make_compressor("powersgd", rank=r)
        wire = comp.compress_partial(_partial(42), key="e")
        dense = _np(densify_buffer(wire["sums"]["buffers"]["weighted"]))
        errs.append(float(np.linalg.norm(dense - target)))
    assert errs[1] < errs[0]
    assert errs[2] < errs[1]


def test_powersgd_warm_start_converges_on_fixed_matrix():
    """Warm-start semantics: with the residual zeroed each round (isolating
    the subspace iteration), re-compressing the SAME matrix must shrink the
    approximation error monotonically — Q tracks the top singular
    subspace."""
    comp = make_compressor("powersgd", rank=2)
    target = _np(LAYOUT.flatten(_payload(9))["weighted"])
    errs = []
    for _ in range(5):
        for k in comp._state:
            comp._state[k]["res"] = np.zeros_like(
                np.asarray(comp._state[k]["res"]))
        wire = comp.compress_partial(_partial(9), key="e")
        dense = _np(densify_buffer(wire["sums"]["buffers"]["weighted"]))
        errs.append(float(np.linalg.norm(dense - target)))
    assert all(b <= a for a, b in zip(errs, errs[1:]))
    assert errs[-1] < errs[0]
    # state is per (sender, group, entry)
    assert any(k.startswith("e/") for k in comp._state)


def test_powersgd_error_feedback_is_unbiased_in_the_long_run():
    """Error feedback: each round's decode approximates x + residual, so the
    RUNNING AVERAGE of decodes telescopes to x - res_T/T — the averaged
    error must fall well below the single-shot error."""
    comp = make_compressor("powersgd", rank=2)
    target = _np(LAYOUT.flatten(_payload(9))["weighted"])
    acc, errs = None, []
    for t in range(1, 13):
        wire = comp.compress_partial(_partial(9), key="e")
        dense = _np(densify_buffer(wire["sums"]["buffers"]["weighted"]))
        acc = dense if acc is None else acc + dense
        errs.append(float(np.linalg.norm(acc / t - target)))
    assert errs[-1] < 0.5 * errs[0]


def test_powersgd_wire_is_p_plus_q_bytes():
    comp = make_compressor("powersgd", rank=4)
    wire = comp.compress_partial(_partial(6), key="e")
    buf = wire["sums"]["buffers"]["weighted"]
    seg = [x for k, x in buf["segments"] if k == "comp"]
    assert len(seg) == 1 and seg[0].kind == "powersgd"
    p, q = seg[0].data["p"], seg[0].data["q"]
    raw = sum(int(np.prod(np.shape(x))) * 4
              for k, x in buf["segments"] if k == "raw")
    n_unit = int(LAYOUT.group_sizes["unit"]) * 4
    assert wire["_wire_bytes"] == int(p.nbytes) + int(q.nbytes) + raw + n_unit
    # low-rank actually compresses the targeted span
    span = LAYOUT.spans["delta"]
    assert int(p.nbytes) + int(q.nbytes) < span.size * 4


# ---------------------------------------------------------------------------
# make_compressor signature (satellite regression)
# ---------------------------------------------------------------------------

def test_make_compressor_passes_entries_and_rank_through():
    c = make_compressor("topk", 0.1, entries=("delta", "delta_c"))
    assert c.fraction == 0.1 and c.entries == ("delta", "delta_c")
    c = make_compressor("int8", entries=("delta", "delta_c"))
    assert c.entries == ("delta", "delta_c")
    c = make_compressor("powersgd", rank=7, entries=("delta", "delta_c"))
    assert c.rank == 7 and c.entries == ("delta", "delta_c")
    assert make_compressor("powersgd", 8).rank == 8    # arg doubles as rank
    assert make_compressor("none") is None
    legacy = make_compressor("topk", 0.1, compiled=False)
    assert not legacy.compiled


def test_extra_entries_compress_scaffold_style_payload():
    """SCAFFOLD-style payloads carry a second reducible entry (the control
    variate delta); entries= must compress BOTH spans."""
    ops = {"delta": Op.WEIGHTED_AVG, "delta_c": Op.AVG}
    payload = {"delta": jnp.asarray(RNG.normal(size=(64,)), jnp.float32),
               "delta_c": jnp.asarray(RNG.normal(size=(64,)), jnp.float32)}
    layout = FlatLayout.build(ops, payload)
    partial = {"sums": flat_sums(dict(layout.flatten(payload))),
               "layout": layout, "weights": {"delta": 1.0, "delta_c": 1.0},
               "counts": {k: 1 for k in ops}, "collected": {},
               "n_clients": 1}
    both = make_compressor("topk", 0.1, entries=("delta", "delta_c"))
    only = make_compressor("topk", 0.1)
    wb = both.compress_partial(partial, key="e")["_wire_bytes"]
    wo = only.compress_partial(partial, key="e")["_wire_bytes"]
    assert wb < wo                      # the second span got compressed too


# ---------------------------------------------------------------------------
# wire accounting hardening (satellite)
# ---------------------------------------------------------------------------

def test_wire_bytes_flat_tolerates_plain_buffers():
    sums = flat_sums({"weighted": [1.0, 2.0, 3.0], "unit": 2.5})
    # python list/scalar buffers bill at the fp32 default, like nested
    assert _wire_bytes(sums) == 3 * 4 + 4


def test_wire_bytes_of_compressed_partial_counts_compressed_sums():
    comp = make_compressor("topk", 0.1)
    wire = comp.compress_partial(_partial(8), key="e")
    dense = wire_bytes(_partial(8))
    assert 0 < wire_bytes(wire) < dense


# ---------------------------------------------------------------------------
# end-to-end through the engines
# ---------------------------------------------------------------------------

def _loss_fn(params, batch):
    logits = batch["x"] @ params["w"] + params["b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(lse - gold)


GRAD_FN = jax.jit(jax.value_and_grad(_loss_fn))


def _server(compressor, engine="bsp", seed=7):
    import tempfile
    from repro.core import (ClientStateManager, LinkProfile, NetworkModel,
                            ParrotServer, SequentialExecutor, TickTimer,
                            make_algorithm)
    from repro.data import make_classification_clients
    data = make_classification_clients(16, dim=8, n_classes=4,
                                       mean_samples=20, batch_size=10,
                                       seed=1)
    algo = make_algorithm("fedavg", GRAD_FN, 0.1)
    sm = ClientStateManager(tempfile.mkdtemp())
    # deterministic virtual timing: schedules must match across the eager
    # and compiled runs for the bit-exactness comparison to be meaningful
    execs = [SequentialExecutor(k, algo, state_manager=sm,
                                speed_model=lambda kk, r: 0.0,
                                timer=TickTimer(1.0)) for k in range(3)]
    opts = {"chunk_size": 2} if engine != "bsp" else None
    # a uniform (deterministic) network so the achieved wire ratio is
    # actually measured at the ship sites (comm-free runs never price it)
    net = NetworkModel({c: LinkProfile(4e4, 8e4, 0.05) for c in range(16)})
    return ParrotServer(params={"w": jnp.zeros((8, 4)),
                                "b": jnp.zeros((4,))},
                        algorithm=algo, executors=execs, data_by_client=data,
                        clients_per_round=6, seed=seed, round_engine=engine,
                        engine_opts=opts, compressor=compressor, network=net)


@pytest.mark.parametrize("engine", ["bsp", "semi-sync", "async"])
def test_engines_eager_vs_compiled_topk_bit_exact(engine):
    """Full server runs under eager vs compiled top-k land on identical
    params: same wire bytes per round (bit-equal segments) and a fold path
    whose arithmetic matches the eager decompress-then-add exactly (the
    top-k fold is a scatter-add of the same values in the same order)."""
    a = _server(TopKCompressor(0.25, compiled=False), engine)
    b = _server(make_compressor("topk", 0.25), engine)
    for _ in range(3):
        a.run_round()
        b.run_round()
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        assert np.array_equal(_np(x), _np(y))
    assert a._wire_ratio == b._wire_ratio


def test_engine_runs_under_powersgd():
    # rank 2: P+Q = 2*(cols*r) = 24 floats < the 36-float weighted group —
    # at rank 4 the low-rank factors would EXPAND this tiny model's wire
    srv = _server(make_compressor("powersgd", rank=2), "async")
    for _ in range(3):
        srv.run_round()
    assert all(np.isfinite(_np(l)).all()
               for l in jax.tree.leaves(srv.params))
    assert 0.0 < srv._wire_ratio < 1.0


def test_server_accepts_compressor_string():
    srv = _server("topk", "bsp")
    assert isinstance(srv.compressor, TopKCompressor)
    assert srv.compressor.compiled
    srv.run_round()


# ---------------------------------------------------------------------------
# pytree plumbing
# ---------------------------------------------------------------------------

def test_compressed_tensor_is_a_pytree_node():
    ct = CompressedTensor("topk", (4,), "float32",
                          {"idx": np.asarray([0, 2], np.int32),
                           "vals": np.asarray([1.0, -1.0], np.float32)})
    leaves = jax.tree.leaves(ct)
    assert len(leaves) == 2
    back = jax.tree.map(lambda x: x, ct)
    assert isinstance(back, CompressedTensor) and back.kind == "topk"
    assert sum(x.nbytes for x in leaves) == ct.nbytes
