"""The dense family (``families/dense.py``) on the CPU: its FLOP and byte
counts against hand counts, its weights from the seed, and its weights and
reference round pinned bit for bit to what they were before the model moved
into a family module."""
import hashlib

import jax
import numpy as np
import pytest

from perfbench import harness, modelcfg, weights

dense = modelcfg.load_family({"family": "dense"})

TINY = dense.Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4, ffn=16,
                  vocab=32, tied=True, qkv_bias=True, window=0,
                  rope_theta=1e4, eps=1e-6, dtype="bfloat16")

# sha256 of every leaf (path, dtype, shape, bytes) of the tiny cell's
# weights and of the reference's parameters after its first round, from
# this seed, as the tree before the family modules computed them on the CPU
PIN_SEED = 2**31 + 29
PIN_WEIGHTS = ("98bbf5427cedebb24eb1b696524e0929"
               "f25fa6e01d00ee2cf346f6a3a39aa347")
PIN_ROUND1 = ("3b5f15ec1245237490dba63d22ee474d"
              "bdf1651e2de2c312d0c3ea67d8987b71")


def _digest(tree) -> str:
    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(jax.device_get(leaf))
        h.update(jax.tree_util.keystr(path).encode())
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_flops_per_token_hand_count():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; MLP 3x8x16 = 384;
    # two layers 1152, head 8x32 = 256 -> 1408 matmul weights
    assert dense.matmul_params(TINY) == 1408
    # causal attention at S=4: (4+1)/2 = 2.5 keys a query on average;
    # 12 x 2 layers x 2 heads x 4 dims x 2.5 = 480
    assert dense.train_flops_per_token(TINY, 4) == 6 * 1408 + 480


def test_flops_window_counts_only_keys_in_the_window():
    m = TINY.__class__(**{**TINY.__dict__, "window": 2})
    # S=4, window 2: queries attend 1, 2, 2, 2 keys -> 7/4 on average
    assert dense.train_flops_per_token(m, 4) == pytest.approx(
        6 * 1408 + 12 * 2 * 2 * 4 * 7 / 4)


def test_fold_bytes_from_shapes():
    n = dense.n_params(TINY)
    # embed 256 + 2 x (192 + biases 16 + MLP 384 + norms 16) + final 8
    assert n == 256 + 2 * (192 + 16 + 384 + 16) + 8
    assert sum(int(np.prod(s)) for s, _ in dense.shapes(TINY).values()) == n
    assert modelcfg.fold_bytes(n, 3) == 3 * n * 2 + 8 * n


def test_weights_follow_the_seed():
    a = weights.make_on_device(dense, TINY, 2**31 + 5)
    b = weights.make_on_device(dense, TINY, 2**31 + 5)
    c = weights.make_on_device(dense, TINY, 2**31 + 5 + 2**32)
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(np.asarray(a["embed"]["w"], np.float32),
                              np.asarray(c["embed"]["w"], np.float32))
    assert a["embed"]["w"].dtype == jax.numpy.bfloat16


def test_weights_are_the_same_bits_as_before_the_family(tiny_cell):
    family, m = harness.model_of(tiny_cell())
    assert family is dense
    assert _digest(weights.make_on_device(family, m, PIN_SEED)) \
        == PIN_WEIGHTS


def test_a_reference_round_is_the_same_bits_as_before_the_family(tiny_cell):
    cell = tiny_cell()
    family, m = harness.model_of(cell)
    streams = harness.streams_of(cell, m, PIN_SEED)
    ref = harness.reference_rounds(cell, family, m, PIN_SEED, streams, 1,
                                   float(cell.traffic["lr"]))
    assert _digest(ref[0]) == PIN_WEIGHTS
    assert _digest(ref[1]) == PIN_ROUND1
