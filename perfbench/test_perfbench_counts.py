"""The benchmark's yardstick on the CPU: FLOP and byte counts against hand
counts, the peaks table, the weights' layout against the program's, the
benchmark file's shape, and the measurement path's refusal of a backend
that is not a TPU."""
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import harness, modelcfg, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

TINY = modelcfg.Dims(layers=2, d=8, heads=2, kv_heads=1, head_dim=4,
                     ffn=16, vocab=32, tied=True, qkv_bias=True, window=0,
                     rope_theta=1e4, eps=1e-6, dtype="bfloat16")


def test_flops_per_token_hand_count():
    # per layer: wq 8x8, wk 8x4, wv 8x4, wo 8x8 = 192; MLP 3x8x16 = 384;
    # two layers 1152, head 8x32 = 256 -> 1408 matmul weights
    assert modelcfg.matmul_params(TINY) == 1408
    # causal attention at S=4: (4+1)/2 = 2.5 keys a query on average;
    # 12 x 2 layers x 2 heads x 4 dims x 2.5 = 480
    assert modelcfg.train_flops_per_token(TINY, 4) == 6 * 1408 + 480


def test_flops_window_counts_only_keys_in_the_window():
    m = TINY.__class__(**{**TINY.__dict__, "window": 2})
    # S=4, window 2: queries attend 1, 2, 2, 2 keys -> 7/4 on average
    assert modelcfg.train_flops_per_token(m, 4) == pytest.approx(
        6 * 1408 + 12 * 2 * 2 * 4 * 7 / 4)


def test_fold_bytes_from_shapes():
    n = modelcfg.n_params(TINY)
    # embed 256 + 2 x (192 + biases 16 + MLP 384 + norms 16) + final 8
    assert n == 256 + 2 * (192 + 16 + 384 + 16) + 8
    assert modelcfg.fold_bytes(TINY, 3) == 3 * n * 2 + 8 * n


@pytest.mark.parametrize("name", ["qwen2-0.5b", "phi3-mini-3.8b-4l"])
def test_config_counts_match_the_program(name):
    cfgd = modelcfg.load_config(name)
    m = modelcfg.dims(cfgd)
    assert modelcfg.n_params(m) == cfgd["n_params"]
    assert sum(int(np.prod(s)) for s, _ in weights.shapes(m).values()) \
        == cfgd["n_params"]
    from repro.launch import train
    args = train.parse_args(harness.train_argv(
        json.load(open(os.path.join(HERE, "traffic", "fedavg-c4.json"))),
        cfgd["program_arch"], 0))
    cfg = harness.program_config(train, args, cfgd, m)
    assert cfg.n_params() == cfgd["n_params"]
    shapes = jax.eval_shape(lambda k: weights.make(m, k), jax.random.key(0))
    harness.check_layout(shapes, cfg)


def test_weights_follow_the_seed():
    a = jax.jit(lambda k: weights.make(TINY, k))(weights.seed_key(2**31 + 5))
    b = jax.jit(lambda k: weights.make(TINY, k))(weights.seed_key(2**31 + 5))
    c = jax.jit(lambda k: weights.make(TINY, k))(
        weights.seed_key(2**31 + 5 + 2**32))
    la, lb, lc = (jax.tree.leaves(t) for t in (a, b, c))
    assert all(np.array_equal(x, y) for x, y in zip(la, lb))
    assert not np.array_equal(np.asarray(a["embed"]["w"], np.float32),
                              np.asarray(c["embed"]["w"], np.float32))
    assert a["embed"]["w"].dtype == jax.numpy.bfloat16


def test_peaks_table_keyed_by_device_kind():
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        harness.peaks_for("cpu")


def test_measurement_path_refuses_a_backend_that_is_not_a_tpu():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "qwen2-0.5b.fedavg-c4", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert p.stdout.strip() == ""


def test_benchmark_file_names_files_that_exist():
    b = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = set()
    for c in b["configs"]:
        assert NAME.match(c["name"]) and os.path.exists(
            os.path.join(ROOT, c["file"]))
        cfgd = json.load(open(os.path.join(ROOT, c["file"])))
        assert cfgd["reduced"] == c["reduced"]
        names.add(c["name"])
    traffics = {w["traffic"] for w in b["workloads"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and w["config"] in names
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert os.path.exists(os.path.join(HERE, "limits",
                                           f"{w['name']}.json"))
    for t in traffics:
        assert os.path.exists(os.path.join(HERE, "traffic", f"{t}.json"))
    metrics = b["end_to_end"] + b["per_layer"]
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}
    for m in metrics:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert os.path.exists(os.path.join(HERE, "metrics",
                                           f"{m['name']}.py"))
    for m in b["per_layer"]:
        assert m["moves"] in {e["name"] for e in b["end_to_end"]}
