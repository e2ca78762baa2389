"""The benchmark's yardstick on the CPU: every configuration's counts and
weight layout against the program's, through its family, the peaks table,
the benchmark file's shape, and the measurement path's refusal of a backend
that is not a TPU."""
import json
import os
import re
import subprocess
import sys

import jax
import numpy as np
import pytest

from perfbench import harness, modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")

CONFIGS = sorted(f[:-len(".json")] for f in os.listdir(
    os.path.join(HERE, "configs")) if f.endswith(".json"))


@pytest.mark.parametrize("name", CONFIGS)
def test_config_counts_match_the_program(name):
    cfgd = modelcfg.load_config(name)
    family = modelcfg.load_family(cfgd)
    m = family.dims(cfgd)
    assert family.n_params(m) == cfgd["n_params"]
    shapes = jax.eval_shape(lambda k: family.make(m, k), jax.random.key(0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == cfgd["n_params"]
    from repro.launch import train
    args = train.parse_args(harness.train_argv(
        json.load(open(os.path.join(HERE, "traffic", "fedavg-c4.json"))),
        cfgd["program_arch"], 0))
    cfg = harness.program_config(train, args, cfgd, family, m)
    assert cfg.n_params() == cfgd["n_params"]
    harness.check_layout(shapes, cfg)


@pytest.mark.parametrize("cfgd, error, says", [
    ({"name": "x", "family": "no-such-family"}, FileNotFoundError,
     os.path.join("families", "no-such-family.py")),
    ({"name": "x"}, ValueError, "names no \"family\""),
], ids=["no_file", "no_key"])
def test_a_config_whose_family_has_no_module_fails(cfgd, error, says):
    with pytest.raises(error) as e:
        modelcfg.load_family(cfgd)
    assert says in str(e.value)


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
        assert os.path.exists(os.path.join(HERE, "families",
                                           f"{cfgd['family']}.py"))
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
