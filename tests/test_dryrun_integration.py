"""Dry-run integration: lower+compile in a SUBPROCESS with forced host
devices (the test process must keep seeing 1 device), on a small mesh with
small-but-structured configs, exercising the whole launch path including the
HLO analysis."""
import json
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, json, sys
import jax
from repro.configs.registry import get_arch
from repro.configs.base import shape_by_name, ShapeConfig
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_host_mesh
from repro.sharding import enable_activation_policy
from repro.launch.hlo_analysis import collective_stats, compute_stats

arch, kind = sys.argv[1], sys.argv[2]
cfg = get_arch(arch)
cfg = dataclasses.replace(cfg.reduced(), n_layers=4, d_model=128, d_ff=256,
                          n_heads=4, n_kv_heads=2, head_dim=32,
                          vocab_size=512, dtype="bfloat16", remat=True,
                          logit_chunk=0)
if cfg.xlstm is not None:
    cfg = dataclasses.replace(cfg, d_ff=0)
shape = {"train": ShapeConfig("t", 128, 8, "train"),
         "prefill": ShapeConfig("p", 128, 8, "prefill"),
         "decode": ShapeConfig("d", 128, 8, "decode")}[kind]
mesh = make_host_mesh(8, model_axis=2)
enable_activation_policy(mesh)
spec = input_specs(cfg, shape, mesh)
with jax.set_mesh(mesh):
    lowered = jax.jit(spec.step_fn, in_shardings=spec.in_shardings,
                      donate_argnums=spec.donate_argnums).lower(*spec.args)
    compiled = lowered.compile()
hlo = compiled.as_text()
out = {
    "mem": int(compiled.memory_analysis().temp_size_in_bytes),
    "coll": collective_stats(hlo)["total_bytes_per_device"],
    "comp": compute_stats(hlo),
    "xla_flops": compiled.cost_analysis().get("flops", 0.0),
}
print("RESULT" + json.dumps(out))
"""


def _run(arch, kind):
    env = dict(os.environ, PYTHONPATH=SRC)
    r = subprocess.run([sys.executable, "-c", SCRIPT, arch, kind],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


@pytest.mark.slow
@pytest.mark.parametrize("arch,kind", [
    ("qwen2-0.5b", "train"),
    ("grok-1-314b", "train"),
    ("hymba-1.5b", "decode"),
    ("xlstm-125m", "prefill"),
])
def test_small_mesh_dryrun_cell(arch, kind):
    out = _run(arch, kind)
    assert out["mem"] > 0
    # trip-count-aware flops must exceed raw XLA (scan bodies counted once)
    if kind == "train":
        assert out["comp"]["flops_per_device"] > out["xla_flops"] * 1.5
    assert out["comp"]["flops_per_device"] > 0


@pytest.mark.slow
def test_trip_count_extraction_matches_layer_count():
    """The n_layers=4 scan must multiply collective/flop counts by ~4: check
    the analysis sees a x4 between 4-layer and 8-layer variants."""
    env = dict(os.environ, PYTHONPATH=SRC)
    script = SCRIPT.replace("n_layers=4", "n_layers=8")
    r = subprocess.run([sys.executable, "-c", script, "qwen2-0.5b", "train"],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    out8 = json.loads(line[len("RESULT"):])
    out4 = _run("qwen2-0.5b", "train")
    ratio = out8["comp"]["flops_per_device"] / out4["comp"]["flops_per_device"]
    assert 1.5 < ratio < 2.6, ratio   # ~2x flops for 2x layers
