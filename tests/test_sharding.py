"""Sharding-rule tests: every (arch × shape) cell's specs must be valid
(divisible) on the production meshes.  Uses AbstractMesh — no device init,
so this runs in the normal 1-device test process."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import ALL_SHAPES
from repro.configs.registry import ARCHS, cell_is_runnable
from repro.launch.inputs import params_abstract
from repro.models import transformer
from repro.sharding import specs as shard_specs

MESHES = {
    "pod": AbstractMesh((16, 16), ("data", "model")),
    "multipod": AbstractMesh((2, 16, 16), ("pod", "data", "model")),
}


def _check_spec(spec: P, shape, mesh, where):
    for i, axes in enumerate(spec):
        if axes is None:
            continue
        n = shard_specs.axis_size(
            mesh, axes if isinstance(axes, (tuple, list)) else (axes,))
        assert shape[i] % n == 0, \
            f"{where}: dim {i} of {shape} not divisible by {n} ({spec})"


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_param_specs_divisible(mesh_name, arch):
    mesh = MESHES[mesh_name]
    p_sds = params_abstract(ARCHS[arch])
    tied = "lm_head" not in p_sds

    def check(path, leaf):
        spec = shard_specs.param_spec(path, leaf.shape, mesh,
                                      tied_embeddings=tied)
        _check_spec(spec, leaf.shape, mesh, shard_specs._path_str(path))

    jax.tree_util.tree_map_with_path(check, p_sds)


@pytest.mark.parametrize("mesh_name", list(MESHES))
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_cache_and_batch_specs_divisible(mesh_name, arch):
    mesh = MESHES[mesh_name]
    cfg = ARCHS[arch]
    for shape in ALL_SHAPES:
        ok, _ = cell_is_runnable(cfg, shape)
        if not ok:
            continue
        bspec = shard_specs.batch_spec((shape.global_batch, shape.seq_len),
                                       mesh, seq_axis=1)
        _check_spec(bspec, (shape.global_batch, shape.seq_len), mesh,
                    f"{arch}/{shape.name}/batch")
        if shape.kind in ("decode", "long_decode"):
            caches = jax.eval_shape(
                lambda: transformer.stack_cache(
                    cfg, shape.global_batch, shape.seq_len,
                    jnp.dtype(cfg.dtype)))

            def check(path, leaf):
                spec = shard_specs.cache_spec(path, leaf.shape, mesh)
                _check_spec(spec, leaf.shape, mesh,
                            f"{arch}/{shape.name}/" +
                            shard_specs._path_str(path))

            jax.tree_util.tree_map_with_path(check, caches)


def test_big_params_are_actually_sharded():
    """Every >32 MB parameter must shard over at least one axis (ZeRO):
    otherwise grok cannot fit."""
    mesh = MESHES["pod"]
    for arch in ("grok-1-314b", "qwen2.5-14b", "llama4-scout-17b-a16e"):
        p_sds = params_abstract(ARCHS[arch])

        def check(path, leaf):
            nbytes = leaf.size * leaf.dtype.itemsize
            if nbytes < (32 << 20):
                return
            spec = shard_specs.param_spec(path, leaf.shape, mesh)
            assert any(a is not None for a in spec), \
                f"{arch}:{shard_specs._path_str(path)} {leaf.shape} unsharded"

        jax.tree_util.tree_map_with_path(check, p_sds)


def test_activation_policy_head_fallback():
    """Non-divisible head counts fall back to sequence-TP."""
    mesh = MESHES["pod"]
    pol = shard_specs.ActivationPolicy(mesh)
    # qwen2: 14 heads, S=4096 -> heads replicated, seq over model
    spec = pol.spec("heads", (256, 4096, 14, 64))
    assert spec[2] is None and spec[1] == "model"
    # qwen2.5: 40 heads? 40 % 16 != 0 -> fallback too
    spec = pol.spec("heads", (256, 4096, 40, 128))
    assert spec[1] == "model"
    # grok: 48 heads % 16 == 0 -> head TP
    spec = pol.spec("heads", (256, 4096, 48, 128))
    assert spec[2] == "model"
