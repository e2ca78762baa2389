"""Ahead-of-time compiles for a described TPU v5e chip at real width.

The main path's kernels and client step are lowered and compiled for one
chip of a ``v5e:2x2`` topology that is described, not attached: what the
TPU compiler refuses (a Pallas primitive with no TPU lowering, a block that
breaks the tiling, a program that does not fit the chip's HBM) fails here,
in the test suite, instead of on the chip.  Sizes are qwen2-0.5b's
(494,032,768 params; arXiv:2407.10671).  Nothing runs, so nothing here says
anything about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, so the call must happen in the
worker that runs this file.  The persistent compilation cache is off around
each compile (entries written for a described chip cannot be read back).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 10**9          # v5e: 16 GB of HBM per chip
N_PARAMS = 494_032_768          # qwen2-0.5b


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _compile(fn, *shapes):
    compiled = fn.lower(*shapes).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes)
    return compiled, total


@pytest.mark.parametrize("C", [1, 8])
def test_fold_kernel_compiles(one_chip, no_persistent_cache, C):
    """The Pallas fold at C bf16 client rows over the whole model."""
    from repro.kernels import agg_weighted_sum as ak
    fn = jax.jit(lambda acc, d, w: ak.agg_weighted_sum(acc, d, w,
                                                       interpret=False),
                 donate_argnums=(0,))
    compiled, total = _compile(
        fn,
        jax.ShapeDtypeStruct((N_PARAMS,), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((C, N_PARAMS), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((C,), jnp.float32, sharding=one_chip))
    assert "tpu_custom_call" in compiled.as_text()
    assert total < HBM_BYTES


@pytest.mark.parametrize("codec", ["topk", "int8"])
def test_group_codec_compiles(one_chip, no_persistent_cache, codec):
    """The one-dispatch group codecs on FedAvg's single whole-model delta
    span, as ``PartialCompressor._span_plans`` lays it out."""
    from repro.core import compression
    plan = (("comp", 0, N_PARAMS),)
    buf = jax.ShapeDtypeStruct((N_PARAMS,), jnp.float32, sharding=one_chip)
    if codec == "topk":
        k = compression.make_compressor("topk")._k_of(N_PARAMS)
        fn, shapes = compression._topk_group_fn(N_PARAMS, plan, (k,)), \
            (buf, buf)
    else:
        fn, shapes = compression._int8_group_fn(N_PARAMS, plan), (buf,)
    _, total = _compile(fn, *shapes)
    assert total < HBM_BYTES


@pytest.mark.parametrize("form", ["client", "block"])
def test_full_width_client_step_fits_one_chip(one_chip, no_persistent_cache,
                                              form):
    """qwen2-0.5b at published width (bf16, remat), FedAvg, 4 batches of
    4x512 tokens: the compiled local update at ``client_block=1`` — the
    single-client scan ``run_client`` dispatches, and the vmapped block at
    B=1 the gang path dispatches — fits one chip's HBM."""
    from repro.core import make_algorithm
    from repro.core.client_step import ClientStepEngine
    from repro.launch import train

    args = train.parse_args(["--model", "lm", "--arch", "qwen2-0.5b",
                             "--full-config"])
    cfg = train.model_config(args)
    built = {}

    def init():
        built["grad_fn"], params = train.build_grad_fn(cfg)
        return params

    params = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        jax.eval_shape(init))
    assert sum(x.size for x in jax.tree.leaves(params)) == N_PARAMS
    engine = ClientStepEngine(make_algorithm("fedavg", built["grad_fn"], 0.5,
                                             local_epochs=1))
    lead = (4,) if form == "client" else (1, 4)
    tokens = jax.ShapeDtypeStruct(lead + (4, 512), jnp.int32,
                                  sharding=one_chip)
    mask = jax.ShapeDtypeStruct(lead, jnp.float32, sharding=one_chip)
    fn = engine._run_jit if form == "client" else engine._run_block_jit
    _, total = _compile(fn, {"params": params}, None,
                        {"inputs": tokens, "labels": tokens}, mask)
    assert total < HBM_BYTES
