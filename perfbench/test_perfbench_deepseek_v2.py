"""The DeepSeek-V2 family on the CPU at a tiny size: the program
(``models/lm.py`` with MLA, a leading dense layer and the dropless held-expert
MoE) against the family's plain reference, one chip's share of an MoE layer
against the uncut layer, dropless routing under adversarial imbalance, the
YaRN settings of the published config, and one tiny cell through the
harness to ``correct``."""
import dataclasses
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, modelcfg

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**31 + 23

TINY = {
    "name": "tiny-deepseek", "family": "deepseek_v2",
    "program_arch": "deepseek-v2-lite",
    "first_k_dense_replace": 1, "hidden_size": 64, "intermediate_size": 128,
    "kv_lora_rank": 32, "moe_intermediate_size": 32, "moe_layer_freq": 1,
    "n_routed_experts": 4, "n_shared_experts": 2, "norm_topk_prob": False,
    "num_attention_heads": 4, "num_experts_per_tok": 3,
    "num_hidden_layers": 3, "num_key_value_heads": 4, "q_lora_rank": None,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "rms_norm_eps": 1e-6,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 1,
    "scoring_func": "softmax", "seq_aux": True, "tie_word_embeddings": False,
    "topk_method": "greedy", "v_head_dim": 16, "vocab_size": 256,
    "aux_loss_alpha": 0.001, "first_held_expert": 0,
    "published": {"n_routed_experts": 8},
    "program": {"remat": True, "logit_chunk": 0, "attn_chunk": 16}}


def _family(cfgd):
    family = modelcfg.load_family(cfgd)
    return family, family.dims(cfgd)


def _program(cfgd, family, m):
    from repro.configs.registry import get_arch
    return dataclasses.replace(get_arch(cfgd["program_arch"]),
                               **family.program_fields(m),
                               **cfgd.get("program", {}))


def _tiny(dtype="float32", **kw):
    cfgd = dict(TINY, torch_dtype=dtype, **kw)
    family, m = _family(cfgd)
    return cfgd, family, m, _program(cfgd, family, m)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))


def test_program_matches_the_reference_loss_and_gradients():
    from repro.models import lm
    cfgd, family, m, cfg = _tiny()
    params = family.make(m, jax.random.key(1))
    harness.check_layout(params, cfg)
    k1, k2 = jax.random.split(jax.random.key(2))
    inputs = jax.random.randint(k1, (2, 32), 0, m.vocab)
    labels = jax.random.randint(k2, (2, 32), 0, m.vocab)
    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(lm.loss_and_aux)(
            params, {"inputs": inputs, "labels": labels}, cfg)
        lr, gr = jax.value_and_grad(
            lambda p: family.loss(m, p, inputs, labels))(params)
    assert abs(float(lp) - float(lr)) <= 1e-5 * abs(float(lr))
    flat_p = jax.tree_util.tree_leaves_with_path(gp)
    for (path, a), b in zip(flat_p, jax.tree.leaves(gr)):
        assert _rel(a, b) < 1e-5, jax.tree_util.keystr(path)


def _moe_layer(held, first, cfgd, params, x):
    """The program's dropless layer holding experts first .. first+held-1
    of the uncut layer's ``params``."""
    from repro.models import moe
    c, family, m, cfg = _tiny(**dict(cfgd, n_routed_experts=held,
                                     first_held_expert=first))
    p = dict(params, **{k: params[k][first:first + held]
                        for k in ("wi", "wg", "wo")})
    return moe.dropless_moe(p, x, cfg)


def _uncut():
    """An uncut layer (all 8 experts held) and its input."""
    from repro.models import moe
    cfgd = {k: v for k, v in TINY.items()
            if k not in ("name", "program_arch", "family")}
    cfgd.update(n_routed_experts=8, published={"n_routed_experts": 8})
    _, family, m, cfg = _tiny(**cfgd)
    params = moe.moe_init(jax.random.key(3), cfg)
    x = jax.random.normal(jax.random.key(4), (2, 16, 64), jnp.float32)
    return cfgd, family, m, params, x


def test_shares_of_an_moe_layer_add_up_to_the_uncut_layer():
    from repro.models import layers
    cfgd, family, m, params, x = _uncut()
    with jax.default_matmul_precision("highest"):
        a, _, rows_a = _moe_layer(4, 0, cfgd, params, x)
        b, _, rows_b = _moe_layer(4, 4, cfgd, params, x)
        shared = layers.swiglu(params["shared"], x)
        want, _ = family._moe(m, None, x, params)
    got = a + b - shared          # the shared experts counted once
    assert _rel(got, want) < 1e-5
    # every pick lands on one of the two shares
    assert int(rows_a.sum() + rows_b.sum()) == x.shape[0] * x.shape[1] * 3


def _adversarial():
    """Every token routed to experts 0, 1 and 2, all held by the first
    share: each of them gets every token, far past any fixed capacity."""
    cfgd, family, m, params, x = _uncut()
    x = x.at[..., 0].set(10.0)
    router = params["router"].at[0].set(0.0).at[0, :3].set(5.0)
    return cfgd, family, m, dict(params, router=router), x


def test_dropless_under_adversarial_routing_loses_no_row():
    cfgd, family, m, params, x = _adversarial()
    T = x.shape[0] * x.shape[1]
    with jax.default_matmul_precision("highest"):
        got, _, rows = _moe_layer(4, 0, cfgd, params, x)
        # the reference given the same share: experts 0-3 of 8
        mm = dataclasses.replace(m, held=4)
        want, _ = family._moe(mm, None, x, dict(
            params, **{k: params[k][:4] for k in ("wi", "wg", "wo")}))
    assert np.asarray(rows).tolist() == [T, T, T, 0]
    assert _rel(got, want) < 1e-5


def test_every_held_group_is_closed_by_a_zero_row(monkeypatch):
    """The grouped matmuls see a buffer of whole row tiles in which each
    held expert's group ends in a zero row, so no group is empty, not even
    that of an expert no token picks; the stats count routed rows only."""
    from repro.models import moe
    cfgd, family, m, params, x = _adversarial()
    T = x.shape[0] * x.shape[1]
    seen = []
    grouped = moe._grouped

    def spy(xs, w, gs):
        seen.append((np.asarray(xs), np.asarray(gs)))
        return grouped(xs, w, gs)

    monkeypatch.setattr(moe, "_grouped", spy)
    _, _, rows = _moe_layer(4, 0, cfgd, params, x)
    assert np.asarray(rows).tolist() == [T, T, T, 0]
    xs, gs = seen[0]
    assert gs.tolist() == [T + 1, T + 1, T + 1, 1]
    assert xs.shape[0] % moe._ROW_TILE == 0
    ends = np.cumsum(gs) - 1
    assert not xs[ends].any()
    assert np.abs(xs[:ends[0]]).sum(axis=1).min() > 0    # routed rows


def test_dropless_stats_count_rows_per_held_expert():
    """The stats the step returns: rows per held expert of each MoE layer,
    through the whole model."""
    from repro.models import lm
    cfgd, family, m, cfg = _tiny()
    params = family.make(m, jax.random.key(5))
    inputs = jax.random.randint(jax.random.key(6), (2, 32), 0, m.vocab)
    _, stats = jax.jit(lambda p, b: lm.loss_and_stats(p, b, cfg))(
        params, {"inputs": inputs, "labels": inputs})
    rows = np.asarray(stats["moe_expert_rows"])
    assert rows.shape == (m.moe_layers, m.held)
    assert rows.dtype == np.int32
    # no more picks than tokens x top_k land here, and some do
    assert 0 < rows.sum(axis=1).max() <= 64 * m.top_k


def test_yarn_at_the_published_settings():
    from repro.configs.registry import get_arch
    from repro.models import attention
    cfg = get_arch("deepseek-v2-lite")
    inv, ms = attention.mla_rope(cfg)
    inv = np.asarray(inv)
    assert inv.shape == (32,) and ms == 1.0
    # dims 0-10 keep the base frequency, 23-31 take it over 40, a ramp
    # between (correction range [10, 23] for beta 32 / 1 at 4096 positions)
    want = {0: 1.0, 1: 0.7498942093324558, 10: 0.056234132519034905,
            11: 0.03900692656714386, 16: 0.0055,
            22: 0.0001778279410038922, 23: 3.33380358040831e-05,
            31: 3.3338035804083097e-06}
    for i, v in want.items():
        assert inv[i] == pytest.approx(v, rel=1e-6), i
    # 192^-0.5 x (0.1 x 0.707 x ln 40 + 1)^2
    assert attention.mla_softmax_scale(cfg) == pytest.approx(
        0.1147213867929261, rel=1e-12)
    cfgd = modelcfg.load_config("deepseek-v2-lite-5l")
    family = modelcfg.load_family(cfgd)
    m = family.dims(cfgd)
    np.testing.assert_allclose(family.rope_inv_freq(m), inv, rtol=1e-6)
    assert family.softmax_scale(m) == pytest.approx(0.1147213867929261,
                                                    rel=1e-12)


def test_the_cut_and_the_registry_entry_count_as_published():
    from repro.configs.registry import get_arch
    assert get_arch("deepseek-v2-lite").n_params() == 15_706_484_224
    cfgd = modelcfg.load_config("deepseek-v2-lite-5l")
    family = modelcfg.load_family(cfgd)
    m = family.dims(cfgd)
    assert family.n_params(m) == 535_060_992
    assert m.router == 64 and m.held == 8 and m.top_k == 6
    # 6 x (router + shared + 6 x 8/64 of an expert) in each of 4 layers
    per = 2048 * 64 + 2 * 3 * 2048 * 1408 + 0.75 * 3 * 2048 * 1408
    assert family.moe_train_flops_per_token(m) == pytest.approx(6 * 4 * per)
    assert family.train_flops_per_token(m, 512) == pytest.approx(
        1.587096576e9)


def test_tiny_cell_runs_to_correct():
    """One tiny cell of the family through ``harness.run``, as the dense
    tiny cell runs in test_perfbench_rehearsal.py."""
    with open(os.path.join(HERE, "traffic", "fedavg-c4.json")) as f:
        traffic = json.load(f)
    traffic.update(clients=6, clients_per_round=4, batches_per_client=2,
                   batch_size=2, seq_len=32)
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        bench = json.load(f)
    cfgd = dict(TINY, torch_dtype="bfloat16")
    cell = harness.Cell(
        name="tiny-deepseek", chips=1, config=cfgd, traffic=traffic,
        limits={"update_gap": 0.1, "change_gap": 0.1, "update_diff": 0.3},
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
    out = harness.run(cell, SEED, 0.3, False, t_start=time.perf_counter(),
                      require_tpu=False, compile_cache=False,
                      log=lambda s: None)
    assert out["correct"] is True, out["checks"]
    assert out["metrics"]["round_s"]["value"] > 0


def _server(cfg, params, grad_fn, block, lr, telemetry):
    """A ParrotServer over 4 clients of 2, 2, 2 and 3 batches of 2x16
    tokens, all of them in every round: a block of three pads to four rows,
    and the 3-batch client's scan to four steps."""
    import tempfile
    from repro.core import (ClientStateManager, ParrotServer,
                            SequentialExecutor, TickTimer, make_algorithm)
    from repro.core.algorithms import ClientData
    rng = np.random.default_rng(0)
    data = {}
    for c, n in enumerate((2, 2, 2, 3)):
        b = [{"inputs": rng.integers(0, cfg.vocab_size, (2, 16), np.int32),
              "labels": rng.integers(0, cfg.vocab_size, (2, 16), np.int32)}
             for _ in range(n)]
        data[c] = ClientData(batches=b, n_samples=2 * n)
    algo = make_algorithm("fedavg", grad_fn, lr)
    ex = SequentialExecutor(0, algo, state_manager=ClientStateManager(
        tempfile.mkdtemp()), client_block=block, timer=TickTimer(1.0))
    return ParrotServer(params=params, algorithm=algo, executors=[ex],
                        data_by_client=data, clients_per_round=4, seed=3,
                        telemetry=telemetry)


@pytest.mark.parametrize("block", [1, 4])
def test_counters_equal_hand_counts_under_adversarial_routing(block):
    from repro.launch import train
    cfgd, family, m, cfg = _tiny("bfloat16")
    params = family.make(m, jax.random.key(7))
    # a zero router scores every expert alike and top_k breaks ties to the
    # lower index: every token picks experts 0, 1 and 2, all held here
    # (lr 0 keeps it so)
    ffn = params["blocks"][0]["ffn"]
    ffn["router"] = jnp.zeros_like(ffn["router"])
    srv = _server(cfg, params, harness.grad_fn_of(train, cfg), block, 0.0,
                  True)
    for _ in range(2):
        extra = srv.run_round().extra
        # 9 real steps of 32 tokens, 3 held picks each, in 2 MoE layers
        assert extra["moe_routed_rows"] == 9 * 32 * 3 * 2
        # experts 0-2 take every token, expert 3 none: max / mean = 4/3
        assert extra["moe_max_expert_share"] == pytest.approx(4 / 3)
    reg = srv.telemetry.registry
    assert reg.value("total/moe_routed_rows") == 2 * 9 * 32 * 3 * 2
    assert reg.value("round/moe_max_expert_share") == pytest.approx(4 / 3)


@pytest.mark.parametrize("block", [1, 4])
def test_telemetry_leaves_the_params_bit_identical(block):
    from repro.launch import train
    cfgd, family, m, cfg = _tiny("bfloat16")
    params = family.make(m, jax.random.key(8))
    grad_fn = harness.grad_fn_of(train, cfg)
    a = _server(cfg, params, grad_fn, block, 0.5, None)
    b = _server(cfg, params, grad_fn, block, 0.5, True)
    for _ in range(2):
        ma, mb = a.run_round(), b.run_round()
        assert "moe_routed_rows" not in ma.extra
        assert 0 < mb.extra["moe_routed_rows"] <= 9 * 32 * 3 * 2
    for x, y in zip(jax.tree.leaves(a.params), jax.tree.leaves(b.params)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_step_stats_are_taken_at_every_commit_without_telemetry():
    """The compiled steps return the counters whether or not anyone reads
    them; the server takes what the executors kept at each round's commit,
    so nothing piles up without telemetry."""
    from repro.launch import train
    cfgd, family, m, cfg = _tiny("bfloat16")
    params = family.make(m, jax.random.key(9))
    srv = _server(cfg, params, harness.grad_fn_of(train, cfg), 1, 0.5, None)
    (ex,) = srv.executors.values()
    for _ in range(2):
        assert "moe_routed_rows" not in srv.run_round().extra
        assert ex.take_step_stats() == []


@pytest.mark.parametrize("key,value", [("norm_topk_prob", True),
                                       ("routed_scaling_factor", 16.0),
                                       ("seq_aux", False)])
def test_routing_settings_the_program_does_not_model_are_refused(key, value):
    family = modelcfg.load_family(TINY)
    with pytest.raises(ValueError, match="modelled"):
        family.dims(dict(TINY, torch_dtype="float32", **{key: value}))


def _moe_ctx(layers):
    from types import SimpleNamespace
    cfgd = modelcfg.load_config("deepseek-v2-lite-5l")
    family = modelcfg.load_family(cfgd)
    return harness._Ctx({
        "layers": layers, "traced_rounds": 2,
        "cell": SimpleNamespace(config=cfgd), "dims": family.dims(cfgd),
        "steps_per_round": 32, "tokens_per_step": 2048,
        "peaks": {"bf16_flops_per_s": 197e12}}), family


def test_moe_readers_add_what_the_step_runs_outside_its_op_events():
    """The ``moe`` scope plus the client step's device time that its op
    events leave out (the grouped matmuls' kernels), per round; the MFU
    over that time."""
    detail = {"scope_s": {"moe": 0.5, "attn": 0.3},
              "device_s_by_span": {"parrot.client_step": 2.0,
                                   "parrot.fold": 0.1},
              "client_step_ops_s": 1.6, "ops_without_path_s": 0.05}
    ctx, family = _moe_ctx({"detail": detail})
    assert ctx.value("client_step.moe_ms_per_round") == pytest.approx(450.0)
    flops = family.moe_train_flops_per_token(ctx["dims"]) * 32 * 2048
    assert ctx.value("client_step.moe_mfu") == pytest.approx(
        100 * flops / (0.45 * 197e12))
    for layers in (None, {"detail": dict(detail, scope_s={"attn": 0.3})}):
        ctx, _ = _moe_ctx(layers)
        assert ctx.value("client_step.moe_ms_per_round") is None
        assert ctx.value("client_step.moe_mfu") is None
