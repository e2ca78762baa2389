"""On-chip smoke test: Parrot's federated training path at full qwen2-0.5b
width on a TPU, through the normal entry points (``launch/train.py``'s
builders -> ``ParrotServer`` -> ``SequentialExecutor`` -> the compiled client
step -> local fold -> codec -> global fold).

  python chip_smoke.py             # one chip: phases 1 and 2
  python chip_smoke.py --chips 4   # four chips: the device-parallel phase only

Phase 1 trains qwen2-0.5b (24 layers, d_model 896, 14 heads / 2 KV heads,
d_ff 4864, vocab 151936, bf16, remat; arXiv:2407.10671) with FedAvg under the
BSP engine: one executor, ``client_block=1``, 8 seeded clients of 3-4
batches of 4x512 tokens, 4 clients per round, 1 local epoch, 3 rounds.
Phase 2 runs one more round on the same server with top-k compression.
The four-chip phase pins 4 executors one per chip (gang dispatch and the
psum global fold) for 2 rounds of 4 clients.

Each phase checks finite params and loss, a nonzero global update, an eval
loss that falls, and agreement with ``run_flat_reference`` (the eager
single-process FL reference) run on the chip for the same cohort.  Weights
and data are made from seeds.  The script runs in one process, needs a TPU
(no CPU fallback), and prints as its last line
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}`` only
when every check passed; otherwise it exits non-zero.  Times are host-clock
seconds ended by ``block_until_ready``: a smoke check, not a benchmark.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 7
# bf16 keeps 8 significant bits, so an SGD step w - lr*g moves a weight only
# where |lr*g| exceeds half the spacing of bf16 values near |w| (2^-9 |w|):
# about 6e-5 for the ~0.03-magnitude dense weights of this model.  lr=0.5
# puts the typical per-element gradient of the large matrices above that
# line, so the local steps and the averaged update register in bf16 instead
# of rounding away.
LR = 0.5
TRAIN_ARGV = ["--model", "lm", "--arch", "qwen2-0.5b", "--full-config",
              "--algorithm", "fedavg", "--executors", "1",
              "--client-block", "1", "--clients", "8",
              "--clients-per-round", "4", "--local-epochs", "1",
              "--partition", "dirichlet", "--lr", str(LR),
              "--seed", str(SEED)]
# 10-16 samples per client at seed 7: 3-4 batches of 4x512 tokens each, all
# in one power-of-two batch bucket, so one client-step executable serves
# every client
LM_DATA = dict(seq_len=512, batch_size=4, mean_samples=13)
TOPK_FRACTION = 0.01          # make_compressor("topk") default
# Agreement with the eager reference.  The compiled scan (fused, remat) and
# the eager per-op steps round bf16 intermediates differently, and the
# server rounds each averaged update into bf16 params, so the two paths
# may differ by a few bf16 spacings per weight:
#  - max |p_sys - p_ref| <= MAX_DIFF_ULPS spacings of bf16 at the largest
#    parameter magnitude (2^-7 * max|p_ref| each);
#  - ||p_sys - p_ref|| / ||p_ref - p_prev|| <= UPDATE_REL_TOL: the update
#    itself agrees to 10% in norm — a missing, doubled or mis-weighted
#    client moves this by tens of percent.
MAX_DIFF_ULPS = 8
UPDATE_REL_TOL = 0.10
# top-k: the system's kept coordinates must carry at least this share of the
# reference update's energy in its own k largest coordinates
TOPK_ENERGY_MIN = 0.9

_failures: list = []


def log(msg: str) -> None:
    print(msg, flush=True)


def check(name: str, ok: bool, detail: str) -> None:
    log(f"  [{'PASS' if ok else 'FAIL'}] {name}: {detail}")
    if not ok:
        _failures.append(name)


def require_tpu():
    """The first TPU device, or exit non-zero: no CPU fallback."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}")
    return dev


class CompileClock:
    """Sums the durations of JAX's compile events (trace, lowering, backend
    compile) — the seconds paid for compiling — and counts the backend
    (XLA) compiles among them."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event.startswith("/jax/core/compile"):
            self.seconds += duration
            self.backend += event.endswith("backend_compile_duration")


def host(tree) -> list:
    """The tree's leaves copied to the host, in their own dtype."""
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tree))]


def f32(x):
    return np.asarray(x, np.float32)


def bf16_spacing(x):
    """The spacing of bf16 values at |x| (8 significant bits)."""
    _, e = np.frexp(np.maximum(np.abs(x), np.float32(2.0 ** -126)))
    return np.ldexp(np.float32(1), e - 8)


def compare(sys_leaves, ref_leaves, prev_leaves, mask=None) -> dict:
    """Max |sys - ref|, that max over max|ref|, the norm of sys - ref over
    the norm of the reference update ref - prev, and bit-equality; over the
    coordinates where ``mask`` (per leaf) is true, or all of them.  Also
    the share of weights that differ at all, and by more than one bf16
    spacing at that weight."""
    max_abs = max_ref = 0.0
    num = den = 0.0
    n = n_diff = n_over = 0
    for i, (s, r, p) in enumerate(zip(sys_leaves, ref_leaves, prev_leaves)):
        s, r, p = f32(s), f32(r), f32(p)
        if mask is not None:
            s, r, p = s[mask[i]], r[mask[i]], p[mask[i]]
        if s.size == 0:
            continue
        d = np.abs(s - r)
        ulps = d / bf16_spacing(np.maximum(np.abs(s), np.abs(r)))
        n += d.size
        n_diff += int(np.count_nonzero(d))
        n_over += int(np.count_nonzero(ulps > 1))
        max_abs = max(max_abs, float(d.max()))
        max_ref = max(max_ref, float(np.abs(r).max()))
        num += float(np.sum(np.square(d, dtype=np.float64)))
        den += float(np.sum(np.square(r - p, dtype=np.float64)))
    return {"max_abs": max_abs,
            "max_rel": max_abs / max_ref if max_ref else 0.0,
            "update_rel": (num / den) ** 0.5 if den else float("inf"),
            "bitexact": n_diff == 0,
            "frac_diff": n_diff / max(n, 1),
            "frac_over_1ulp": n_over / max(n, 1)}


def check_reference(tag: str, cmp: dict) -> None:
    bound = MAX_DIFF_ULPS * 2.0 ** -7
    check(f"{tag} reference max diff", cmp["max_rel"] <= bound,
          f"max|sys-ref|={cmp['max_abs']:.6g} max_rel={cmp['max_rel']:.6g} "
          f"(bound {bound:.6g} = {MAX_DIFF_ULPS} bf16 spacings of "
          f"max|p_ref|) bitexact={cmp['bitexact']}; {cmp['frac_diff']:.6%} "
          f"of weights differ, {cmp['frac_over_1ulp']:.6%} by more than "
          f"one bf16 spacing")
    check(f"{tag} reference update", cmp["update_rel"] <= UPDATE_REL_TOL,
          f"||sys-ref||/||ref-prev||={cmp['update_rel']:.6g} "
          f"(bound {UPDATE_REL_TOL})")


def check_state(tag: str, leaves, prev_leaves, losses) -> None:
    finite = all(np.isfinite(f32(x)).all() for x in leaves)
    check(f"{tag} finite params", finite, f"{len(leaves)} leaves")
    check(f"{tag} finite loss", all(np.isfinite(losses)), f"{losses}")
    moved = sum(int(np.count_nonzero(f32(s) != f32(p)))
                for s, p in zip(leaves, prev_leaves))
    n = sum(x.size for x in leaves)
    check(f"{tag} nonzero update", moved > 0,
          f"{moved} of {n} weights moved ({moved / n:.4%})")


def eval_loss(grad_fn, params, batches) -> float:
    """Mean loss over the fixed eval batches (``grad_fn``'s value; its
    shapes are the training batch's, so it reuses that compile)."""
    return float(np.mean([float(grad_fn(params, b)[0][0]) for b in batches]))


def eval_batches(data) -> list:
    """Fixed eval batches: the first sequence of every client, 4 per
    batch (the training batch shape)."""
    rows = [{k: v[:1] for k, v in data[c].batches[0].items()}
            for c in sorted(data)]
    return [{k: np.concatenate([r[k] for r in rows[i:i + 4]])
             for k in rows[0]} for i in range(0, len(rows), 4)]


def cohort(server, data) -> list:
    """The clients ``server``'s next round selects: its selection is
    rng-identical to ``rng.choice(sorted(ids), k, replace=False)``."""
    rng = copy.deepcopy(server.rng)
    k = min(server.clients_per_round, len(data))
    return [int(c) for c in rng.choice(sorted(data), size=k, replace=False)]


def timed_round(server, clock, tag: str):
    """One round, timed on the host clock up to ``block_until_ready`` on the
    new params; returns (wall seconds, compile events in the round)."""
    import jax
    from repro.core import client_step
    c0, s0, b0 = client_step.compile_events(), clock.seconds, clock.backend
    t0 = time.perf_counter()
    m = server.run_round()
    jax.block_until_ready(server.params)
    wall = time.perf_counter() - t0
    n = client_step.compile_events() - c0
    log(f"  {tag} round {m.round}: {wall:.3f} s host-clock wall to "
        f"block_until_ready, {m.n_clients} clients, {n} compile events, "
        f"{clock.backend - b0} XLA compiles ({clock.seconds - s0:.1f} s "
        f"compiling)" + ("" if clock.backend > b0 else " [warm]"))
    return wall, n


def reference(algorithm, data, params, clients) -> list:
    """``run_flat_reference`` for one round from ``params`` over exactly
    ``clients`` (it selects all of them)."""
    from repro.core.round import run_flat_reference
    p, _ = run_flat_reference(params, algorithm,
                              {c: data[c] for c in clients},
                              clients_per_round=len(clients), n_rounds=1,
                              seed=SEED)
    return host(p)


def phase_summary(tag: str, dev, c0: int, s0: float, clock) -> None:
    from repro.core import client_step
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    log(f"  {tag}: {client_step.compile_events() - c0} compile events, "
        f"{clock.seconds - s0:.1f} s compiling; peak_bytes_in_use since "
        f"start {peak} ({peak / 2**30:.2f} GiB)")


def one_chip(dev, clock) -> None:
    import jax
    from repro.core import client_step
    from repro.core.compression import make_compressor
    from repro.launch import train

    args = train.parse_args(TRAIN_ARGV)
    cfg = train.model_config(args)
    grad_fn, params = train.build_grad_fn(cfg)
    data = train.build_data(args, cfg, **LM_DATA)
    server = train.build_server(args, grad_fn, params, data)
    ev = eval_batches(data)
    n = sum(x.size for x in jax.tree.leaves(server.params))
    log(f"model {cfg.name}: {n} params, {cfg.n_layers} layers, "
        f"d_model {cfg.d_model}, vocab {cfg.vocab_size}, dtype {cfg.dtype}; "
        f"clients {[len(data[c].batches) for c in sorted(data)]} batches "
        f"of {LM_DATA['batch_size']}x{LM_DATA['seq_len']}; lr {LR}")

    # Each reference runs before the system's round it checks: it needs
    # only the round's starting params and cohort, and run after the round
    # it would share the chip with what the round leaves resident (the
    # top-k codec's fp32 residual), which does not fit beside it.

    # ---- phase 1: FedAvg, BSP, 3 rounds --------------------------------
    log("phase 1: fedavg / bsp / 1 executor / client_block 1 / 3 rounds")
    c0, s0 = client_step.compile_events(), clock.seconds
    p0_host = host(server.params)
    loss0 = eval_loss(grad_fn, server.params, ev)
    first = cohort(server, data)
    log(f"  reference: run_flat_reference round 1, cohort {first}")
    ref1 = reference(server.algorithm, data, server.params, first)
    timed_round(server, clock, "phase 1")
    p1_host = host(server.params)
    for _ in range(2):
        timed_round(server, clock, "phase 1")
    p3_host = host(server.params)
    loss3 = eval_loss(grad_fn, server.params, ev)
    phase_summary("phase 1", dev, c0, s0, clock)
    check_state("phase 1", p3_host, p0_host, [loss0, loss3])
    check("phase 1 eval loss falls", loss3 < loss0,
          f"{loss0:.6f} before round 1 -> {loss3:.6f} after round 3")
    check_reference("phase 1 round 1", compare(p1_host, ref1, p0_host))
    del p1_host, ref1

    # ---- phase 2: one top-k round on the same server -------------------
    log(f"phase 2: one more round with compressor=topk "
        f"(fraction {TOPK_FRACTION})")
    c0, s0 = client_step.compile_events(), clock.seconds
    server.compressor = make_compressor("topk", TOPK_FRACTION)
    clients = cohort(server, data)
    log(f"  reference: run_flat_reference (dense), cohort {clients}")
    ref = reference(server.algorithm, data, server.params, clients)
    timed_round(server, clock, "phase 2")
    p4_host = host(server.params)
    loss4 = eval_loss(grad_fn, server.params, ev)
    phase_summary("phase 2", dev, c0, s0, clock)
    check_state("phase 2", p4_host, p3_host, [loss3, loss4])
    check("phase 2 eval loss falls", loss4 < loss3,
          f"{loss3:.6f} before the round -> {loss4:.6f} after")
    kept = [f32(s) != f32(p) for s, p in zip(p4_host, p3_host)]
    n_kept = sum(int(m.sum()) for m in kept)
    k = max(1, int(n * TOPK_FRACTION))
    check("phase 2 sparsity", n_kept <= k,
          f"{n_kept} weights moved, top-k keeps k={k}")
    check_reference("phase 2 kept coordinates",
                    compare(p4_host, ref, p3_host, mask=kept))
    upd = np.concatenate([np.abs(f32(r) - f32(p)).ravel()
                          for r, p in zip(ref, p3_host)])
    e_top = float(np.sum(np.square(np.partition(upd, upd.size - k)[-k:],
                                   dtype=np.float64)))
    e_sys = sum(float(np.sum(np.square(f32(r)[m] - f32(p)[m],
                                       dtype=np.float64)))
                for r, p, m in zip(ref, p3_host, kept))
    share = e_sys / e_top if e_top else 0.0
    check("phase 2 top-k selection", share >= TOPK_ENERGY_MIN,
          f"kept coordinates carry {share:.4f} of the energy of the "
          f"reference update's top-{k} (bound {TOPK_ENERGY_MIN})")


def four_chips(clock) -> None:
    import jax
    from repro.core import client_step
    from repro.launch import train

    devices = jax.devices()
    if len(devices) != 4:
        sys.exit(f"chip_smoke --chips 4: JAX sees {len(devices)} devices")
    argv = list(TRAIN_ARGV)
    argv[argv.index("--executors") + 1] = "4"
    args = train.parse_args(argv)
    cfg = train.model_config(args)
    grad_fn, params = train.build_grad_fn(cfg)
    data = train.build_data(args, cfg, **LM_DATA)
    server = train.build_server(args, grad_fn, params, data)
    pins = {k: ex.device.id for k, ex in server.executors.items()}
    log(f"four-chip phase: fedavg / bsp / 4 executors pinned {pins} / "
        f"4 clients per round / 2 rounds")

    homes = []                       # per round: device ids of each partial
    fold = server.global_fold

    def global_fold(partials):
        homes.append([sorted({d.id for b in p["sums"]["buffers"].values()
                              for d in b.sharding.device_set})
                      for p in partials])
        return fold(partials)

    server.global_fold = global_fold
    # the unpinned engine of the algorithm issues only the gang (SPMD)
    # dispatches; each pinned executor has an engine of its own
    gang = client_step.engine_for(server.algorithm)
    g0 = gang.n_dispatches
    c0, s0 = client_step.compile_events(), clock.seconds
    ev = eval_batches(data)
    treedef = jax.tree.structure(server.params)
    starts = [host(server.params)]   # the params each round starts from
    loss0 = eval_loss(grad_fn, server.params, ev)
    cohorts = []
    for _ in range(2):
        cohorts.append(cohort(server, data))
        timed_round(server, clock, "four-chip")
        starts.append(host(server.params))
    loss2 = eval_loss(grad_fn, server.params, ev)
    log(f"  four-chip: {client_step.compile_events() - c0} compile events, "
        f"{clock.seconds - s0:.1f} s compiling")
    p0_host, p2_host = starts[0], starts[-1]
    check_state("four-chip", p2_host, p0_host, [loss0, loss2])
    check("four-chip eval loss falls", loss2 < loss0,
          f"{loss0:.6f} -> {loss2:.6f}")
    own = all(len(h) == 4 and all(len(d) == 1 for d in h)
              and sorted(d[0] for d in h) == sorted(pins.values())
              for h in homes)
    check("four-chip partials on own chips", own and len(homes) == 2,
          f"partial device ids per round {homes}")
    check("four-chip gang dispatch", gang.n_dispatches > g0,
          f"{gang.n_dispatches - g0} SPMD block dispatches over the 4 chips")
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devices]
    check("four-chip peak memory on every chip", all(peaks),
          f"peak_bytes_in_use per chip {peaks}")

    # Each round is checked from the params the system started it from, as
    # in phases 1 and 2: a two-round reference from the first round's
    # params compounds the bf16 differences of round 1 through round 2's
    # gradients.  The references run once the rounds are over, with every
    # device buffer freed: chip 0 holds no room for the eager reference
    # beside the server, executor 0 and the gang-replicated payload.
    algorithm = server.algorithm
    del server
    for a in jax.live_arrays():
        a.delete()
    for r, clients in enumerate(cohorts):
        log(f"  reference: run_flat_reference round {r + 1} on chip 0, "
            f"cohort {clients}")
        params = jax.device_put(jax.tree.unflatten(treedef, starts[r]),
                                devices[0])
        ref = reference(algorithm, data, params, clients)
        del params
        check_reference(f"four-chip round {r + 1}",
                        compare(starts[r + 1], ref, starts[r]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the four-chip device-parallel phase")
    opts = ap.parse_args(argv)
    dev = require_tpu()
    import jax
    from repro.launch import train
    log(f"device: {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compile cache {train.enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if opts.chips == 4:
        four_chips(clock)
    else:
        one_chip(dev, clock)
    log(f"total {time.perf_counter() - t0:.1f} s host clock, "
        f"{clock.seconds:.1f} s compiling")
    if _failures:
        log(f"FAILED: {_failures}")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
