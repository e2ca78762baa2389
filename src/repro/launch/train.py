"""End-to-end Parrot FL training driver.

Runs Algorithm 2 with K sequential executors over a synthetic federated
dataset, any of the 6 FL algorithms, heterogeneity-aware scheduling, state
management, checkpointing and auto-resume.  The client model is a small MLP
(``--model mlp``, the CPU-friendly default mirroring the paper's FEMNIST
setting) or an LM from the arch registry (``--model lm --arch ...``),
reduced to a CPU-sized config unless ``--full-config`` asks for the
registry's published widths and depth.  Executors are pinned round-robin
over the local devices.

Examples:
  python -m repro.launch.train --algorithm scaffold --rounds 20
  python -m repro.launch.train --model lm --arch qwen2-0.5b --rounds 5 \\
      --clients 50
  python -m repro.launch.train --model lm --arch qwen2-0.5b --full-config \\
      --executors 1 --client-block 1 --clients 8 --clients-per-round 4 \\
      --rounds 3
  python -m repro.launch.train --resume --ckpt-dir /tmp/parrot_ckpt
"""
from __future__ import annotations

import argparse
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

_CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                         "..", "..", ".."))


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to ``<checkout>/.jax_cache``
    (git-ignored): a fixed path, so a later process finds what an earlier
    one compiled.  Call before the first compile, never at import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--algorithm", default="fedavg",
                    choices=["fedavg", "fedprox", "fednova", "mime",
                             "scaffold", "feddyn"])
    ap.add_argument("--model", default="mlp", choices=["mlp", "lm"])
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--full-config", action="store_true",
                    help="use the full (not reduced) config — published "
                         "widths and depth (--model lm)")
    ap.add_argument("--rounds", type=int, default=10)
    ap.add_argument("--clients", type=int, default=100)
    ap.add_argument("--clients-per-round", type=int, default=20)
    ap.add_argument("--executors", type=int, default=4)
    ap.add_argument("--client-block", type=int, default=8,
                    help="clients per vmapped compiled block")
    ap.add_argument("--local-epochs", type=int, default=2)
    ap.add_argument("--lr", type=float, default=0.05)
    ap.add_argument("--scheduler", default="parrot",
                    choices=["parrot", "uniform", "none"])
    ap.add_argument("--time-window", type=int, default=0)
    ap.add_argument("--partition", default="natural")
    ap.add_argument("--compression", default="none",
                    choices=["none", "topk", "int8"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=5)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def model_config(args: argparse.Namespace):
    """The LM's ``ModelConfig`` (None for the MLP): reduced unless
    ``--full-config``."""
    if args.model != "lm":
        return None
    from repro.configs.registry import get_arch
    cfg = get_arch(args.arch)
    return cfg if args.full_config else cfg.reduced()


def build_grad_fn(cfg):
    """Returns (grad_fn, params0) for the LM ``cfg``, or for the MLP when
    ``cfg`` is None.  ``grad_fn(params, batch) -> ((loss, stats), grads)``:
    ``stats`` are the model's step counters (``lm.loss_and_stats``; a
    dropless MoE's rows per held expert), ``{}`` for a model without."""
    key = jax.random.PRNGKey(0)
    if cfg is None:
        dims = [32, 64, 10]
        ks = jax.random.split(key, len(dims) - 1)
        params = {f"w{i}": jax.random.normal(k, (a, b)) / np.sqrt(a)
                  for i, (k, a, b) in enumerate(zip(ks, dims[:-1], dims[1:]))}
        params.update({f"b{i}": jnp.zeros((b,))
                       for i, b in enumerate(dims[1:])})

        def loss_fn(p, batch):
            x = batch["x"]
            n = len(dims) - 1
            for i in range(n):
                x = x @ p[f"w{i}"] + p[f"b{i}"]
                if i < n - 1:
                    x = jax.nn.relu(x)
            lse = jax.nn.logsumexp(x, axis=-1)
            gold = jnp.take_along_axis(
                x, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
            return jnp.mean(lse - gold), {}

        return jax.jit(jax.value_and_grad(loss_fn, has_aux=True)), params

    from repro.models import lm
    params = lm.init_params(key, cfg)

    def loss_fn(p, batch):
        return lm.loss_and_stats(p, batch, cfg)

    return jax.jit(jax.value_and_grad(loss_fn, has_aux=True)), params


def build_data(args: argparse.Namespace, cfg, **lm_kw):
    """The seeded synthetic federation: classification clients for the MLP,
    token-stream clients (``make_lm_clients``; ``lm_kw`` sets its sequence
    length, batch size and samples per client) for an LM."""
    from repro.data import make_classification_clients, make_lm_clients
    if cfg is None:
        return make_classification_clients(
            args.clients, dim=32, n_classes=10, partition=args.partition,
            seed=args.seed)
    return make_lm_clients(args.clients, vocab=cfg.vocab_size,
                           partition=args.partition, seed=args.seed, **lm_kw)


def build_server(args: argparse.Namespace, grad_fn, params, data):
    """Algorithm, executors pinned round-robin over ``jax.local_devices()``
    (the server derives its device placement from the pins), and the
    ``ParrotServer`` — everything ``main`` runs."""
    from repro.checkpoint import CheckpointManager
    from repro.core import (ClientStateManager, ParrotServer,
                            SequentialExecutor, make_algorithm)
    from repro.core.compression import make_compressor

    algo = make_algorithm(args.algorithm, grad_fn, args.lr,
                          local_epochs=args.local_epochs)
    state_dir = args.ckpt_dir or tempfile.mkdtemp(prefix="parrot_state_")
    sm = ClientStateManager(os.path.join(state_dir, "client_state"))
    devices = jax.local_devices()
    executors = [SequentialExecutor(k, algo, state_manager=sm,
                                    client_block=args.client_block,
                                    device=devices[k % len(devices)])
                 for k in range(args.executors)]
    ckpt = CheckpointManager(os.path.join(state_dir, "ckpt"),
                             every_rounds=args.ckpt_every) \
        if args.ckpt_dir else None
    return ParrotServer(
        params=params, algorithm=algo, executors=executors,
        data_by_client=data, clients_per_round=args.clients_per_round,
        scheduler_policy=args.scheduler, time_window=args.time_window,
        compressor=make_compressor(args.compression),
        checkpoint_manager=ckpt, seed=args.seed)


def main(argv=None) -> None:
    args = parse_args(argv)
    enable_compile_cache()
    from repro.checkpoint import restore_latest

    cfg = model_config(args)
    grad_fn, params = build_grad_fn(cfg)
    server = build_server(args, grad_fn, params, build_data(args, cfg))

    start = 0
    if args.resume and args.ckpt_dir:
        restored = restore_latest(server, os.path.join(args.ckpt_dir, "ckpt"))
        if restored is not None:
            start = restored
            print(f"[train] resumed from round {restored}")

    for _ in range(start, args.rounds):
        m = server.run_round()
        print(f"[round {m.round:4d}] makespan={m.makespan:.3f}s "
              f"sched={m.schedule_time*1e3:.2f}ms "
              f"comm={m.comm_bytes/1e6:.2f}MB trips={m.comm_trips} "
              f"K={m.n_executors} est_err={m.estimation_error:.3f}")
    print("[train] done")


if __name__ == "__main__":
    main()
