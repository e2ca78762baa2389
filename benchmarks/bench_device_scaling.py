"""Device-parallel executor scaling (DESIGN.md §8): simulation throughput
vs local device count.

Each cell runs in a SUBPROCESS (the device count is frozen at backend
init): ``--xla_force_host_platform_device_count=N`` with one executor per
virtual device, the device-resident stacked-batch cache, non-blocking
steady-state dispatch, and SPMD gang dispatch (one sharded execution per
block wave) — versus the ``1dev`` cell, which is the pre-placement
single-device path (unpinned executors, per-block host staging and sync).
A ``1dev_devpath`` cell (the full device stack pinned to one device)
separates the cache/pipelining contribution from true device parallelism.

Every cell pins XLA intra-op threading to one thread
(``--xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1``):
virtual CPU host devices share the machine, so un-pinned intra-op
threading lets the single-device cell consume every core and the
device-count axis measures nothing.  With it pinned, the axis isolates
exactly what it claims — executor-level device parallelism (on real
accelerators, intra-device parallelism is orthogonal to this axis).

Reported per cell: client local-steps/sec over the timed rounds (compiles
happen in the warmup rounds) plus a bit-level digest of the final params —
the speedup only counts if every cell converges to the *identical* model.
BSP fold order is executor order, independent of wall timing; at this
model size (~2.7k elements/group, below ``psum_min_elements``) the global
fold takes the colocating left-fold, which is trivially bit-identical —
the shard_map/psum branch itself is pinned bit-exact by
``tests/test_device_parallel.py`` and the parity driver's forced-psum
end-to-end case.

Acceptance target (ISSUE 4): the 4-device cell reaches >= 2x the steps/s
of the single-device path at equal round results.  CAVEAT on this
container: the CI host has 2 physical cores, so even perfect 4-device
parallelism cannot exceed 2x, and the measured XLA ceiling is lower — one
sharded gang execution runs its 4 per-device shards at ~90% parallel
efficiency but is bound by the 2 cores (isolated microbenchmark: 1.45x at
4 devices, `/tmp`-style run in tests/device_parity_driver.py workloads).
The recorded numbers (~1.2x end-to-end at 4 devices, bit-exact params)
demonstrate the device axis works; the full multiplier needs >= K cores or
real accelerators, where per-device queues also overlap without ganging.

``BENCH_DEVICE_SCALING_ROUNDS`` / ``BENCH_DEVICE_SCALING_REPS`` override
the timed round and repetition counts.
"""
import json
import os
import subprocess
import sys

from benchmarks import common

ROUNDS = int(os.environ.get("BENCH_DEVICE_SCALING_ROUNDS", "10"))
REPS = int(os.environ.get("BENCH_DEVICE_SCALING_REPS", "3"))
WARMUP = 3
K = 4                     # executors (fixed: only the device count varies)
N_CLIENTS = 128           # every client selected every round (warm caches)
LOCAL_EPOCHS = 1
N_BATCHES = 8
BATCH_SIZE = 128

CHILD = r"""
import os, sys, hashlib, json, time
n_dev = int(sys.argv[1]); rounds = int(sys.argv[2]); warmup = int(sys.argv[3])
devpath = bool(int(sys.argv[4]))
flags = os.environ.get("XLA_FLAGS", "")
os.environ["XLA_FLAGS"] = (
    flags + f" --xla_force_host_platform_device_count={n_dev}"
    " --xla_cpu_multi_thread_eigen=false intra_op_parallelism_threads=1"
).strip()
sys.path.insert(0, "src"); sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np, tempfile
from repro.core import ClientStateManager, ParrotServer, SequentialExecutor, \
    make_algorithm
from repro.core.algorithms import ClientData

K, n_clients, E, nb, bs = %(K)d, %(n_clients)d, %(E)d, %(nb)d, %(bs)d
dim, hidden = 32, 64

def loss_fn(params, batch):
    x = batch["x"]
    h = jax.nn.relu(x @ params["w0"] + params["b0"])
    logits = h @ params["w1"] + params["b1"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(lse - gold)

GRAD = jax.jit(jax.value_and_grad(loss_fn))
k1, k2 = jax.random.split(jax.random.PRNGKey(0))
params = {"w0": jax.random.normal(k1, (dim, hidden)) / np.sqrt(dim),
          "b0": jnp.zeros((hidden,)),
          "w1": jax.random.normal(k2, (hidden, 10)) / np.sqrt(hidden),
          "b1": jnp.zeros((10,))}
rng = np.random.default_rng(0)
# uniform client signature: steady state is one executable per device (and
# one sharded gang executable), reached inside the warmup rounds
data = {c: ClientData(
    batches=[{"x": rng.standard_normal((bs, dim)).astype(np.float32),
              "y": rng.integers(0, 10, bs).astype(np.int32)}
             for _ in range(nb)], n_samples=bs * nb)
    for c in range(n_clients)}
algo = make_algorithm("fedavg", GRAD, 0.05, local_epochs=E)
sm = ClientStateManager(tempfile.mkdtemp(prefix="devscale_"))
devices = jax.devices() if devpath else None
kw = {} if devpath else dict(batch_cache_bytes=0, nonblocking=False)
execs = [SequentialExecutor(k, algo, state_manager=sm, client_block=16,
                            device=None if devices is None
                            else devices[k %% len(devices)], **kw)
         for k in range(K)]
srv = ParrotServer(params=params, algorithm=algo, executors=execs,
                   data_by_client=data, clients_per_round=n_clients,
                   scheduler_policy="uniform", seed=0)
for _ in range(warmup):
    srv.run_round()
jax.block_until_ready(jax.tree.leaves(srv.params))
t0 = time.perf_counter()
for _ in range(rounds):
    srv.run_round()
# non-blocking dispatch leaves device work in flight: the timed span ends
# only when the final params are actually materialised
jax.block_until_ready(jax.tree.leaves(srv.params))
wall = time.perf_counter() - t0
n_steps = rounds * n_clients * E * nb
digest = hashlib.sha256()
for leaf in jax.tree.leaves(srv.params):
    digest.update(np.ascontiguousarray(np.asarray(leaf)).tobytes())
print("RESULT" + json.dumps({
    "platform": jax.devices()[0].platform,
    "n_devices": n_dev, "devpath": devpath, "wall_s": wall,
    "steps": n_steps, "steps_per_s": n_steps / wall,
    "digest": digest.hexdigest()}))
"""


def _run_cell(n_dev: int, devpath: bool):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    script = CHILD % {"K": K, "n_clients": N_CLIENTS, "E": LOCAL_EPOCHS,
                      "nb": N_BATCHES, "bs": BATCH_SIZE}
    r = subprocess.run([sys.executable, "-c", script, str(n_dev),
                        str(ROUNDS), str(WARMUP), str(int(devpath))],
                       capture_output=True, text=True, timeout=1800, env=env)
    if r.returncode != 0:
        raise RuntimeError(f"device-scaling cell n_dev={n_dev} failed:\n"
                           + r.stderr[-3000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def run() -> None:
    # interleaved best-of-REPS per cell: the cells are subprocesses on a
    # shared host, and slow co-tenant phases would otherwise land entirely
    # on one cell and fake (or hide) a speedup
    grid = [("1dev", 1, False), ("1dev_devpath", 1, True),
            ("2dev", 2, True), ("4dev", 4, True)]
    cells = {}
    for _ in range(REPS):
        for name, n_dev, devpath in grid:
            c = _run_cell(n_dev, devpath)
            if name not in cells or \
                    c["steps_per_s"] > cells[name]["steps_per_s"]:
                cells[name] = c
    for name, c in cells.items():
        common.emit(f"device_scaling/{name}/steps_per_s",
                    1e6 / max(c["steps_per_s"], 1e-9),
                    f"platform={c['platform']} "
                    f"steps_per_s={c['steps_per_s']:.1f} "
                    f"wall_s={c['wall_s']:.2f} steps={c['steps']}")
    base = cells["1dev"]
    for name in ("1dev_devpath", "2dev", "4dev"):
        c = cells[name]
        speedup = c["steps_per_s"] / max(base["steps_per_s"], 1e-9)
        exact = c["digest"] == base["digest"]
        common.emit(f"device_scaling/{name}/speedup", speedup,
                    f"platform={c['platform']} "
                    f"speedup_x={speedup:.2f} params_bitexact={exact}")
