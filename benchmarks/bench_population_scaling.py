"""Million-client population scaling (DESIGN.md §11): peak RSS and
selection cost vs population size at a FIXED cohort.

Each cell runs in a SUBPROCESS (``ru_maxrss`` is process-wide and
monotonic, so cells must not share a process): build a streamed
``make_classification_population(M)``, run a few training rounds with
``clients_per_round`` held constant, then report the peak RSS and the
amortised ``select_clients`` latency.  With the registry-backed
population, dataset residency is bounded by the fetch cache and client
state by the tier budgets — RSS must stay essentially flat in M (the
registry itself is one int64 array, 8 bytes/client), and selection must
scale with the cohort, not the population.

Reported per cell: peak RSS (MB), selection latency (us/draw), round
wall.  Derived rows pin the ISSUE acceptance bars:

  population_scaling/rss_ratio_100k_over_1k   <= 1.5   (CI-smoked)
  population_scaling/rss_ratio_1m_over_1k     <= 1.5   (full grid runs)
  population_scaling/sel_ratio_1m_over_1k     — O(cohort) selection: the
      per-draw latency may grow only logarithmically (searchsorted), not
      linearly, in M

``BENCH_POPULATION_CLIENTS`` (comma list, default
``1000,10000,100000,1000000``) and ``BENCH_POPULATION_ROUNDS`` override
the grid — CI smoke uses ``1000,100000`` to keep the step short.
"""
import json
import os
import subprocess
import sys

from benchmarks import common

SIZES = [int(x) for x in os.environ.get(
    "BENCH_POPULATION_CLIENTS", "1000,10000,100000,1000000").split(",") if x]
ROUNDS = int(os.environ.get("BENCH_POPULATION_ROUNDS", "3"))
COHORT = 64
SEL_DRAWS = 200

CHILD = r"""
import os, sys, json, time, resource, tempfile
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, "src"); sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np
from repro.core import ClientStateManager, ParrotServer, SequentialExecutor, \
    TickTimer, make_algorithm
from repro.data import make_classification_population

M, rounds, cohort, sel_draws = (int(sys.argv[1]), int(sys.argv[2]),
                                int(sys.argv[3]), int(sys.argv[4]))
dim, n_classes = 16, 8

def loss_fn(params, batch):
    logits = batch["x"] @ params["w"] + params["b"]
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(
        logits, batch["y"][:, None].astype(jnp.int32), axis=-1)[:, 0]
    return jnp.mean(lse - gold)

GRAD = jax.jit(jax.value_and_grad(loss_fn))
params = {"w": jnp.zeros((dim, n_classes)), "b": jnp.zeros((n_classes,))}
pop = make_classification_population(
    M, dim=dim, n_classes=n_classes, mean_samples=20, batch_size=10,
    seed=0, fetch_cache_bytes=32 << 20)
algo = make_algorithm("scaffold", GRAD, 0.05, local_epochs=1)
sm = ClientStateManager(tempfile.mkdtemp(prefix="popscale_"),
                        memory_budget_bytes=16 << 20, shard_clients=64)
execs = [SequentialExecutor(k, algo, state_manager=sm,
                            timer=TickTimer(1.0)) for k in range(4)]
srv = ParrotServer(params=params, algorithm=algo, executors=execs,
                   data_by_client=pop, clients_per_round=cohort, seed=7)
t0 = time.perf_counter()
for _ in range(rounds):
    srv.run_round()
jax.block_until_ready(jax.tree.leaves(srv.params))
round_wall = time.perf_counter() - t0
# amortised selection latency on a fresh rng (post-run, caches warm):
# O(cohort) + a searchsorted in M, never O(M)
rng = np.random.default_rng(123)
t0 = time.perf_counter()
for _ in range(sel_draws):
    srv.population.sample(rng, cohort)
sel_us = (time.perf_counter() - t0) / sel_draws * 1e6
rss_kb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
print("RESULT" + json.dumps({
    "platform": jax.devices()[0].platform,
    "n_clients": M, "rounds": rounds, "cohort": cohort,
    "rss_mb": rss_kb / 1024.0, "sel_us_per_draw": sel_us,
    "round_wall_s": round_wall,
    "fetch_cache_bytes": pop.cache_bytes,
    "fetches": pop.stats["fetches"], "evictions": pop.stats["evictions"]}))
"""


def _run_cell(m: int):
    r = subprocess.run(
        [sys.executable, "-c", CHILD, str(m), str(ROUNDS), str(COHORT),
         str(SEL_DRAWS)],
        capture_output=True, text=True, timeout=1800,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    if r.returncode != 0:
        raise RuntimeError(f"population cell M={m} failed:\n"
                           + r.stderr[-3000:])
    line = [l for l in r.stdout.splitlines() if l.startswith("RESULT")][-1]
    return json.loads(line[len("RESULT"):])


def run() -> None:
    cells = {m: _run_cell(m) for m in SIZES}
    for m, c in sorted(cells.items()):
        common.emit(f"population_scaling/{m}/rss", c["rss_mb"] * 1e3,
                    f"platform={c['platform']} rss_mb={c['rss_mb']:.1f} "
                    f"sel_us={c['sel_us_per_draw']:.1f} "
                    f"round_wall_s={c['round_wall_s']:.2f} "
                    f"fetches={c['fetches']} evictions={c['evictions']}")
        common.emit(f"population_scaling/{m}/select",
                    c["sel_us_per_draw"],
                    f"platform={c['platform']} cohort={c['cohort']} "
                    f"sel_us={c['sel_us_per_draw']:.1f}")
    base = cells.get(min(SIZES))
    for m in SIZES:
        if m == min(SIZES):
            continue
        c = cells[m]
        rss_ratio = c["rss_mb"] / max(base["rss_mb"], 1e-9)
        sel_ratio = c["sel_us_per_draw"] / max(base["sel_us_per_draw"], 1e-9)
        tag = f"{m // 1000}k" if m < 10**6 else f"{m // 10**6}m"
        base_tag = (f"{min(SIZES) // 1000}k" if min(SIZES) < 10**6
                    else f"{min(SIZES) // 10**6}m")
        common.emit(f"population_scaling/rss_ratio_{tag}_over_{base_tag}",
                    rss_ratio,
                    f"platform={c['platform']} "
                    f"rss_ratio={rss_ratio:.3f} bound=1.5 "
                    f"pass={rss_ratio <= 1.5}")
        common.emit(f"population_scaling/sel_ratio_{tag}_over_{base_tag}",
                    sel_ratio,
                    f"platform={c['platform']} sel_ratio={sel_ratio:.2f}")
