"""One run of one benchmark cell, driven by ``BENCHMARK.json`` and the
files its names point at:

  configs/<config>.json     the model's sizes under its published key names,
                            and its ``"family"``
  families/<family>.py      the kind of model: its sizes, the program's
                            settings for them, seeded weights, the
                            reference loss and the counts
  traffic/<traffic>.json    the federation and the round settings
  limits/<workload>.json    the limit of every number ``correct`` compares
  metrics/<metric>.py       one reader per metric: ``read(ctx)``
  peaks.json                the chip's peaks, keyed by ``device_kind``

A run builds the cell (``build``) through the program's normal path
(``launch/train.py``'s ``model_config`` and ``build_server``:
``ParrotServer`` -> executor -> the compiled client step -> local fold ->
codec -> server update), with weights and data made here from the seed.
Set-up ends after the cell's first rounds, which the reference follows
later.  The window then runs whole rounds back to back, each ended by
``block_until_ready`` on the server's parameters, until ``seconds`` have
passed.  With ``trace`` it instead records a profiler trace of a few rounds
and reports the per-layer metrics, read from the trace's programs and from
its reduction by the program's own spans and scopes (``spans.layers``).
Last, with the program's state freed, the reference runs and decides
``correct``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import gc
import importlib.util
import json
import math
import os
import shutil
import tempfile
import time

from perfbench import compare, datagen, modelcfg, trace, weights

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
ROUND_SPAN = trace.PREFIX + "run_round"
SYNC_SPAN = trace.PREFIX + "sync"


class NoChip(SystemExit):
    """The run needs chips that JAX does not see: exit non-zero, print no
    result."""


def _load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = _load_json(root, "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; "
                         f"known: {sorted(cells)}")
    w = cells[workload]

    def mine(metrics):
        return [m for m in metrics
                if workload in m.get("workloads", [workload])]

    return Cell(name=workload, chips=int(w["chips"]),
                config=modelcfg.load_config(w["config"]),
                traffic=_load_json(HERE, "traffic", f"{w['traffic']}.json"),
                limits=_load_json(HERE, "limits", f"{workload}.json"),
                end_to_end=mine(bench["end_to_end"]),
                per_layer=mine(bench["per_layer"]))


def load_reader(name: str):
    path = os.path.join(HERE, "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(kind: str) -> dict:
    table = _load_json(HERE, "peaks.json")
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r}; "
                       f"known: {sorted(table)}")
    return table[kind]


def require_chips(n: int):
    """The local devices, when they are at least ``n`` TPU chips; else
    ``NoChip``."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"perfbench: needs a TPU, JAX found "
                     f"{devs[0].platform!r}")
    if len(devs) < n:
        raise NoChip(f"perfbench: the cell needs {n} chips, JAX sees "
                     f"{len(devs)}")
    return devs


# --------------------------------------------------------------------- build

def train_argv(traffic: dict, program_arch: str, seed: int) -> list:
    return ["--model", "lm", "--arch", program_arch, "--full-config",
            "--algorithm", traffic["algorithm"],
            "--executors", str(traffic["executors"]),
            "--client-block", str(traffic["client_block"]),
            "--clients", str(traffic["clients"]),
            "--clients-per-round", str(traffic["clients_per_round"]),
            "--local-epochs", str(traffic["local_epochs"]),
            "--lr", str(traffic["lr"]),
            "--compression", traffic["compression"],
            "--seed", str(seed)]


def program_config(train, args, config: dict, family, m):
    """The program's ``ModelConfig`` for this configuration file: the
    registry's entry through ``train.model_config``, with every size the
    file states (``family.program_fields``) and its ``"program"``
    settings."""
    cfg = train.model_config(args)
    return dataclasses.replace(cfg, **family.program_fields(m),
                               **config.get("program", {}))


def check_layout(tree, cfg) -> None:
    """The benchmark's weights must have the program's layout exactly."""
    import jax
    from repro.models import lm
    want = jax.eval_shape(lambda k: lm.init_params(k, cfg),
                          jax.random.key(0))
    if jax.tree.structure(want) != jax.tree.structure(tree):
        raise ValueError(f"weight layout differs from the program's: "
                         f"{jax.tree.structure(tree)} vs "
                         f"{jax.tree.structure(want)}")
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(want)):
        if a.shape != b.shape or a.dtype != b.dtype:
            raise ValueError(f"leaf {a.shape} {a.dtype} != program's "
                             f"{b.shape} {b.dtype}")


def grad_fn_of(train, cfg):
    """``build_grad_fn``'s loss-gradient function without the weights it
    draws (under ``eval_shape`` they are shapes only, drawn from nothing)."""
    import jax
    box = {}

    def build():
        box["fn"], params = train.build_grad_fn(cfg)
        return params

    jax.eval_shape(build)
    return box["fn"]


def _load_class(path: str):
    """``package.module.Class`` -> the class."""
    import importlib
    mod, _, name = path.rpartition(".")
    return getattr(importlib.import_module(mod), name)


def model_of(cell: Cell):
    """(family module, its dims) of the cell's configuration."""
    family = modelcfg.load_family(cell.config)
    return family, family.dims(cell.config)


def streams_of(cell: Cell, m, seed: int) -> dict:
    """client -> token batches of the cell's traffic, from ``seed``."""
    t = cell.traffic
    return datagen.token_streams(seed, t["clients"], m.vocab, t["seq_len"],
                                 t["batch_size"], t["batches_per_client"])


@dataclasses.dataclass
class Build:
    """One cell as a run builds it."""
    family: object            # families/<family>.py of the configuration
    dims: object              # family.dims(config)
    program: object           # the program's ModelConfig
    streams: dict             # client -> token batches from the seed
    server: object            # the program's ParrotServer
    devices: list


def build(cell: Cell, seed: int, require_tpu: bool = True) -> Build:
    """The cell's server on the program's normal path, with weights and
    clients made from ``seed``.  With ``require_tpu``, ``NoChip`` unless
    the cell's chips are there."""
    import jax
    devices = (require_chips(cell.chips) if require_tpu
               else jax.devices()[:cell.chips])
    from repro.core.algorithms import ClientData
    from repro.launch import train
    t, cfgd = cell.traffic, cell.config
    family, m = model_of(cell)
    args = train.parse_args(train_argv(t, cfgd["program_arch"], seed))
    cfg = program_config(train, args, cfgd, family, m)
    params = weights.make_on_device(family, m, seed)
    check_layout(params, cfg)
    streams = streams_of(cell, m, seed)
    data = {c: ClientData(batches=b, n_samples=t["batch_size"] * len(b))
            for c, b in streams.items()}
    server = train.build_server(args, grad_fn_of(train, cfg), params, data)
    if t.get("communicator"):
        server.comm = _load_class(t["communicator"])()
    return Build(family, m, cfg, streams, server, devices)


# -------------------------------------------------------------------- window

def _rounds(server, until, annotate):
    """Run whole rounds back to back until ``until(rounds, elapsed)`` says
    stop, each ended by ``block_until_ready`` on the server's parameters,
    so no round's buffers are allocated beside the running round's (the
    cells fill most of the chip's memory).  Returns (rounds, seconds, comm bytes of the last round)."""
    import jax
    t0 = time.perf_counter()
    n, comm = 0, 0
    while True:
        with annotate(ROUND_SPAN):
            m = server.run_round()
        n += 1
        comm = m.comm_bytes
        with annotate(SYNC_SPAN):
            jax.block_until_ready(server.params)
        if until(n, time.perf_counter() - t0):
            break
    return n, time.perf_counter() - t0, comm


def _peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def _free_device_state() -> None:
    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


# ------------------------------------------------------------------- the run

def run(cell: Cell, seed: int, seconds: float, trace_on: bool, *,
        t_start: float, require_tpu: bool = True,
        compile_cache: bool = True, log=print) -> dict:
    """One run; returns the result line's object."""
    import jax
    from repro.core import client_step
    from repro.launch import train

    if compile_cache:
        log(f"compile cache: {train.enable_compile_cache()}")
        # every program, however quick to compile, is kept: set-up after
        # the first run of a cell compiles nothing
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    t = cell.traffic
    b = build(cell, seed, require_tpu)
    family, m, cfg, streams, server, devices = (
        b.family, b.dims, b.program, b.streams, b.server, b.devices)
    dev = devices[0]
    del b
    n_params = sum(int(x.size) for x in jax.tree.leaves(server.params))
    log(f"cell {cell.name}: {cfg.name} {cfg.n_layers} layers d_model "
        f"{cfg.d_model} vocab {cfg.vocab_size} {cfg.dtype}, {n_params} "
        f"params; {t['clients']} clients x {t['batches_per_client']} "
        f"batches of {t['batch_size']}x{t['seq_len']}, "
        f"{t['clients_per_round']} per round, compression "
        f"{t['compression']}; device {dev.device_kind} x{len(devices)}")

    # the first rounds: the cell's warm-up, and what the reference follows
    nref = int(t["reference_rounds"])
    snap = {}
    for r in range(1, nref + 1):
        server.run_round()
        if r in (1, nref):
            snap[r] = jax.device_get(server.params)
    jax.block_until_ready(server.params)
    setup_s = time.perf_counter() - t_start
    log(f"set-up {setup_s:.3f} s (process start to the first timed round, "
        f"{nref} rounds included); peak bytes so far "
        f"{_peak_bytes(devices)}")

    c0 = client_step.compile_events()
    ctx = {"cell": cell, "dims": m, "seq_len": t["seq_len"],
           "steps_per_round": (t["clients_per_round"] * t["local_epochs"]
                               * t["batches_per_client"]),
           "tokens_per_step": t["batch_size"] * t["seq_len"],
           "chips": cell.chips, "setup_s": setup_s,
           "peaks": peaks_for(dev.device_kind) if require_tpu else None}
    ctx["flops_per_round"] = (ctx["steps_per_round"] * ctx["tokens_per_step"]
                              * family.train_flops_per_token(
                                  m, t["seq_len"]))
    ctx["fold_bytes_per_round"] = t["executors"] * modelcfg.fold_bytes(
        family.n_params(m),
        math.ceil(t["clients_per_round"] / t["executors"]))
    if trace_on:
        ctx.update(_traced(server, int(t["trace_rounds"]), log))
        s = ctx["trace"]
        for name, sec in sorted(s.programs.items(), key=lambda kv: -kv[1]):
            log(f"program {name}: {sec:.9f} s in {s.calls[name]} calls")
        log(f"traced {ctx['rounds']} rounds: busy {s.busy_s:.6f} s of "
            f"{s.window_s:.6f} s; longest gaps {s.gaps}")
    else:
        ctx["rounds"], ctx["window_s"], comm = _rounds(
            server, lambda n, el: el >= seconds, contextlib.nullcontext)
        log(f"window {ctx['window_s']:.6f} s, {ctx['rounds']} rounds, "
            f"{comm} wire bytes in the last round")
    in_window = client_step.compile_events() - c0
    log(f"compile events inside the window: {in_window}")
    peak = _peak_bytes(devices)
    ctx["peak_bytes"] = peak

    del server
    _free_device_state()
    nums = reference_numbers(cell, family, m, seed, streams, snap, nref,
                             float(t["lr"]), log=log)
    correct, rows = compare.verdict(nums, cell.limits)

    wanted = cell.per_layer if trace_on else cell.end_to_end
    metrics = {}
    for spec in wanted:
        value = load_reader(spec["name"])(_Ctx(ctx))
        if value is not None:
            metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    out = {"correct": bool(correct), "attempted": ctx["rounds"],
           "failed": 0, "metrics": metrics, "device": device}
    if trace_on:
        s = ctx["trace"]
        device["busy_s"] = s.busy_s
        device["window_s"] = s.window_s
        top = sorted(s.programs.items(), key=lambda kv: -kv[1])[:10]
        out["breakdown"] = {"device_ops": [[n, v] for n, v in top],
                            "idle_gaps": [[n, v] for n, v in s.gaps[:10]]}
    out["numbers"] = {k: v for k, v in nums.items() if isinstance(v, float)}
    out["checks"] = {r["name"]: {"value": r["value"], "limit": r["limit"]}
                     for r in rows}
    return out


class _Ctx(dict):
    """A reader's view of the run: the numbers above, plus ``value(name)``,
    another metric's reading."""

    def __init__(self, d):
        super().__init__(d)
        self._cache = {}

    def value(self, name):
        if name not in self._cache:
            self._cache[name] = load_reader(name)(self)
        return self._cache[name]


def _traced(server, rounds: int, log) -> dict:
    """``rounds`` rounds under the profiler: the trace's summary by program
    name (``trace``) and its reduction by the program's spans and scopes
    (``layers``, ``spans.layers``; left out where the trace lacks what it
    reads), both over the rounds' window."""
    import jax
    from perfbench import spans
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    d = tempfile.mkdtemp(prefix="perfbench_trace_")
    layers = None
    try:
        with jax.profiler.trace(d, profiler_options=opts):
            n, el, _ = _rounds(server, lambda n, _: n >= rounds,
                               jax.profiler.TraceAnnotation)
        path = trace.find_xplane(d)
        pd = jax.profiler.ProfileData.from_file(path)
        window = trace.span_window(pd, ROUND_SPAN, SYNC_SPAN)
        summary = trace.summarize(pd, window)
        t0 = time.perf_counter()
        try:
            layers = spans.layers(pd, window, n, spans.op_names(path))
        except ValueError as e:
            log(f"no span reduction: {e}")
        log(f"span reduction: {time.perf_counter() - t0:.1f} s")
    finally:
        shutil.rmtree(d, ignore_errors=True)
    out = {"trace": summary, "rounds": n, "traced_rounds": n,
           "window_s": el}
    if layers is not None:
        detail = layers["detail"]
        log(f"trace lines used: {detail['lines_used']}")
        log("spans: " + json.dumps({k: v for k, v in layers.items()
                                    if k != "detail"}))
        summary.gaps = detail["idle_gaps"]       # named by program phase
        out["layers"] = layers
    return out


# ----------------------------------------------------------------- reference

def reference_rounds(cell: Cell, family, m, seed: int, streams: dict,
                     nrounds: int, lr: float, **kw) -> dict:
    """The reference's parameters after rounds 1 and ``nrounds`` from the
    seed's weights, over the cohorts FedAvg's sampling draws from the
    seed, with the family's loss at sizes ``m``."""
    import jax
    t = cell.traffic
    from perfbench import reference
    cohorts = datagen.cohorts(seed, t["clients"], t["clients_per_round"],
                              nrounds)
    used = sorted({c for co in cohorts for c in co})
    data = {c: [(jax.device_put(b["inputs"]), jax.device_put(b["labels"]))
                for b in streams[c]] for c in used}
    samples = {c: t["batch_size"] * len(streams[c]) for c in used}
    topk = t.get("topk_fraction") if t["compression"] == "topk" else None
    p0 = weights.make_on_device(family, m, seed)
    out = reference.run_rounds(functools.partial(family.loss, m), p0, data,
                               cohorts, lr, samples, topk=topk,
                               keep={1, nrounds}, **kw)
    out[0] = p0
    return out


def reference_numbers(cell: Cell, family, m, seed: int, streams: dict,
                      snap: dict, nrounds: int, lr: float,
                      log=print) -> dict:
    import jax
    t0 = time.perf_counter()
    ref = reference_rounds(cell, family, m, seed, streams, nrounds, lr)
    s1 = jax.device_put(snap[1])
    sn = jax.device_put(snap[nrounds])
    nums = compare.numbers(ref[0], s1, ref[1], sn, ref[nrounds])
    log(f"reference: {nrounds} rounds in {time.perf_counter() - t0:.1f} s; "
        f"worst update leaf {nums['worst_update_leaf']}, worst change leaf "
        f"{nums['worst_change_leaf']}, excluded {nums['excluded_leaves']}")
    log("numbers: " + json.dumps({k: v for k, v in nums.items()
                                  if isinstance(v, float)}))
    return nums
