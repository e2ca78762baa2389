import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: ``lower().compile()`` every (arch × shape × mesh) cell.

Proves the distribution config is coherent without hardware: sharding
mismatches, compile-time OOM and unsupported collectives all fail here.
Outputs one JSON per cell (memory analysis, cost analysis, per-kind
collective bytes) under ``results/dryrun/`` — the roofline analysis
(benchmarks/roofline.py) consumes them.

Usage:
  python -m repro.launch.dryrun --arch qwen2-0.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--arch-filter moe]
"""
import argparse
import json
import time
import traceback

import jax

from repro.configs.base import ALL_SHAPES, shape_by_name
from repro.configs.registry import ARCHS, cell_is_runnable, get_arch
from repro.launch.hlo_analysis import collective_stats, compute_stats
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_production_mesh

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir: str = RESULTS_DIR, verbose: bool = True,
             overrides: dict | None = None) -> dict:
    cfg = get_arch(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    shape = shape_by_name(shape_name)
    ok, why = cell_is_runnable(cfg, shape)
    tag = f"{arch}__{shape_name}__{'multipod' if multi_pod else 'pod'}"
    if not ok:
        rec = {"cell": tag, "status": "skipped", "reason": why}
        _save(rec, out_dir, tag)
        if verbose:
            print(f"[dryrun] {tag}: SKIP ({why})")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    from repro.sharding import enable_activation_policy
    enable_activation_policy(mesh)
    spec = input_specs(cfg, shape, mesh)
    t0 = time.perf_counter()
    rec = {"cell": tag, "arch": arch, "shape": shape_name,
           "multi_pod": multi_pod, "mesh": dict(zip(mesh.axis_names,
                                                    mesh.devices.shape))}
    try:
        with jax.set_mesh(mesh):
            jitted = jax.jit(spec.step_fn, in_shardings=spec.in_shardings,
                             donate_argnums=spec.donate_argnums)
            lowered = jitted.lower(*spec.args)
            t_lower = time.perf_counter() - t0
            compiled = lowered.compile()
            t_compile = time.perf_counter() - t0 - t_lower

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis()
        hlo = compiled.as_text()
        coll = collective_stats(hlo)
        comp = compute_stats(hlo)
        n_dev = mesh.devices.size

        rec.update({
            "status": "ok",
            "step": spec.static_desc,
            "lower_s": round(t_lower, 2),
            "compile_s": round(t_compile, 2),
            "n_devices": n_dev,
            "memory": _mem_dict(mem),
            # raw cost_analysis (counts scan bodies once — kept for reference)
            "xla_flops_per_device": cost.get("flops", 0.0),
            "xla_bytes_per_device": cost.get("bytes accessed", 0.0),
            # trip-count-aware estimates (see hlo_analysis.compute_stats)
            "flops_per_device": comp["flops_per_device"],
            "bytes_per_device": comp["bytes_per_device_est"],
            "collectives": coll,
            "model": {"n_params": get_arch(arch).n_params(),
                      "n_active_params": get_arch(arch).n_active_params()},
        })
        if verbose:
            print(f"[dryrun] {tag}: OK  lower {t_lower:.1f}s  "
                  f"compile {t_compile:.1f}s")
            print(f"  memory_analysis: {rec['memory']}")
            print(f"  flops/dev={rec['flops_per_device']:.3e} "
                  f"bytes/dev={rec['bytes_per_device']:.3e} "
                  f"(xla raw: {rec['xla_flops_per_device']:.3e})")
            print(f"  collectives: {coll['summary']}")
    except Exception as e:  # noqa: BLE001 — report and continue the sweep
        rec.update({"status": "error", "error": f"{type(e).__name__}: {e}",
                    "traceback": traceback.format_exc()[-2000:]})
        if verbose:
            print(f"[dryrun] {tag}: ERROR {type(e).__name__}: {e}")
    _save(rec, out_dir, tag)
    return rec


def _mem_dict(mem) -> dict:
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes"):
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def _save(rec: dict, out_dir: str, tag: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, f"{tag}.json"), "w") as f:
        json.dump(rec, f, indent=1, default=str)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--arch-filter", default="")
    ap.add_argument("--out-dir", default=RESULTS_DIR)
    args = ap.parse_args()

    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    n_ok = n_err = n_skip = 0
    if args.all:
        for name in ARCHS:
            if args.arch_filter and args.arch_filter not in name:
                continue
            for shape in ALL_SHAPES:
                for mp in meshes:
                    rec = run_cell(name, shape.name, mp, args.out_dir)
                    n_ok += rec["status"] == "ok"
                    n_err += rec["status"] == "error"
                    n_skip += rec["status"] == "skipped"
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        for mp in meshes:
            rec = run_cell(args.arch, args.shape, mp, args.out_dir)
            n_ok += rec["status"] == "ok"
            n_err += rec["status"] == "error"
            n_skip += rec["status"] == "skipped"
    print(f"[dryrun] done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
