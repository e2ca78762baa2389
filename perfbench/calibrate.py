"""Readings that set the limits of ``correct`` (run on the chip, at the
cell's own size; the benchmark's own runs never run this):

  program   the compared numbers of sound runs of the program, one per
            seed: the lower readings;
  control   the reference put in the program's place, computed one
            precision below the configuration's (bfloat16 -> float8 e4m3:
            weights stored and matmul operands rounded to it): the upper
            readings;
  half      the reference with only the first half of each cohort folded
            and averaged (half of the round's batch left out);
  negate    the reference with the first client's update sign-flipped (an
            answer altered where it is produced).

  python3 perfbench/calibrate.py --workload <cell> --seeds 1,2,3 \
      --variant-seeds 4,5,6

Prints one JSON line per reading.  A state left unchanged reads 1 on the
gap numbers by construction and needs no run; a one-chip cell has no
exchange between chips to leave out.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

VARIANTS = {
    "control": dict(storage="float8_e4m3fn", operands="float8_e4m3fn"),
    "half": dict(fault="half"),
    "negate": dict(fault="negate"),
}


def variant_numbers(cell, seed: int, variants, fault_rounds: int = 1,
                    log=print) -> dict:
    """variant -> the compared numbers of that variant, put in the
    program's place, against the reference, on the cell's data for
    ``seed``.  The control follows the reference's rounds; a fault only
    ``fault_rounds``."""
    import jax.numpy as jnp
    from perfbench import compare, harness
    t = cell.traffic
    family, m = harness.model_of(cell)
    n = int(t["reference_rounds"])
    streams = harness.streams_of(cell, m, seed)
    ref = harness.reference_rounds(cell, family, m, seed, streams, n,
                                   float(t["lr"]))
    out = {}
    for v in variants:
        t0 = time.perf_counter()
        kw = dict(VARIANTS[v])
        for k in ("storage", "operands"):
            if k in kw:
                kw[k] = jnp.dtype(kw[k])
        k = n if v == "control" else min(fault_rounds, n)
        alt = harness.reference_rounds(cell, family, m, seed, streams, k,
                                       float(t["lr"]), **kw)
        out[v] = compare.numbers(ref[0], alt[1], ref[1], alt[k], ref[k])
        out[v]["rounds"] = k
        log(f"{v} seed {seed}: {k} rounds, {time.perf_counter() - t0:.1f} s")
        del alt
    del ref
    harness._free_device_state()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--variant-seeds", default="")
    ap.add_argument("--variants", default="control,half,negate")
    ap.add_argument("--fault-rounds", type=int, default=1)
    opts = ap.parse_args(argv)
    from perfbench import harness
    cell = harness.load_cell(opts.workload)
    log = lambda s: print(s, file=sys.stderr, flush=True)  # noqa: E731
    harness.require_chips(cell.chips)
    for s in [int(x) for x in opts.seeds.split(",") if x]:
        out = harness.run(cell, s, 0.0, False, t_start=time.perf_counter(),
                          log=log)
        print(json.dumps({"reading": "program", "seed": s,
                          "correct": out["correct"],
                          "numbers": out["numbers"],
                          "metrics": out["metrics"]}), flush=True)
        harness._free_device_state()
    for s in [int(x) for x in opts.variant_seeds.split(",") if x]:
        got = variant_numbers(cell, s, opts.variants.split(","),
                              opts.fault_rounds, log=log)
        for v, nums in got.items():
            print(json.dumps({"reading": v, "seed": s,
                              "rounds": nums["rounds"], "numbers": {
                                  k: x for k, x in nums.items()
                                  if isinstance(x, float)},
                              "worst": [nums["worst_update_leaf"],
                                        nums["worst_change_leaf"]]}),
                  flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
