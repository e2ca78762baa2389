"""Benchmark entry point: one run of one cell on the chips of this machine.

  python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> \
      --trace <0|1>

Run from the root of a checkout.  Exits non-zero, printing no result, when
JAX finds no TPU or fewer chips than the cell needs.  The last line of
standard output is the result as one JSON object; the numbers that decide
``correct`` are the last lines of standard error, each beside its limit.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    opts = ap.parse_args(argv)
    from perfbench import harness
    cell = harness.load_cell(opts.workload)
    out = harness.run(cell, opts.seed, opts.seconds, bool(opts.trace),
                      t_start=T_START,
                      log=lambda s: print(s, file=sys.stderr, flush=True))
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
