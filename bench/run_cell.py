#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run_cell.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

``<name>`` is a cell of ``BENCHMARK.json``.  Progress goes to standard
error; the last line of standard output is one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics``, ``device`` (and, with
``--trace 1``, ``breakdown``) and last ``checks``: each number of the
comparison with the plain reference beside its limit.  Without the chips
the cell asks for, or without the program's ``src/repro`` in the checkout,
the run exits non-zero and prints no result.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"run_cell: the program (src/repro) is not in {ROOT}",
              file=sys.stderr)
        return 2
    # caches and logs stay inside the checkout, at fixed paths
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [BENCH, src]

    from benchlib import run, spec

    try:
        cell = spec.load_cell(args.workload, ROOT)
        result = run.run(cell, args.seed, args.seconds, bool(args.trace),
                         t_start=T_START, root=ROOT)
    except run.NoChip as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 1
    except spec.SpecError as e:
        print(f"run_cell: {e}", file=sys.stderr)
        return 2
    run.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
