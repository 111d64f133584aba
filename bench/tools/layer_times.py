#!/usr/bin/env python3
"""Where a cell's step goes, layer by layer, by the program's own names.

    python3 bench/tools/layer_times.py --workload clustering.dense-512 \\
        --seed 2200000001 [--seconds 10] [--record PATH]

Runs the cell as ``bench/run_cell.py --trace 1`` does and prints the same
result line on standard output, then one more JSON line: ``layers``, the
ms per simulated step of each layer the program names with a ``sim.*``
scope (device time, mean over chips) or span (``guard_host_ms_per_step``:
the guards' host-side check), and ``unscoped_device_ms_per_step``, the
device time in no scope; ``idle_gaps``, the longest stretches in which a
chip ran nothing, each named by the innermost host span over most of it;
and ``scopes``, the scopes found in each compiled program.  A program
without scopes or spans gives empty ``layers`` and names its gaps as the
benchmark does.  ``--record`` writes the traced window as a small JSON
file that ``bench/tests`` reads back.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def traced(cell, seed, seconds, *, t_start, require_chip=True, root=ROOT):
    """``run.run`` with tracing on, the result with ``layers``,
    ``layer_gaps``, ``scopes`` and ``trace_context`` added: the program's
    names are read from the same trace, and the benchmark's own readings
    stay as the harness gives them."""
    from benchlib import hlo, layers, run, trace as tr

    seen = {}
    normalize, context = tr.normalize, run.trace_context

    def keep_names(trace_dir, device_ids):
        seen["norm"] = layers.normalize(trace_dir, device_ids)
        return normalize(trace_dir, device_ids)

    def keep_scopes(cell, sim, compiled, norm, steps, *rest):
        seen["scopes"] = {hlo.module_name(t): layers.names_by_scope(t)
                          for t in (c.as_text() for c in compiled.values())}
        seen["ctx"] = context(cell, sim, compiled, norm, steps, *rest)
        return seen["ctx"]

    tr.normalize, run.trace_context = keep_names, keep_scopes
    try:
        result = run.run(cell, seed, seconds, True, t_start=t_start,
                         require_chip=require_chip, root=root)
    finally:
        tr.normalize, run.trace_context = normalize, context
    norm, ctx = seen["norm"], seen["ctx"]
    w = tr.window(norm)
    result.update(
        layers=layers.readings(norm, seen["scopes"], ctx["steps"]),
        layer_gaps=layers.idle_gaps(norm, w) if w else [],
        scopes=seen["scopes"], norm=norm, trace_context=ctx)
    return result


def record(result, seed, workload, device_kind):
    """The traced window in the form ``bench/tests`` reads: device ops and
    spans inside the window, module names interned, each module's scopes
    cut to the instructions that ran, and every reading."""
    from benchlib import trace as tr

    norm, ctx = result["norm"], result["trace_context"]
    w = tr.window(norm)
    mods = sorted({m for evs in norm["devices"].values()
                   for _, _, _, m in evs})
    ran = {n for evs in norm["devices"].values() for n, _, _, _ in evs}
    return {
        "source": f"{workload} on one {device_kind}, seed {seed}, "
                  "--trace 1: the normalized trace of the traced window "
                  "with module names interned, and each module's sim.* "
                  "scopes restricted to the instructions in the trace",
        "modules": mods,
        "devices": {d: [[n, s, e, mods.index(m)] for n, s, e, m in evs
                        if tr.clip([(s, e)], w)]
                    for d, evs in norm["devices"].items()},
        "host": [h for h in norm["host"] if tr.clip([(h[1], h[2])], w)],
        "scopes": {m: {s: sorted(set(n) & ran) for s, n in sc.items()}
                   for m, sc in result["scopes"].items()},
        "context": {"steps": ctx["steps"],
                    "kernels": sorted(ctx["kernels"]),
                    "sorts": sorted(ctx["sorts"]),
                    "modules": sorted(ctx["modules"]),
                    "collectives": sorted(ctx["collectives"]),
                    "pairs_per_step": ctx["pairs_per_step"]},
        "readings": dict({k: v["value"]
                          for k, v in result["metrics"].items()},
                         **result["layers"]),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--record", default=None)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

    from benchlib import run, spec

    cell = spec.load_cell(args.workload, ROOT)
    result = traced(cell, args.seed, args.seconds, t_start=T_START)
    run.emit(result)
    print(json.dumps({
        "layers": result["layers"], "idle_gaps": result["layer_gaps"],
        "scopes": {m: sorted(sc) for m, sc in result["scopes"].items()}}),
        flush=True)
    if args.record:
        with open(args.record, "w") as f:
            json.dump(record(result, args.seed, args.workload,
                             result["device"]["kind"]), f,
                      separators=(",", ":"))
    return 0


if __name__ == "__main__":
    sys.exit(main())
