#!/usr/bin/env python3
"""Readings that the limits of a cell's comparison are set from.

    python3 bench/calibrate.py --workload clustering.dense-512 \\
        --seeds 1000-1011 --control 3

For each seed, in one process: the cell's set-up as a run makes it, its
first ``check_steps`` steps through the window's own call
(``Simulation.run``), the program's state freed, then

* ``program``: the numbers of the comparison with the float32 reference
  (the lower reading of each limit is the largest over the seeds);
* ``control`` (the first ``--control`` seeds): the reference computed in
  bfloat16, put in the program's place and compared with the float32
  reference in the same way (the upper reading is the smallest).

One JSON line per seed on standard output, then one line with the
largest program reading and the smallest control reading of each number.
The benchmark's own runs never run the control.

With ``--fault <name>`` the program runs with that fault planted under
its timed path (``FAULTS``), no control runs, and the summary gives the
smallest reading of each number: the fault's upper reading.
"""

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def control_numbers(cell, pos, attrs, eseed, answers):
    """The bfloat16 reference in the program's place, against the float32
    reference: same draws, same slots, same comparison."""
    import numpy as np

    from benchlib import compare as cmp, run, traffic

    cfg, mix = cell.config, cell.traffic
    steps = len(answers) - 1
    ctx = {"engine_seed": eseed, "slots": run.slots_by_id(answers, len(pos))}
    ref = cell.reference.run(cfg, mix, pos, attrs, steps, ctx)
    low = cell.reference.run(cfg, mix, pos, attrs, steps, ctx,
                             dtype="bfloat16")
    size = traffic.domain_size(cfg, mix)
    tor = [cfg["boundary"] == "toroidal"] * len(size)
    names = cfg.get("compare_attrs", [])
    ids = np.arange(len(pos), dtype=np.int64)
    as_answer = [dict(lo, ids=ids) for lo in low]
    mags = cmp.magnitudes(np.asarray(pos), ref)
    return cmp.summary([cmp.compare(a, r, size, tor, names, t + 1, mags[t])
                      for t, (a, r) in enumerate(zip(as_answer, ref))])


def plant_no_exchange():
    """The exchange between chips left out: every aura keeps what it
    held, and nothing goes on the wire."""
    import jax.numpy as jnp
    from repro.core import engine

    def no_exchange(geom, soa, comm, refs, cfg, full, owned=None):
        return soa, refs, jnp.int32(0), jnp.int32(0)

    engine.halo_exchange = no_exchange


FAULTS = {"no_exchange": plant_no_exchange}


def main(argv=None, require_chip=True) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="first-last")
    ap.add_argument("--control", type=int, default=3,
                    help="seeds (the first ones) that also run the control")
    ap.add_argument("--fault", choices=sorted(FAULTS),
                    help="plant this fault under the timed path")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ["TPU_LOG_DIR"] = "disabled"
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import jax

    from benchlib import compare as cmp, run, spec, traffic

    cell = spec.load_cell(args.workload, ROOT)
    run.device_info(cell.chips, require_chip)
    from repro.core.compile_cache import enable_persistent_cache
    enable_persistent_cache()
    if args.fault:
        FAULTS[args.fault]()
    label = "fault" if args.fault else "program"
    n_control = 0 if args.fault else args.control
    cfg, mix = cell.config, cell.traffic
    spc, check = int(mix["steps_per_call"]), int(mix["check_steps"])
    names = list(cfg.get("compare_attrs", []))
    ndim = len(mix["interior"])
    lo, _, hi = args.seeds.partition("-")
    seeds = list(range(int(lo), int(hi or lo) + 1))
    prog_max, prog_min, ctrl_min = {}, {}, {}
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        sim = run.build_sim(cell)
        pos, attrs = traffic.draw_agents(cfg, mix, seed)
        eseed = traffic.engine_seed(seed)
        sim.init(pos, attrs, seed=eseed)
        kept = [sim.state]
        for _ in range(check // spc):
            sim.run(spc)
            kept.append(sim.state)
        answers = [cmp.answer_of(s, ndim, names) for s in kept]
        del sim, kept
        gc.collect()
        line = {"seed": seed, label: run.reference_numbers(
            cell, pos, attrs, eseed, answers)}
        if i < n_control:
            line["control"] = control_numbers(cell, pos, attrs, eseed,
                                              answers)
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        for k, v in line[label].items():
            prog_max[k] = max(prog_max.get(k, v), v)
            prog_min[k] = min(prog_min.get(k, v), v)
        for k, v in line.get("control", {}).items():
            ctrl_min[k] = min(ctrl_min.get(k, v), v)
    print(json.dumps({"workload": cell.name, "seeds": len(seeds),
                      "device": jax.devices()[0].device_kind,
                      **({"fault": args.fault, "fault_min": prog_min}
                         if args.fault else
                         {"program_max": prog_max, "control_min": ctrl_min})
                      }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
