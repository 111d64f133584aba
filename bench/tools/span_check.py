#!/usr/bin/env python3
"""Largest per-cell occupancy over a span, against the cell capacity.

    JAX_PLATFORMS=cpu python3 bench/tools/span_check.py \\
        --config cell_clustering --traffic dense-512 --cells 128 \\
        --seeds 0-11

Draws each seed's population as the benchmark does, on a grid of
``--cells`` x ``--cells`` cells, drives the program's normal path for the
mix's ``span_steps`` steps and prints, per seed, the fullest cell after
every step and the agents dropped.  The evidence for a configuration's
cap and a mix's span is recorded in the configuration file.
"""

import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(BENCH), "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--cells", type=int, default=128)
    ap.add_argument("--seeds", default="0-11")
    args = ap.parse_args()

    import numpy as np

    from benchlib import run, spec, traffic

    with open(os.path.join(BENCH, "configs", args.config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(BENCH, "traffic", args.traffic + ".json")) as f:
        mix = json.load(f)
    mix = dict(mix, interior=[args.cells, args.cells], mesh_shape=[1, 1])
    cell = spec.Cell(name="span_check", chips=1, run_seconds=0,
                     config_name=args.config, config=cfg,
                     traffic_name=args.traffic, traffic=mix, limits={},
                     reference=None, end_to_end=[], per_layer=[])
    lo, _, hi = args.seeds.partition("-")
    for seed in range(int(lo), int(hi or lo) + 1):
        sim = run.build_sim(cell)
        pos, attrs = traffic.draw_agents(cfg, mix, seed)
        sim.init(pos, attrs, seed=traffic.engine_seed(seed))
        peaks = []
        for _ in range(int(mix["span_steps"])):
            sim.run(1)
            occ = np.asarray(sim.state.soa.valid).sum(axis=-1)
            peaks.append(int(occ.max()))
        dropped = int(np.asarray(sim.state.dropped).sum())
        print(json.dumps({"seed": seed, "cap": cfg["cap"],
                          "max_occupancy": max(peaks),
                          "per_step": peaks, "dropped": dropped}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
