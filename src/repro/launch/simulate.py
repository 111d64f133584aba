"""ABM simulation launcher — the TeraAgent-analogue entry point.

    PYTHONPATH=src python -m repro.launch.simulate --sim epidemiology \
        --agents 800 --steps 50 --mesh 2x2 --delta int16 --rebalance 10

Every sim runs through the :class:`repro.core.Simulation` facade: spatial
meshes map devices to the partitioning grid exactly as the paper maps MPI
ranks (Figure 1); ``--delta`` enables the §2.3 delta-encoded aura exchange;
``--rebalance`` arms the §2.4.5 dynamic load balancer (the facade keeps its
engine/state consistent across any mid-run re-shard).
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core import DeltaConfig, Rebalance, total_agents
from repro.core.compile_cache import enable_persistent_cache
from repro.launch.mesh import make_abm_mesh

SIMS = ["cell_clustering", "cell_proliferation", "epidemiology",
        "oncology", "sir_mechanics", "tumor_spheroid"]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sim", required=True, choices=SIMS)
    ap.add_argument("--agents", type=int, default=400)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--mesh", default="1x1",
                    help="spatial device mesh, e.g. 2x2 (2-D) or 1x1x2 "
                         "(3-D); the axis count sets the Domain's ndim")
    ap.add_argument("--delta", default="off",
                    choices=["off", "int8", "int16"])
    ap.add_argument("--interior", type=int, default=16,
                    help="global NSG cells per axis")
    ap.add_argument("--rebalance", type=int, default=0, metavar="N",
                    help="check occupancy imbalance every N iterations "
                         "and re-shard past --imbalance")
    ap.add_argument("--imbalance", type=float, default=0.5,
                    help="re-shard threshold for --rebalance")
    ap.add_argument("--weighted", action="store_true",
                    help="weight the rebalance histogram by measured "
                         "per-device step times")
    ap.add_argument("--ownership", default="equal",
                    choices=["equal", "rcb"],
                    help="what a triggered re-shard may realize: equal-"
                         "split meshes, or box-granular uneven RCB "
                         "partitions on padded per-device grids "
                         "(docs/load_balancing.md)")
    ap.add_argument("--sweep-backend", default="auto",
                    choices=["auto", "reference", "tiled", "pallas"],
                    help="neighbor-interaction sweep implementation "
                         "(docs/performance.md); auto = tiled on CPU/GPU, "
                         "pallas on TPU")
    args = ap.parse_args()
    enable_persistent_cache()

    import importlib

    mod = importlib.import_module(f"repro.sims.{args.sim}")
    # a sim declares its dimensionality via a module-level NDIM (3-D sims
    # only; 2-D is the default); an all-ones --mesh broadcasts to it so
    # the single-device default works for any sim, and a real mesh must
    # match the sim's axis count
    sim_ndim = getattr(mod, "NDIM", 2)
    mesh_shape = tuple(int(v) for v in args.mesh.split("x"))
    if len(mesh_shape) != sim_ndim:
        if all(m == 1 for m in mesh_shape):
            mesh_shape = (1,) * sim_ndim
        else:
            ap.error(f"--mesh {args.mesh} has {len(mesh_shape)} axes but "
                     f"{args.sim} is {sim_ndim}-D")
    n_dev = 1
    for m in mesh_shape:
        n_dev *= m
    mesh = None
    if n_dev > 1:
        assert len(jax.devices()) >= n_dev, (
            f"need {n_dev} devices (set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev})")
        mesh = make_abm_mesh(mesh_shape)
    delta = None
    if args.delta != "off":
        delta = DeltaConfig(enabled=True, qdtype=jnp.dtype(args.delta),
                            refresh_interval=16)
    rebalance = None
    if args.rebalance > 0:
        rebalance = Rebalance(every=args.rebalance,
                              threshold=args.imbalance,
                              weighted=args.weighted,
                              ownership=args.ownership)
    elif args.ownership != "equal":
        ap.error("--ownership rcb needs --rebalance N (the re-shard "
                 "runtime is what realizes uneven partitions)")

    interior = tuple(args.interior // m for m in mesh_shape)
    t0 = time.time()
    state, metrics = mod.run(
        n_agents=args.agents, steps=args.steps, mesh=mesh,
        mesh_shape=mesh_shape, interior=interior, delta=delta,
        rebalance=rebalance, sweep_backend=args.sweep_backend)
    dt = time.time() - t0
    n = total_agents(state)
    print(f"sim={args.sim} devices={n_dev} agents={n} steps={args.steps} "
          f"wall={dt:.2f}s ({n*args.steps/dt:.0f} agent_updates/s)")
    print(f"aura bytes/iter={int(state.halo_bytes.ravel()[0])} "
          f"dropped={int(state.dropped.sum())}")
    for k, v in metrics.items():
        if not hasattr(v, "__len__") or len(str(v)) < 120:
            print(f"  {k}: {v}")


if __name__ == "__main__":
    main()
