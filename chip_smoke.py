#!/usr/bin/env python3
"""Chip smoke test: run the engine's main path on a TPU and check what
comes out.

    python chip_smoke.py                # one chip: phases a, b, c
    python chip_smoke.py --four-chips   # one process driving four chips:
                                        # the 2x2 sharded path only

One chip:

a. Deployment: ``cell_clustering`` (paper §3.1) at 512x512 interior cells,
   cell size 2.0, cap 24, diameter 1.0, interaction radius 2.0 and 6.25
   agents per cell (1,638,400 agents), built with ``make_sim`` and driven
   by ``Simulation.run``.  Warm up, then run 20 steps.  Checks: no agent
   dropped, the population exactly conserved, every position finite, the
   guards' health word zero, and the same-type neighbour fraction rising.
b. Reference: at 128x128 cells the ``reference`` sweep runs beside the
   backend ``auto`` picks, for 5 steps from one initial state.
c. Serve: the batching scenario server's smoke path
   (``python -m repro.launch.serve --smoke``).

``--four-chips``, in this order: the same global grid split 2x2 with
``delta="off"``; the default multi-device configuration (int8 delta,
``overlap="auto"``) on a left-heavy initial density with a
device-to-device re-shard; a single-chip run of the first on chip 0.  The
2x2 run must match the single-chip run, and each chip's peak memory after
either sharded phase must be at most half the single-chip run's.

Positions are compared agent by agent, matched by global id (the four-chip
runs are given the same ids).  Runs that add pair forces in different
orders agree to float32 rounding, so the bulk (99.9th percentile of
per-agent |dpos|) must agree within ``Q_TOL`` and every agent within
``MAX_TOL``, about 60 times the largest difference seen on the chip.

The steps use dt = 0.005.  At ``make_sim``'s dt of 0.1 this model's
adhesion condenses clusters past 24 agents per cell within 3-4 steps, and
the engine then drops agents (cap overflow); 20 steps at dt 0.005 stay
below the cap.

The last line of standard output is one JSON object, ``{"ok": true,
"device": {...}}``, printed only when every phase passed on a TPU.  With no
TPU, or without the repository's ``src/`` next to this file, the script
exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

G = 512               # interior cells per axis: phase a and --four-chips
G_REF = 128           # phase b
DENSITY = 6.25        # agents per cell (the sim's default 400 on 8x8)
CAP = 24
DT = 0.005
WARMUP, STEPS = 2, 20
REF_STEPS = 5
SHARD_STEPS = 5
REBALANCE_STEPS = 5
Q_TOL = 1e-4          # 99.9th percentile of per-agent |dpos|
MAX_TOL = 1e-3        # every agent


class SmokeError(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeError(what)


def gib(nbytes: float) -> str:
    return f"{nbytes / 2**30:.3f} GiB"


def peak_bytes(devices):
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


# ---------------------------------------------------------------------------
# agent bookkeeping (host side)
# ---------------------------------------------------------------------------

def agents_by_id(state, ndim: int = 2):
    """(ids, positions) of every live agent, sorted by global id."""
    import numpy as np

    v = np.asarray(state.soa.valid).ravel()
    rank = np.asarray(state.soa.attrs["gid_rank"]).ravel()[v]
    count = np.asarray(state.soa.attrs["gid_count"]).ravel()[v]
    ids = (rank.astype(np.int64) << 32) | count.astype(np.int64)
    pos = np.asarray(state.soa.attrs["pos"]).reshape(-1, ndim)[v]
    order = np.argsort(ids)
    return ids[order], pos[order]


def compare(tag: str, pa, pb) -> None:
    import numpy as np

    d = np.abs(pa - pb).max(axis=1)
    q = float(np.quantile(d, 0.999))
    m = float(d.max())
    log(f"[{tag}] per-agent |dpos| over {len(d)} agents: max {m!r}, "
        f"99.9th pct {q!r}, {int((d > Q_TOL).sum())} above {Q_TOL:g} "
        f"(limits: 99.9th pct <= {Q_TOL:g}, max <= {MAX_TOL:g})")
    require(q <= Q_TOL and m <= MAX_TOL,
            f"{tag}: positions disagree beyond the stated tolerance")


def check_run(tag: str, sim, n: int) -> None:
    """Zero drops, exact population, finite positions, zero health."""
    import numpy as np

    st = sim.state
    dropped = int(np.asarray(st.dropped).sum())
    live = int(np.asarray(st.soa.valid).sum())
    v = np.asarray(st.soa.valid)
    finite = bool(np.isfinite(np.asarray(st.soa.attrs["pos"])[v]).all())
    health = int(np.asarray(st.health).sum())
    log(f"[{tag}] dropped {dropped}, agents {live}/{n}, positions finite "
        f"{finite}, health word sum {health}")
    require(dropped == 0, f"{tag}: {dropped} agents dropped")
    require(live == n, f"{tag}: population {live} != {n}")
    require(finite, f"{tag}: non-finite positions")
    require(health == 0, f"{tag}: guard health word {health}")


def cluster_sim(**kw):
    from repro.sims import cell_clustering as cc
    from repro.sims.common import make_sim

    return make_sim(cc.behavior(), cap=CAP, dt=DT, guards="error", **kw)


def clustering_agents(seed: int, n: int, geom):
    """The initial population ``cell_clustering.init`` draws."""
    import numpy as np
    from repro.sims.common import uniform_positions

    rng = np.random.default_rng(seed)
    pos = uniform_positions(rng, n, geom)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return pos, attrs


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_deployment() -> None:
    import jax
    import numpy as np
    from repro.core.neighbors import resolve_sweep_backend
    from repro.sims import cell_clustering as cc

    n = int(DENSITY * G * G)
    t = time.perf_counter()
    sim = cluster_sim(interior=(G, G))
    cc.init(sim, n, seed=0)
    jax.block_until_ready(sim.state.soa.valid)
    log(f"[a] cell_clustering {G}x{G} cells, cap {CAP}, {n} agents, "
        f"dt {DT}: init {time.perf_counter() - t:.2f} s")

    backend = resolve_sweep_backend(sim.engine.sweep_backend)
    program = sim.engine.make_segment_runner(None).programs[True]
    lowered = program.lower(sim.state, np.int32(1))
    custom = "tpu_custom_call" in lowered.as_text()
    log(f"[a] sweep backend auto chose: {backend}; tpu_custom_call in the "
        f"step's HLO: {custom}")
    if backend == "pallas":
        require(custom, "[a] pallas chosen but the step holds no kernel")
    t = time.perf_counter()
    compiled = lowered.compile()
    log(f"[a] segment program compile {time.perf_counter() - t:.2f} s; "
        f"temporaries {gib(compiled.memory_analysis().temp_size_in_bytes)}")

    f0 = cc.same_type_fraction(sim.state, sim.engine)
    t = time.perf_counter()
    sim.run(WARMUP)
    jax.block_until_ready(sim.state.soa.valid)
    log(f"[a] warm-up {WARMUP} steps {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    sim.run(STEPS)
    jax.block_until_ready(sim.state.soa.valid)
    dt = time.perf_counter() - t
    f1 = cc.same_type_fraction(sim.state, sim.engine)
    log(f"[a] {STEPS} steps {dt:.3f} s: {STEPS / dt:.3f} steps/s, "
        f"{n * STEPS / dt:.4g} agent updates/s (guards on)")
    log(f"[a] same-type fraction {f0:.5f} -> {f1:.5f}")
    log(f"[a] peak_bytes_in_use per device: "
        f"{[gib(b) for b in peak_bytes(jax.devices())]}")
    check_run("a", sim, n)
    require(f1 > f0, "[a] same-type fraction did not rise")


def phase_reference() -> None:
    import numpy as np
    from repro.core.neighbors import resolve_sweep_backend

    n = int(DENSITY * G_REF * G_REF)
    out = {}
    for backend in ("reference", "auto"):
        sim = cluster_sim(interior=(G_REF, G_REF), sweep_backend=backend)
        sim.init(*clustering_agents(1, n, sim.geom), seed=1)
        sim.run(REF_STEPS)
        check_run(f"b {backend}", sim, n)
        out[backend] = agents_by_id(sim.state)
    auto = resolve_sweep_backend("auto")
    (ia, pa), (ib, pb) = out["reference"], out["auto"]
    require(len(ia) == len(ib) == n and bool((ia == ib).all()),
            "[b] populations differ")
    log(f"[b] {G_REF}x{G_REF} cells, {n} agents, {REF_STEPS} steps: "
        f"reference vs {auto} (auto): populations equal")
    compare(f"b reference vs {auto}", pa, pb)


def phase_serve() -> None:
    from repro.core.neighbors import resolve_sweep_backend
    from repro.launch import serve

    log(f"[c] scenario server smoke, sweep backend "
        f"{resolve_sweep_backend('auto')} under vmap")
    require(serve.main(["--smoke"]) == 0, "[c] serve smoke failed")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def left_heavy_agents(seed: int, geom):
    """7.5 agents per cell on the left half, 5 on the right (6.25 on
    average), each placed uniformly inside its cell: an occupancy
    imbalance of 0.2 with no Poisson tail over the cap."""
    import numpy as np

    rng = np.random.default_rng(seed)
    gx, gy = geom.global_cells
    counts = np.full((gx, gy), 5, np.int64)
    counts[: gx // 2] = 7 + (np.arange(gy)[None, :] % 2)
    cells = np.repeat(np.arange(gx * gy), counts.ravel())
    cxy = np.stack(np.unravel_index(cells, (gx, gy)), axis=1)
    pos = ((cxy + rng.uniform(0.05, 0.95, cxy.shape)) * geom.cell_size
           ).astype(np.float32)
    n = len(pos)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return pos, attrs


def state_bytes(state) -> int:
    import jax

    return sum(x.nbytes for x in jax.tree_util.tree_leaves(state))


def phase_four_chips() -> None:
    import jax
    import numpy as np
    from repro.core import Rebalance
    from repro.core.neighbors import resolve_sweep_backend

    devs = jax.devices()
    require(len(devs) >= 4, f"--four-chips needs 4 chips, found {len(devs)}")
    devs = devs[:4]
    n = int(DENSITY * G * G)
    half = (G // 2, G // 2)
    log(f"[4] sweep backend auto chose: {resolve_sweep_backend('auto')}")

    # 1. the 2x2 split of the deployment grid, raw f32 aura slabs; both
    # this run and the single-chip one get the same explicit global ids
    t = time.perf_counter()
    sim4 = cluster_sim(interior=half, mesh_shape=(2, 2), delta="off")
    pos, attrs = clustering_agents(0, n, sim4.geom)
    attrs.update(gid_rank=np.zeros(n, np.int32),
                 gid_count=np.arange(n, dtype=np.int32))
    sim4.init(pos, attrs, seed=0)
    whole = state_bytes(sim4.state)
    per_dev = {d.id: 0 for d in devs}
    for leaf in jax.tree_util.tree_leaves(sim4.state):
        for s in leaf.addressable_shards:
            per_dev[s.device.id] += s.data.nbytes
    log(f"[4] 2x2 init {time.perf_counter() - t:.2f} s; state bytes per "
        f"device {per_dev} of {whole} in all")
    require(max(per_dev.values()) * 2 <= whole,
            "[4] one chip holds more than half the state")
    t = time.perf_counter()
    sim4.run(SHARD_STEPS)
    jax.block_until_ready(sim4.state.soa.valid)
    log(f"[4] 2x2 {SHARD_STEPS} steps (compile included) "
        f"{time.perf_counter() - t:.2f} s")
    check_run("4 2x2", sim4, n)
    ids4, p4 = agents_by_id(sim4.state)
    del sim4
    split_peaks = peak_bytes(devs)
    log(f"[4] peak_bytes_in_use per device after the 2x2 run "
        f"{[gib(b) for b in split_peaks]}")

    # 2. default multi-device configuration with a device re-shard
    sim_rb = cluster_sim(
        interior=half, mesh_shape=(2, 2),
        rebalance=Rebalance(every=4, threshold=0.1, ownership="rcb",
                            transport="device"))
    pos_rb, attrs_rb = left_heavy_agents(2, sim_rb.geom)
    sim_rb.init(pos_rb, attrs_rb, seed=2)
    log(f"[4] rebalance run: delta {sim_rb.engine.delta_cfg.qdtype.__name__}"
        f" (enabled {sim_rb.engine.delta_cfg.enabled}), overlap "
        f"{sim_rb.engine.overlap}, {len(pos_rb)} agents, left-heavy, "
        f"state {state_bytes(sim_rb.state)} bytes in all")
    t = time.perf_counter()
    sim_rb.run(REBALANCE_STEPS)
    jax.block_until_ready(sim_rb.state.soa.valid)
    applied = [r for r in sim_rb.rebalancer.history if r["applied"]]
    for r in applied:
        log(f"[4] re-shard at it {r['it']}: {r['mesh_from']} -> "
            f"{r['mesh_to']}, transport {r['transport']}, imbalance "
            f"{r['imbalance_before']:.4f} -> {r['imbalance_after']:.4f}, "
            f"widths {r.get('partition_widths')}, "
            f"{r['migration_s']:.2f} s (compile included)")
    log(f"[4] rebalance run {REBALANCE_STEPS} steps "
        f"{time.perf_counter() - t:.2f} s")
    require(any(r["transport"] == "device" for r in applied),
            "[4] no device-to-device re-shard applied")
    check_run("4 rebalance", sim_rb, len(pos_rb))
    del sim_rb
    rb_peaks = peak_bytes(devs)
    log(f"[4] peak_bytes_in_use per device after the re-shard run "
        f"{[gib(b) for b in rb_peaks]}; chip 0 memory_stats "
        f"{devs[0].memory_stats()}")

    # 3. the single-chip run on chip 0 (whose high-water mark then covers
    # its share of the sharded runs too)
    sim1 = cluster_sim(interior=(G, G))
    sim1.init(pos, attrs, seed=0)
    t = time.perf_counter()
    sim1.run(SHARD_STEPS)
    jax.block_until_ready(sim1.state.soa.valid)
    log(f"[4] 1-chip {SHARD_STEPS} steps (compile included) "
        f"{time.perf_counter() - t:.2f} s")
    check_run("4 1-chip", sim1, n)
    ids1, p1 = agents_by_id(sim1.state)
    del sim1
    require(len(ids4) == len(ids1) == n and bool((ids4 == ids1).all()),
            "[4] populations differ")
    compare("4 2x2 vs 1-chip", p4, p1)
    single = peak_bytes(devs[:1])[0]
    log(f"[4] peak_bytes_in_use of the 1-chip run {gib(single)}; per "
        f"device after the 2x2 run {[gib(b) for b in split_peaks]}, "
        f"after the re-shard run {[gib(b) for b in rb_peaks]}")
    require(max(split_peaks + rb_peaks) * 2 <= single,
            "[4] a chip's sharded peak is not well under the 1-chip run's")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2 sharded path and its oracle")
    args = ap.parse_args(argv)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print(f"chip_smoke: no src/repro next to {__file__}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, src)

    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: no TPU; JAX's first device is "
              f"{devs[0].platform} ({devs[0].device_kind})", file=sys.stderr)
        return 1
    from repro.core.compile_cache import enable_persistent_cache

    log(f"devices: {len(devs)} x {devs[0].device_kind}; compile cache "
        f"{enable_persistent_cache()}")
    try:
        if args.four_chips:
            phase_four_chips()
        else:
            phase_deployment()
            phase_reference()
            phase_serve()
    except SmokeError as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
