"""Benchmark harness — one benchmark per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:

  serialization_*   — paper Fig. 10: TeraAgent IO (zero-copy SoA slab) vs a
                      generic pack/unpack serializer baseline
  delta_*           — paper Fig. 11: delta encoding message-size reduction +
                      distribution-op overhead per benchmark simulation
  sweep_*           — interaction-sweep micro-bench: the three backends
                      (reference | tiled | pallas) on one workload, pair
                      evaluations/s and speedup vs the reference gather
                      (docs/performance.md explains how to read these);
                      sweep_3d_* repeats it on a 3-D Domain (27-offset
                      stencil, incl. the pallas row — the kernel factory
                      takes 3-D blocks)
  halo_bytes_3d     — 3-D aura-exchange wire bytes/iter (6 directed edges),
                      full f32 vs int16 delta
  halo_bytes_per_iter_* / reshard_downtime_steps
                    — communication budget (ROADMAP item 1,
                      docs/performance.md): per-sim steady-state aura wire
                      bytes int8-compressed (R=16) vs raw, re-shard
                      downtime in steps host-path vs device-to-device
  sim_*             — paper Fig. 6 analogue: per-simulation iteration rate
                      (agent_updates/s, the Biocellion comparison metric
                      §3.8); sim_tumor_spheroid_3d tracks the 3-D flagship
  scaling_*         — paper Fig. 8/9 analogue: strong scaling over placeholder
                      spatial meshes at FIXED global problem size
                      (subprocess: needs >1 XLA host device); derived reports
                      agent_updates/s, parallel efficiency vs 1 device, and
                      halo bytes/iter
  rebalance_uneven_* — §2.4.5 uneven ownership: per clustered workload the
                      imbalance before / after-equal / after-rcb (the
                      realized box-granular partition) vs the rcb_bound,
                      plus the padded-grid memory overhead

CPU wall-clock here characterizes the harness, not TPU performance; no row
is a chip measurement (``chip_smoke.py`` is what runs the engine on a TPU).
The subprocess phases run on placeholder CPU devices on every platform: a
child never asks for the chip the parent holds.

``--only PREFIX[,PREFIX...]`` runs a subset (e.g. ``--only sweep`` for the
CI sweep smoke step).
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

ROWS = []


def emit(name: str, us: float, derived: str = ""):
    ROWS.append((name, us, derived))
    print(f"{name},{us:.1f},{derived}")


def merge_rows(path, rows):
    """Merge this run's rows into the checked-in results keyed by row
    name: a partial run (``--only``) updates its rows and leaves the rest
    of the perf trajectory in place instead of truncating the file."""
    merged = {}
    if path.exists():
        try:
            for row in json.loads(path.read_text()):
                merged[row["name"]] = row
        except (ValueError, KeyError, TypeError):
            pass  # unreadable history: rebuild from this run
    for n, us, d in rows:
        merged[n] = {"name": n, "us_per_call": us, "derived": d}
    return list(merged.values())


def timeit(fn, n=5, warmup=2):
    for _ in range(warmup):
        fn()
    t0 = time.perf_counter()
    for _ in range(n):
        fn()
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------
# Fig 10 analogue: serialization
# ---------------------------------------------------------------------------

def bench_serialization():
    """TeraAgent IO == the SoA slab itself (serialization is the identity);
    baseline == generic per-leaf pack/unpack into a byte buffer (the
    ROOT-IO-style copy pipeline)."""
    from repro.core import AgentSchema
    from repro.core.agent_soa import AgentSoA
    from repro.core.halo import take_slab

    schema = AgentSchema.create({
        "diameter": ((), jnp.float32), "ctype": ((), jnp.int32)})
    soa = AgentSoA.empty(schema, (66, 66), 16)
    soa = soa.replace(valid=soa.valid.at[:, :, :8].set(True))

    def ta_io():
        # zero-copy: the exchange slab IS the wire format
        slab = take_slab(soa, 0, 1)
        return jax.block_until_ready(slab["pos"])

    def generic_pack_unpack():
        slab = take_slab(soa, 0, 1)
        bufs = [np.asarray(v).tobytes() for v in slab.values()]  # pack
        wire = b"".join(bufs)
        out = []
        off = 0                                                   # unpack
        for k, v in slab.items():
            n = np.asarray(v).nbytes
            arr = np.frombuffer(wire[off:off + n],
                                dtype=np.asarray(v).dtype.str)
            out.append(jnp.asarray(arr.reshape(np.asarray(v).shape)))
            off += n
        return jax.block_until_ready(out[0])

    t_ta = timeit(ta_io, n=20)
    t_gen = timeit(generic_pack_unpack, n=20)
    emit("serialization_ta_io", t_ta, f"speedup_vs_generic={t_gen/t_ta:.1f}x")
    emit("serialization_generic", t_gen, "baseline")


# ---------------------------------------------------------------------------
# Fig 11 analogue: delta encoding
# ---------------------------------------------------------------------------

def bench_delta():
    from repro.core import DeltaConfig
    from repro.sims import cell_clustering

    for qd, label in ((jnp.int8, "int8"), (jnp.int16, "int16")):
        delta = DeltaConfig(enabled=True, qdtype=qd, refresh_interval=16)
        # plain
        t0 = time.perf_counter()
        s_plain, _ = cell_clustering.run(n_agents=300, steps=8)
        t_plain = time.perf_counter() - t0
        b_plain = int(s_plain.halo_bytes[0, 0])
        t0 = time.perf_counter()
        s_delta, _ = cell_clustering.run(n_agents=300, steps=8, delta=delta)
        t_delta = time.perf_counter() - t0
        b_delta = int(s_delta.halo_bytes[0, 0])
        emit(f"delta_{label}_msg_bytes", t_delta / 8 * 1e6,
             f"reduction={b_plain/max(b_delta,1):.2f}x "
             f"({b_plain}->{b_delta}B/iter)")
    # steady-state analytic reduction for float-only payloads
    r = 16
    emit("delta_int8_float_payload", 0.0,
         f"steady_state_reduction={4*r/(4+(r-1)*1):.2f}x_at_R={r}")


# ---------------------------------------------------------------------------
# Interaction-sweep micro-bench: the hot kernel, isolated per backend
# ---------------------------------------------------------------------------

def bench_sweep():
    """Time one jitted neighborhood sweep per backend on a shared workload.

    ``pairs/s`` counts candidate pair evaluations (interior agents x 9K
    neighborhood slots) — the sweep's actual arithmetic work.  The Pallas
    row runs in interpret mode on CPU (that row measures the interpreter,
    not Mosaic; it exists to keep the TPU path's parity + plumbing hot).
    """
    from repro.core import Engine, Domain
    from repro.core.neighbors import sweep_accumulate
    from repro.sims import cell_clustering

    beh = cell_clustering.behavior()
    geom = Domain(cell_size=2.0, interior=(16, 16), mesh_shape=(1, 1),
                    cap=24)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(0)
    n = 2000
    lx, ly = geom.domain_size
    pos = rng.uniform(0.5, lx - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    state = eng.init_state(pos, attrs, seed=0)
    ix, iy = geom.interior
    # the sweep's masked arithmetic runs over every interior agent SLOT
    # (valid or not) x its 9K neighborhood candidates
    pairs = ix * iy * geom.cap * 9 * geom.cap

    times = {}
    for backend in ("reference", "tiled", "pallas"):
        fn = jax.jit(lambda soa, b=backend: sweep_accumulate(
            geom, soa, beh.pair_fn, beh.pair_attrs, beh.radius, beh.params,
            backend=b))
        out = fn(state.soa)                      # compile
        jax.block_until_ready(out)
        reps = 2 if backend == "pallas" else 10
        t = timeit(lambda: jax.block_until_ready(fn(state.soa)),
                   n=reps, warmup=1)
        times[backend] = t
        extra = "_interpret" if backend == "pallas" else ""
        emit(f"sweep_{backend}", t,
             f"pairs_per_s={pairs / (t / 1e6):.3g}"
             f"_speedup_vs_reference={times['reference'] / t:.2f}x{extra}")


# ---------------------------------------------------------------------------
# 3-D sweep micro-bench: the same hot kernel on the new spatial axis
# ---------------------------------------------------------------------------

def bench_sweep_3d():
    """reference | tiled | pallas on a 3-D Domain (27-offset stencil).
    The kernel factory takes 3-D blocks since the uneven-ownership PR;
    as in :func:`bench_sweep`, the pallas row runs the interpreter on CPU
    (it tracks parity/plumbing, not Mosaic performance)."""
    from repro.core import Domain, Engine
    from repro.core.neighbors import sweep_accumulate
    from repro.sims import cell_clustering

    beh = cell_clustering.behavior()
    geom = Domain(cell_size=2.0, interior=(8, 8, 8), mesh_shape=(1, 1, 1),
                  cap=16)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(0)
    n = 2000
    size = geom.domain_size
    pos = rng.uniform([0.5] * 3, [s - 0.5 for s in size],
                      (n, 3)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    state = eng.init_state(pos, attrs, seed=0)
    cells = geom.interior[0] * geom.interior[1] * geom.interior[2]
    pairs = cells * geom.cap * 27 * geom.cap

    times = {}
    for backend in ("reference", "tiled", "pallas"):
        fn = jax.jit(lambda soa, b=backend: sweep_accumulate(
            geom, soa, beh.pair_fn, beh.pair_attrs, beh.radius, beh.params,
            backend=b))
        jax.block_until_ready(fn(state.soa))     # compile
        reps = 2 if backend == "pallas" else 5
        t = timeit(lambda: jax.block_until_ready(fn(state.soa)),
                   n=reps, warmup=1)
        times[backend] = t
        extra = "_interpret" if backend == "pallas" else ""
        emit(f"sweep_3d_{backend}", t,
             f"pairs_per_s={pairs / (t / 1e6):.3g}"
             f"_speedup_vs_reference={times['reference'] / t:.2f}x{extra}")


# ---------------------------------------------------------------------------
# 3-D aura-exchange wire bytes: 6 directed edges, full vs delta
# ---------------------------------------------------------------------------

def bench_halo_bytes_3d():
    """Wire bytes per iteration of the 3-D aura exchange (2*ndim = 6
    directed face slabs), full f32 vs int16 quantized-delta — the 3-D
    continuation of the ``delta_*`` rows."""
    from repro.core import DeltaConfig
    from repro.sims import tumor_spheroid

    _ = tumor_spheroid.run(n_agents=40, steps=2)   # warm compile
    t0 = time.perf_counter()
    s_plain, _ = tumor_spheroid.run(n_agents=40, steps=4)
    t_plain = time.perf_counter() - t0
    b_plain = int(s_plain.halo_bytes.ravel()[0])
    delta = DeltaConfig(enabled=True, qdtype=jnp.int16, refresh_interval=16)
    s_delta, _ = tumor_spheroid.run(n_agents=40, steps=4, delta=delta)
    b_delta = int(s_delta.halo_bytes.ravel()[0])
    emit("halo_bytes_3d", t_plain / 4 * 1e6,
         f"reduction={b_plain/max(b_delta,1):.2f}x "
         f"({b_plain}->{b_delta}B/iter_6_edges)")


# ---------------------------------------------------------------------------
# Fig 6 / §3.8 analogue: per-sim iteration rate
# ---------------------------------------------------------------------------

def bench_sims():
    from repro.sims import (cell_clustering, cell_proliferation,
                            epidemiology, oncology)

    for name, mod, kw in (
        ("cell_clustering", cell_clustering, dict(n_agents=400, steps=4)),
        ("cell_proliferation", cell_proliferation,
         dict(n_agents=60, steps=4)),
        ("epidemiology", epidemiology, dict(n_agents=500, steps=4)),
        ("oncology", oncology, dict(n_agents=30, steps=4)),
    ):
        _ = mod.run(**{**kw, "steps": 2})  # warm compile
        t0 = time.perf_counter()
        state, _ = mod.run(**kw)
        dt_iter = (time.perf_counter() - t0) / kw["steps"]
        from repro.core.engine import total_agents

        n = total_agents(state)
        emit(f"sim_{name}", dt_iter * 1e6,
             f"agent_updates_per_s={n/dt_iter:.0f}")


def bench_sim_tumor_spheroid():
    """3-D flagship workload (sims/tumor_spheroid): iteration rate of the
    composed mechanics + nutrient-gated-growth stack on a 3-D Domain."""
    from repro.core.engine import total_agents
    from repro.sims import tumor_spheroid

    kw = dict(n_agents=40, steps=4)
    _ = tumor_spheroid.run(**{**kw, "steps": 2})   # warm compile
    t0 = time.perf_counter()
    state, _ = tumor_spheroid.run(**kw)
    dt_iter = (time.perf_counter() - t0) / kw["steps"]
    n = total_agents(state)
    emit("sim_tumor_spheroid_3d", dt_iter * 1e6,
         f"agent_updates_per_s={n/dt_iter:.0f}_ndim=3")


# ---------------------------------------------------------------------------
# Fig 8/9 analogue: strong scaling over spatial meshes (subprocess)
# ---------------------------------------------------------------------------

def bench_scaling():
    """Strong scaling at FIXED global problem size (800 agents on a fixed
    16x16 global cell grid): the step loop itself is timed (init and metric
    setup excluded), normalized to agent_updates/s, with parallel
    efficiency vs the 1-device run and the aura-exchange wire bytes per
    iteration — the quantities a mesh-shape comparison is actually about."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import time, numpy as np, jax
from repro.sims import cell_clustering

n, steps = 800, 12
base_rate = None
for mesh_shape in ((1, 1), (2, 1), (2, 2)):
    n_dev = mesh_shape[0] * mesh_shape[1]
    from repro.launch.mesh import make_abm_mesh
    mesh = make_abm_mesh(mesh_shape) if n_dev > 1 else None
    interior = (16 // mesh_shape[0], 16 // mesh_shape[1])
    sim = cell_clustering.simulation(n_agents=n, interior=interior,
                                     mesh_shape=mesh_shape, mesh=mesh)
    sim.run(2)                                    # warm compile
    jax.block_until_ready(sim.state.soa.valid)
    t0 = time.perf_counter()
    sim.run(steps)
    jax.block_until_ready(sim.state.soa.valid)
    dt = (time.perf_counter() - t0) / steps
    rate = n / dt
    base_rate = base_rate or rate
    eff = rate / (base_rate * n_dev)
    hb = int(np.asarray(sim.state.halo_bytes).sum())
    print(f"scaling_devices_{n_dev},{dt*1e6:.1f},"
          f"agent_updates_per_s={rate:.0f}_efficiency={eff:.2f}"
          f"_halo_bytes_iter={hb}")
"""
    run_sub_bench(code, "scaling_")


def run_sub_bench(code: str, prefix: str) -> None:
    """Run a benchmark snippet in a subprocess (placeholder devices need a
    fresh XLA) and collect its ``prefix``-named CSV rows."""
    env = dict(os.environ)
    env["PYTHONPATH"] = str(ROOT / "src")
    # placeholder CPU devices on every platform: the parent holds the chip
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=1800, env=env)
    if p.returncode != 0:
        raise RuntimeError(f"{prefix} phase failed:\n{p.stderr[-2000:]}")
    for line in p.stdout.strip().splitlines():
        if line.startswith(prefix):
            print(line)
            name, us, derived = line.split(",", 2)
            ROWS.append((name, float(us), derived))


# ---------------------------------------------------------------------------
# §2.4.5 analogue: dynamic load balancing (re-shard runtime)
# ---------------------------------------------------------------------------

def bench_rebalance():
    """Gaussian-clustered density on a 2x2 mesh: report imbalance() and
    iteration rate before/after the Rebalancer's one-time mass migration
    (subprocess: needs 4 XLA host devices)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import time, numpy as np, jax, jax.numpy as jnp
from repro.core import AgentSchema, Behavior, Engine, Domain, Rebalancer, total_agents
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.core.reshard import current_imbalance
from repro.launch.mesh import make_abm_mesh

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.6,
                                   "same_type_only": 1.0, "max_step": 0.5})
rng = np.random.default_rng(0)
n = 600
c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, n).astype(np.int32)}

geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=48)
eng = Engine(geom=geom, behavior=beh, dt=0.1)
state = eng.init_state(pos, attrs, seed=0)
imb0 = current_imbalance(eng.geom, state)

def rate(engine, st, steps=6):
    step = engine.make_sharded_step(make_abm_mesh(engine.geom.mesh_shape))
    st = step(st, full_halo=True)  # warm compile
    t0 = time.perf_counter()
    for _ in range(steps):
        st = step(st, full_halo=True)
    jax.block_until_ready(st.soa.valid)
    dt = (time.perf_counter() - t0) / steps
    return dt, st

dt0, _ = rate(eng, state)
rb = Rebalancer(every=1, threshold=0.2)
t0 = time.perf_counter()
eng2, state2, did = rb.maybe_reshard(eng, state)
t_mig = time.perf_counter() - t0
assert did, rb.history
imb1 = current_imbalance(eng2.geom, state2)
assert total_agents(state2) == n
dt1, _ = rate(eng2, state2)
rec = rb.history[-1]
print(f"rebalance_imbalance,{t_mig*1e6:.1f},"
      f"imb={imb0:.2f}->{imb1:.2f}_mesh={rec['mesh_from']}->{rec['mesh_to']}"
      f"_rcb_bound={rec['rcb_bound']:.2f}".replace(" ", ""))
print(f"rebalance_iter_rate,{dt1*1e6:.1f},"
      f"agent_updates_per_s={n/dt1:.0f}_vs_{n/dt0:.0f}_static")
"""
    run_sub_bench(code, "rebalance_")


def bench_rebalance_uneven():
    """Uneven ownership on the clustered workloads: per workload the
    imbalance before / after the equal-split plan / after the realized
    box-granular RCB partition, plus the reported ``rcb_bound`` — the rows
    that show the former plan-vs-realizable gap is closed (subprocess:
    needs 4 XLA host devices)."""
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import time, numpy as np, jax
from repro.core import total_agents
from repro.core.reshard import (current_imbalance, occupancy_histogram,
                                plan_reshard, reshard_state)

def report(name, eng, state, n):
    hist = occupancy_histogram(eng.geom, state)
    imb0 = current_imbalance(eng.geom, state)
    plan = plan_reshard(hist, eng.geom)
    eng_eq, st_eq = reshard_state(eng, state, plan.mesh_shape)
    imb_eq = current_imbalance(eng_eq.geom, st_eq)
    assert total_agents(st_eq) == n
    t0 = time.perf_counter()
    eng_un, st_un = reshard_state(eng, state, partition=plan.partition)
    t_mig = time.perf_counter() - t0
    imb_un = current_imbalance(eng_un.geom, st_un)
    assert total_agents(st_un) == n
    rcb = plan.rcb_bound
    within = imb_un <= rcb * 1.1 + 1e-9
    print(f"rebalance_uneven_{name},{t_mig*1e6:.1f},"
          f"imb={imb0:.2f}_after_equal={imb_eq:.2f}_after_rcb={imb_un:.2f}"
          f"_rcb_bound={rcb:.2f}_within_10pct={within}"
          f"_mesh={eng_un.geom.mesh_shape}"
          f"_pad={eng_un.geom.partition.pad_fraction() if eng_un.geom.uneven else 0.0:.2f}"
          .replace(" ", ""))

# (a) cell_clustering: diagonal two-cluster Gaussian density on a 2x2 mesh
from repro.sims import cell_clustering
from repro.sims.common import init_agents, make_sim
rng = np.random.default_rng(0)
n = 600
c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, n).astype(np.int32)}
sim = make_sim(cell_clustering.behavior(adhesion=0.3),
               interior=(8, 8), mesh_shape=(2, 2), cap=64)
init_agents(sim, pos, attrs, seed=0)
sim.run(2)
report("cell_clustering", sim.engine, sim.state, n)

# (b) tumor_spheroid: off-center 3-D ball on a 2x2x1 mesh
from repro.sims import tumor_spheroid
sim3 = tumor_spheroid.simulation(
    n_agents=60, mesh_shape=(2, 2, 1), interior=(6, 6, 12), cap=64,
    center_frac=(0.3, 0.3, 0.3))
sim3.run(2)
report("tumor_spheroid", sim3.engine, sim3.state, sim3.n_agents())
"""
    run_sub_bench(code, "rebalance_uneven_")


# ---------------------------------------------------------------------------
# ROADMAP item 1: the communication budget (docs/performance.md)
# ---------------------------------------------------------------------------

def bench_comm_budget():
    """Communication-budget rows: per-sim steady-state aura wire bytes
    compressed (int8 delta, R=16) vs raw f32, and re-shard downtime in
    steps for the host path vs the device-to-device collective."""
    from repro.core import DeltaConfig
    from repro.sims import (cell_clustering, cell_proliferation,
                            epidemiology, oncology)

    cfg = DeltaConfig(enabled=True, qdtype=jnp.int8, refresh_interval=16)
    for name, mod, kw in (
        ("cell_clustering", cell_clustering, dict(n_agents=300)),
        ("cell_proliferation", cell_proliferation, dict(n_agents=50)),
        ("epidemiology", epidemiology, dict(n_agents=400)),
        ("oncology", oncology, dict(n_agents=30)),
    ):
        sp, _ = mod.run(steps=8, **kw)
        raw = int(np.asarray(sp.halo_bytes).sum())
        sd, _ = mod.run(steps=8, delta=cfg, **kw)
        comp = int(np.asarray(sd.halo_bytes).sum())
        # Static per-slot byte split from the slab spec: int attrs and
        # the valid mask ride the codec unchanged, float attrs quantize
        # 4B -> 1B (+ one 4B scale per field per slab), so the whole-slab
        # reduction is diluted by the integer payload while the float
        # payload itself hits the codec's steady-state 4R/(4+(R-1)q).
        nd = int(np.asarray(sd.soa.attrs["pos"]).shape[-1])
        fB = iB = 0
        for _n, v in sd.soa.attrs.items():
            per = int(np.dtype(np.asarray(v).dtype).itemsize) * int(
                np.prod(np.asarray(v).shape[nd + 1:], dtype=int))
            if np.issubdtype(np.asarray(v).dtype, np.floating):
                fB += per
            else:
                iB += per
        tot = fB + iB + 1                      # + 1B valid mask
        raw_f = raw * fB / tot
        comp_f = comp - raw * (iB + 1) / tot   # ints pass through as-is
        amort = (raw + 15 * comp) / 16
        emit(f"halo_bytes_per_iter_{name}", float(comp),
             f"compressed={comp}B_raw={raw}B"
             f"_slab_reduction={raw / max(comp, 1):.2f}x"
             f"_float_payload_reduction={raw_f / max(comp_f, 1e-9):.2f}x"
             f"_amortized={raw / max(amort, 1e-9):.2f}x_at_R=16")

    # --- re-shard downtime: host vs device transport (subprocess) ------
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import time, numpy as np, jax, jax.numpy as jnp
from repro.core import (AgentSchema, Behavior, Domain, Engine, Rebalancer,
                        total_agents)
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.launch.mesh import make_abm_mesh

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.6,
                                   "same_type_only": 1.0, "max_step": 0.5})
rng = np.random.default_rng(0)
n = 600
c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, n).astype(np.int32)}
geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=48)
eng = Engine(geom=geom, behavior=beh, dt=0.1)
state = eng.init_state(pos, attrs, seed=0)
mesh = make_abm_mesh((2, 2))

step = eng.make_sharded_step(mesh)
st = step(state, full_halo=True)
t0 = time.perf_counter()
for _ in range(6):
    st = step(st, full_halo=True)
jax.block_until_ready(st.soa.valid)
dt = (time.perf_counter() - t0) / 6

mig = {}
for transport in ("host", "device"):
    # one warm pass populates the compiled-migration cache, the timed
    # pass (fresh Rebalancer, same state) reports steady re-shard cost
    for rnd in range(2):
        rb = Rebalancer(every=1, threshold=0.2, ownership="rcb",
                        transport=transport)
        e2, s2, did = rb.maybe_reshard(eng, state)
        assert did, rb.history
        rec = rb.history[-1]
        assert rec["transport"] == transport, rec
        assert total_agents(s2) == n
    mig[transport] = rec["migration_s"]
host_steps = mig["host"] / dt
dev_steps = mig["device"] / dt
print(f"reshard_downtime_steps,{mig['device']*1e6:.1f},"
      f"host={host_steps:.2f}_device={dev_steps:.2f}_steps"
      f"_at_step={dt*1e6:.0f}us"
      f"_migration_host={mig['host']*1e6:.0f}us_device={mig['device']*1e6:.0f}us")
"""
    run_sub_bench(code, "reshard_downtime")


# ---------------------------------------------------------------------------
# simcheck: construction-time audit cost, zero per-step cost
# ---------------------------------------------------------------------------

def bench_simcheck():
    """Cost of the static contract gate and the full validate() audit.
    Both run at construction / on demand only — the contract the row pins
    is that the *per-step* cost of a gated simulation is zero (the gate
    adds no tracing, no callbacks, nothing to the compiled step)."""
    import numpy as np

    from repro.analysis import check_engine
    from repro.core import Engine, Domain, Simulation
    from repro.sims import cell_clustering

    beh = cell_clustering.behavior()
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(1, 1),
                  cap=24)
    rng = np.random.default_rng(0)
    n = 400
    lx, ly = geom.domain_size
    pos = rng.uniform(0.5, lx - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}

    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    t_gate = timeit(lambda: check_engine(eng), n=20, warmup=2)

    sim = Simulation(dict(interior=(8, 8), cap=24), beh, dt=0.1)
    sim.init(pos, attrs, seed=0)
    t_validate = timeit(lambda: sim.validate(), n=3, warmup=1)

    steps = 30

    def per_step(check):
        e = Engine(geom=geom, behavior=beh, dt=0.1, check=check)
        s0 = e.init_state(pos, attrs, seed=0)
        step = e.make_local_step()

        def run():
            _, s, _ = e.drive(s0, steps, step_fn=step)
            jax.block_until_ready(s.soa.attrs["pos"])
        return timeit(run, n=3, warmup=1) / steps

    t_off = per_step("off")
    t_gated = per_step("error")

    emit("simcheck_contract_gate", t_gate, "construction_time_only")
    emit("simcheck_validate_ms", t_validate / 1e3,
         "full_audit=contracts+jaxpr+lint_on_demand_only")
    emit("simcheck_step_overhead", t_gated - t_off,
         f"per_step_cost_gated_vs_off={t_gated/t_off - 1:+.2%}_target_0")


def bench_resilience():
    """Cost of the resilience stack (docs/resilience.md): the fused guard
    set's per-step overhead (budget: <= 5%) and the replay debt of a
    checkpoint-rollback recovery at the bench's cadence."""
    import tempfile

    import numpy as np

    from repro.core import Engine, Domain
    from repro.core.guards import GuardConfig
    from repro.distributed.chaos import Fault, FaultPlan
    from repro.launch.supervise import Supervised, Supervisor
    from repro.sims import cell_clustering
    from repro.sims.common import make_sim

    beh = cell_clustering.behavior()
    geom = Domain(cell_size=2.0, interior=(16, 16), mesh_shape=(1, 1),
                  cap=24)
    rng = np.random.default_rng(0)
    n = 900
    lx, ly = geom.domain_size
    pos = rng.uniform(0.5, lx - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}

    steps = 30

    def per_step(guards):
        e = Engine(geom=geom, behavior=beh, dt=0.1,
                   guards=GuardConfig(policy=guards))
        s0 = e.init_state(pos, attrs, seed=0)
        step = e.make_local_step()

        def run():
            _, s, _ = e.drive(s0, steps, step_fn=step)
            jax.block_until_ready(s.soa.attrs["pos"])
        return timeit(run, n=3, warmup=1) / steps

    t_off = per_step("off")
    t_guarded = per_step("error")
    emit("guard_overhead_per_step", t_guarded - t_off,
         f"guarded_vs_off={t_guarded/t_off - 1:+.2%}_budget_5%")

    # recovery: NaN burst mid-chunk -> guard trip -> rollback -> replay
    every, fault_at, total = 10, 14, 30
    with tempfile.TemporaryDirectory() as ck:
        sim = make_sim(beh, interior=(16, 16), cap=24, dt=0.1,
                       guards="error")
        sim.init(pos, attrs, seed=0)
        plan = FaultPlan((Fault(step=fault_at, kind="nan_attrs",
                                frac=0.05),), seed=7)
        sv = Supervisor(sim, Supervised(dir=ck, every=every, keep=3),
                        fault_plan=plan)
        t0 = time.perf_counter()
        sv.run(total)
        wall = time.perf_counter() - t0
        rec = sv.events("recovered")[0]
    emit("recovery_time_steps", rec["replay_steps"],
         f"replay_debt_steps_at_every={every}_"
         f"supervised_{total}_steps_wall={wall:.2f}s")


def bench_ensemble():
    """Configs/s through the ensemble vs sequential solo runs over FRESH
    parameter points — the sweep/calibration workload the serving layer
    exists for (docs/serving.md; acceptance bar: >= 2x at R >= 8 on CPU).

    Every round of a sweep or an ABC fit proposes parameter points never
    run before.  Sequentially, each distinct point is a distinct behavior
    -> a distinct engine -> its own trace + compile (the solo compiled-
    step caches key on behavior identity, so fresh points always miss).
    The ensemble traces its family ONCE with parameters as tracers; new
    points ride the cached runner.  So the steady-state comparison is
    warm-family batched vs compile-inclusive sequential — per fresh
    config, forever, by construction.  The warm-vs-warm ratio (pure
    batching, no compile anywhere) is reported alongside for honesty."""
    import time as _time

    from repro.core.ensemble import replica_state
    from repro.sims import sir_mechanics as sm

    R, steps, n_agents = 8, 20, 200

    def mk_points(lo):
        return [{**sm.ensemble_defaults(), "beta": lo + 0.01 * r,
                 "seed": r} for r in range(R)]

    ens = sm.ensemble_family(interior=(8, 8))
    warm = sm.ensemble_init(ens, mk_points(0.010), n_agents=n_agents)
    t0 = _time.perf_counter()
    out, _ = ens.run(warm, steps)   # compiles the family runner once
    jax.block_until_ready(out.state.soa.attrs["pos"])
    family_compile_s = _time.perf_counter() - t0

    # fresh points through the warm family: no retrace
    estate = sm.ensemble_init(ens, mk_points(0.011), n_agents=n_agents)

    def run_batched():
        o, _ = ens.run(estate, steps)
        jax.block_until_ready(o.state.soa.attrs["pos"])

    us_batched = timeit(run_batched, n=3, warmup=1)

    # sequential over another fresh set: per-point compile is inherent
    # (cold by construction — each point measured once)
    seq_points = mk_points(0.012)
    states = [replica_state(estate.state, r) for r in range(R)]
    t0 = _time.perf_counter()
    warm_solo_us = 0.0
    for r, p in enumerate(seq_points):
        eng = ens.solo_engine({k: p[k] for k in ens.param_names})
        seg = eng.make_segment_runner(None)
        jax.block_until_ready(seg(states[r], steps, True)
                              .soa.attrs["pos"])
        t1 = _time.perf_counter()   # warm rerun, for the no-compile ratio
        jax.block_until_ready(seg(states[r], steps, True)
                              .soa.attrs["pos"])
        warm_solo_us += (_time.perf_counter() - t1) * 1e6
    us_seq = (_time.perf_counter() - t0) * 1e6 - warm_solo_us

    speedup = us_seq / us_batched
    warm_ratio = warm_solo_us / us_batched
    cps = R / (us_batched / 1e6)
    emit("ensemble_configs_per_s", us_batched / R,
         f"{cps:.2f} configs/s at R={R} x {steps} steps; {speedup:.1f}x "
         f"vs sequential solo over fresh points (compile-inclusive, "
         f"{us_seq / R / 1e6:.1f} s/config); warm-vs-warm {warm_ratio:.2f}x; "
         f"family compile {family_compile_s:.0f}s, amortized over every "
         "later batch")


def bench_serve():
    """Steady-state request latency through the scenario server: one
    warm-up slot compiles the family's runner, then a full slot measures
    submit->done wall time per request (shared cached dispatches)."""
    from repro.launch.serve import (
        ScenarioRequest, ScenarioServer, sir_mechanics_family)

    slot, steps = 8, 20
    server = ScenarioServer([sir_mechanics_family(n_agents=200)],
                            slot_size=slot)

    def batch(seed0):
        rids = [server.submit(ScenarioRequest(
                    family="sir_mechanics", params={"beta": 0.05},
                    steps=steps, stream_every=5, seed=seed0 + i))
                for i in range(slot)]
        server.drain()
        return [server.handle(r) for r in rids]

    batch(0)                       # warm-up: compiles the runner
    handles = batch(slot)
    lat_ms = [h.latency_s * 1e3 for h in handles]
    occ = server.stats()["mean_occupancy"]
    emit("serve_request_latency_ms", float(np.mean(lat_ms)) * 1e3,
         f"{np.mean(lat_ms):.1f} ms mean over a full slot of {slot} "
         f"({steps} steps, stream_every=5, occupancy {occ:.2f})")


BENCHES = {
    "serialization": bench_serialization,
    "simcheck": bench_simcheck,
    "resilience": bench_resilience,
    "delta": bench_delta,
    "sweep": bench_sweep,
    "sweep_3d": bench_sweep_3d,
    "halo_bytes_3d": bench_halo_bytes_3d,
    "comm_budget": bench_comm_budget,
    "sim": bench_sims,
    "sim_tumor_spheroid": bench_sim_tumor_spheroid,
    "scaling": bench_scaling,
    "rebalance": bench_rebalance,
    "rebalance_uneven": bench_rebalance_uneven,
    "ensemble": bench_ensemble,
    "serve": bench_serve,
}


def main(argv=None) -> None:
    from repro.core.compile_cache import enable_persistent_cache

    argv = sys.argv[1:] if argv is None else argv
    enable_persistent_cache()
    only = None
    if argv and argv[0] == "--only":
        if len(argv) < 2:
            sys.exit("--only needs a prefix list, e.g. --only sweep,sim")
        only = [p.strip() for p in argv[1].split(",")]
        if not any(n.startswith(p) for n in BENCHES for p in only):
            sys.exit(f"--only {argv[1]}: no benchmark matches "
                     f"(known: {', '.join(BENCHES)})")
    for name, fn in BENCHES.items():
        if only is None or any(name.startswith(p) for p in only):
            fn()
    out = ROOT / "BENCH_results.json"
    merged = merge_rows(out, ROWS)
    out.write_text(json.dumps(merged, indent=1))
    print(f"\n# {len(ROWS)} benchmark rows -> {out} "
          f"({len(merged)} total after merge)")


if __name__ == "__main__":
    main()
