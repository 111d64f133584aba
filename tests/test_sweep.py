"""Fused interaction-sweep parity + scan-fused driver equivalence.

Pins the three sweep backends (reference | tiled | pallas) against each
other for every bundled sim behavior and for composed stacks (including the
spawn path), the INTERPRET auto-detection contract, the one-pass migration
invariants, and the segment runner (``Engine.drive`` / ``Simulation.run``
scan fusion) against the per-step loop — the latter under
warnings-as-errors so no deprecation or tracing warning hides in the fused
path.

Tolerances: ``tiled`` re-associates nothing (the j axis is reduced in the
reference's offset order) but XLA fuses the two graphs differently, so FMA
contraction can flip the last bit of float force chains — tiled parity is
pinned to 1e-5 absolute on float accumulators and *exact* on count-valued
ones.  ``pallas`` (interpret mode on CPU) is pinned to the usual kernel
tolerance.  The scan-fused driver runs the identical per-step graph inside
``fori_loop`` and is pinned bit-exact.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AgentSchema, Behavior, DeltaConfig, Domain, Engine, compose,
    total_agents,
)
from repro.core.behaviors import displacement_update, soft_repulsion_adhesion
from repro.core.grid import clear_ring
from repro.core.halo import LocalComm, halo_exchange
from repro.core.neighbors import (
    SWEEP_BACKENDS,
    pair_accumulate,
    resolve_sweep_backend,
    sweep_accumulate,
    sweep_accumulate_overlapped,
)
from repro.sims import (
    cell_clustering, cell_proliferation, epidemiology, oncology,
    sir_mechanics,
)

SIM_BEHAVIORS = {
    "cell_clustering": (cell_clustering.behavior(), "closed"),
    "cell_proliferation": (cell_proliferation.behavior(), "closed"),
    "epidemiology": (epidemiology.behavior(), "toroidal"),
    "oncology": (oncology.behavior(), "closed"),
}


def make_state(beh, boundary="closed", n=260, seed=0, interior=(6, 6),
               cap=16):
    geom = Domain(cell_size=2.0, interior=interior, mesh_shape=(1, 1),
                    cap=cap, boundary=boundary)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(seed)
    lx, ly = geom.domain_size
    pos = rng.uniform(0.5, lx - 0.5, (n, 2)).astype(np.float32)
    attrs = {}
    for name, _, dtype in beh.schema.fields:
        if dtype == jnp.int32:
            attrs[name] = rng.integers(0, 2, n).astype(np.int32)
        else:
            attrs[name] = rng.uniform(0.6, 1.4, n).astype(np.float32)
    return eng, eng.init_state(pos, attrs, seed=seed)


def run_sweep(eng, state, backend):
    beh = eng.behavior
    fn = jax.jit(lambda soa: sweep_accumulate(
        eng.geom, soa, beh.pair_fn, beh.pair_attrs, beh.radius, beh.params,
        backend=backend))
    return fn(state.soa)


def assert_acc_close(got, want, atol):
    assert set(got) == set(want)
    for k in want:
        g, w = np.asarray(got[k]), np.asarray(want[k])
        if atol == 0:
            np.testing.assert_array_equal(g, w, err_msg=k)
        else:
            np.testing.assert_allclose(g, w, atol=atol, rtol=atol,
                                       err_msg=k)


# ---------------------------------------------------------------------------
# backend parity: all four sims
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", sorted(SIM_BEHAVIORS))
@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_sweep_backend_matches_reference(name, backend):
    beh, boundary = SIM_BEHAVIORS[name]
    eng, state = make_state(beh, boundary)
    want = run_sweep(eng, state, "reference")
    got = run_sweep(eng, state, backend)
    assert_acc_close(got, want, atol=1e-5)


def test_tiled_count_accumulators_exact():
    """Integer-valued accumulators (sums of 1.0) have no rounding: the
    tiled sweep must agree with the reference bit-for-bit on them."""
    beh, boundary = SIM_BEHAVIORS["epidemiology"]
    eng, state = make_state(beh, boundary)
    want = run_sweep(eng, state, "reference")
    got = run_sweep(eng, state, "tiled")
    assert_acc_close(got, want, atol=0)   # n_inf: pure neighbor counts


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_sweep_backend_matches_reference_composed(backend):
    """Composed stack (mechanics + SIR, distinct radii, namespaced
    accumulators) through one sweep on every backend."""
    beh = sir_mechanics.behavior()
    eng, state = make_state(beh, "toroidal")
    want = run_sweep(eng, state, "reference")
    assert any(k.startswith("b0.") for k in want)  # namespaced stack
    got = run_sweep(eng, state, backend)
    assert_acc_close(got, want, atol=1e-5)


@pytest.mark.parametrize("backend", ["tiled", "pallas"])
def test_composed_spawning_stack_end_to_end(backend):
    """compose(mechanics, proliferation) driven through the engine on each
    backend vs the reference backend: the spawn path (children, gid issue,
    re-bin) must produce the same population and near-identical positions."""
    comp = compose(cell_clustering.behavior(), cell_proliferation.behavior())
    assert comp.can_spawn

    def final(backend):
        geom = Domain(cell_size=2.0, interior=(6, 6), mesh_shape=(1, 1),
                        cap=32)
        eng = Engine(geom=geom, behavior=comp, dt=0.1,
                     sweep_backend=backend)
        rng = np.random.default_rng(3)
        lx, ly = geom.domain_size
        n = 40
        pos = rng.uniform(2.0, lx - 2.0, (n, 2)).astype(np.float32)
        attrs = {"diameter": np.full((n,), 0.8, np.float32),
                 "ctype": rng.integers(0, 2, n).astype(np.int32)}
        state = eng.init_state(pos, attrs, seed=0)
        _, state, _ = eng.drive(state, 8)
        return state

    want = final("reference")
    got = final(backend)
    assert total_agents(got) == total_agents(want) > 40
    sort = lambda s: np.sort(
        np.asarray(s.soa.attrs["pos"]).reshape(-1, 2)[
            np.asarray(s.soa.valid).ravel()], axis=0)
    np.testing.assert_allclose(sort(got), sort(want), atol=1e-4)


@pytest.mark.parametrize("boundary", ["closed", "toroidal"])
def test_3d_pallas_matches_tiled_oracle(boundary):
    """The kernel factory on a 3-D Domain (27-offset stencil, INTERPRET
    mode on CPU) against the tiled oracle: count accumulators exact, float
    accumulators to kernel tolerance; the explicit 2-D path is covered
    bit-for-bit by the parametrized parity tests above."""
    from repro.sims import tumor_spheroid

    beh = tumor_spheroid.behavior()       # composed stack, count acc
    geom = Domain(cell_size=2.0, interior=(3, 4, 5), mesh_shape=(1, 1, 1),
                  cap=12, boundary=boundary)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(7)
    n = 150
    size = geom.domain_size
    pos = rng.uniform([0.5] * 3, [s - 0.5 for s in size], (n, 3)
                      ).astype(np.float32)
    attrs = {"diameter": rng.uniform(0.6, 1.4, n).astype(np.float32),
             "ctype": np.ones((n,), np.int32),
             "nutrient": rng.uniform(0.0, 1.0, n).astype(np.float32)}
    state = eng.init_state(pos, attrs, seed=0)

    want = run_sweep(eng, state, "tiled")
    got = run_sweep(eng, state, "pallas")
    assert set(got) == set(want)
    counts = [k for k in want if k.endswith("crowd")]
    assert counts
    for k in counts:
        np.testing.assert_array_equal(np.asarray(got[k]),
                                      np.asarray(want[k]), err_msg=k)
    assert_acc_close(got, want, atol=1e-5)


def test_resolve_backend_3d_no_longer_falls_back():
    """The kernel factory now takes 3-D blocks: explicit 'pallas' is legal
    on 3-D domains, and 'auto' resolves identically for 2-D and 3-D (pallas
    on TPU, tiled elsewhere)."""
    assert resolve_sweep_backend("pallas", ndim=3) == "pallas"
    assert resolve_sweep_backend("auto", ndim=3) == \
        resolve_sweep_backend("auto", ndim=2)
    if jax.default_backend() != "tpu":
        assert resolve_sweep_backend("auto", ndim=3) == "tiled"


def test_resolve_backend_and_interpret_auto(monkeypatch):
    from repro.kernels import ops

    # auto resolves per JAX backend (this container is CPU -> tiled,
    # interpreted Pallas)
    assert resolve_sweep_backend("auto") in SWEEP_BACKENDS
    if jax.default_backend() != "tpu":
        assert resolve_sweep_backend("auto") == "tiled"
        assert ops.use_interpret() is True
    with pytest.raises(ValueError):
        resolve_sweep_backend("vectorized")
    # explicit overrides win over auto-detection
    assert ops.use_interpret(True) is True
    assert ops.use_interpret(False) is False
    old = ops.INTERPRET
    try:
        ops.INTERPRET = False
        assert ops.use_interpret() is False
        assert ops.use_interpret(True) is True
    finally:
        ops.INTERPRET = old
    # per platform: pallas only on TPU, and a TPU run never interprets,
    # whatever the call site or the module-level force asks
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert resolve_sweep_backend("auto") == "tiled"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert resolve_sweep_backend("auto") == "pallas"
    monkeypatch.setattr(ops, "INTERPRET", True)
    assert ops.use_interpret() is False
    assert ops.use_interpret(True) is False


# ---------------------------------------------------------------------------
# scan-fused driver vs per-step loop (warnings-as-errors)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("delta", [False, True])
def test_segment_runner_matches_per_step_drive(delta):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        beh = cell_clustering.behavior()
        cfg = DeltaConfig(enabled=delta, qdtype=jnp.int16,
                          refresh_interval=4)
        geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(1, 1),
                        cap=24)
        eng = Engine(geom=geom, behavior=beh, delta_cfg=cfg, dt=0.1)
        rng = np.random.default_rng(0)
        pos = rng.uniform(0.5, 15.5, (250, 2)).astype(np.float32)
        attrs = {"diameter": np.full((250,), 1.0, np.float32),
                 "ctype": rng.integers(0, 2, 250).astype(np.int32)}
        s0 = eng.init_state(pos, attrs, seed=0)

        # per-step loop (explicit step_fn keeps drive on the legacy path)
        _, s1, _ = eng.drive(s0, 10, step_fn=eng.make_local_step())
        # scan-fused: one dispatch per refresh segment
        _, s2, _ = eng.drive(s0, 10)

        np.testing.assert_array_equal(np.asarray(s1.soa.attrs["pos"]),
                                      np.asarray(s2.soa.attrs["pos"]))
        np.testing.assert_array_equal(np.asarray(s1.soa.valid),
                                      np.asarray(s2.soa.valid))
        np.testing.assert_array_equal(np.asarray(s1.key), np.asarray(s2.key))
        assert int(s2.it[0, 0]) == 10


def test_facade_fuses_segments_and_matches_per_step():
    """Simulation.run with a sparse scheduled op fuses the gaps; results
    and op cadence match the per-step facade exactly."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        from repro.core import Simulation

        beh = cell_clustering.behavior()
        geom = dict(interior=(8, 8), cap=24)
        pos, attrs = _inputs()

        sim_fused = Simulation(geom, beh, dt=0.1).init(pos, attrs, seed=0)
        sim_fused.every(5, lambda s: s.n_agents(), name="n")
        sim_fused.run(12)

        sim_step = Simulation(geom, beh, dt=0.1).init(pos, attrs, seed=0)
        sim_step.every(5, lambda s: s.n_agents(), name="n")
        sim_step.run(12, fused=False)   # one dispatch per step

        assert sim_fused.series["n"] == sim_step.series["n"]
        assert sim_fused.iteration == sim_step.iteration == 12
        np.testing.assert_array_equal(
            np.asarray(sim_fused.state.soa.attrs["pos"]),
            np.asarray(sim_step.state.soa.attrs["pos"]))


def _inputs(n=250, seed=0, domain=16.0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.5, domain - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return pos, attrs


# ---------------------------------------------------------------------------
# stencil soundness: radius > cell_size is rejected, not silently wrong
# ---------------------------------------------------------------------------

def test_radius_over_cell_size_rejected_and_pins_the_silent_failure():
    """Before the simcheck gate, ``radius > cell_size`` built fine and the
    3**ndim sweep silently dropped every pair between non-adjacent cells.
    The facade now rejects it at construction; ``check="off"`` keeps the
    escape hatch and this test pins the miss the gate is protecting
    against: the identical two-agent configuration interacts when the cell
    covers the radius and is invisible when it doesn't."""
    from repro.analysis import ContractError
    from repro.core import Simulation

    beh = Behavior(
        schema=AgentSchema.create({"diameter": ((), jnp.float32),
                                   "ctype": ((), jnp.int32)}),
        pair_fn=soft_repulsion_adhesion, pair_attrs=("diameter", "ctype"),
        update_fn=displacement_update, radius=3.0,
        params={"repulsion": 2.0, "adhesion": 0.4, "same_type_only": 0.0,
                "max_step": 0.5})

    with pytest.raises(ContractError, match="stencil-soundness"):
        Simulation(dict(cell_size=2.0, interior=(6, 6), cap=8), beh, dt=0.1)

    # two agents 2.2 apart (< radius 3): cells (0, *) and (2, *) under
    # cell_size=2.0 -- non-adjacent, so the sweep never pairs them
    pos = np.array([[1.9, 6.0], [4.1, 6.0]], np.float32)

    def total_force(cell_size, interior):
        geom = Domain(cell_size=cell_size, interior=interior,
                      mesh_shape=(1, 1), cap=8)
        eng = Engine(geom=geom, behavior=beh, dt=0.1)   # check defaults off
        attrs = {"diameter": np.full((2,), 1.0, np.float32),
                 "ctype": np.zeros((2,), np.int32)}
        state = eng.init_state(pos, attrs, seed=0)
        acc = sweep_accumulate(geom, state.soa, beh.pair_fn, beh.pair_attrs,
                               beh.radius, beh.params)
        return float(jnp.sum(jnp.abs(acc["force"])))

    assert total_force(cell_size=4.0, interior=(3, 3)) > 0.0  # honest cell
    assert total_force(cell_size=2.0, interior=(6, 6)) == 0.0  # dropped


# ---------------------------------------------------------------------------
# one-pass migration invariants
# ---------------------------------------------------------------------------

def test_one_pass_migration_conserves_through_diagonal_wrap():
    """Toroidal single-device domain with diagonal drift: every step every
    agent crosses a ring in both axes (the forwarded-corner path) and the
    population, ids and domain bounds must hold."""
    schema = AgentSchema.create({"diameter": ((), jnp.float32),
                                 "ctype": ((), jnp.int32)})

    def drift(attrs, valid, acc, key, params, dt):
        new = dict(attrs)
        new["pos"] = attrs["pos"] + jnp.where(
            valid[..., None], jnp.asarray([1.7, 1.3]), 0.0)
        return new, valid, jnp.zeros_like(valid), None

    beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
                   pair_attrs=("diameter", "ctype"), update_fn=drift,
                   radius=2.0,
                   params={"repulsion": 0.0, "adhesion": 0.0,
                           "same_type_only": 0.0, "max_step": 0.0})
    geom = Domain(cell_size=2.0, interior=(6, 6), mesh_shape=(1, 1),
                    cap=16, boundary="toroidal")
    eng = Engine(geom=geom, behavior=beh, dt=1.0)
    rng = np.random.default_rng(1)
    n = 150
    lx, ly = geom.domain_size
    pos = rng.uniform(0.0, lx, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": np.zeros((n,), np.int32)}
    state = eng.init_state(pos, attrs, seed=0)
    _, state, _ = eng.drive(state, 25)
    assert total_agents(state) == n
    assert int(state.dropped.sum()) == 0
    p = np.asarray(state.soa.attrs["pos"]).reshape(-1, 2)[
        np.asarray(state.soa.valid).ravel()]
    assert (p >= 0).all() and (p[:, 0] <= lx).all() and (p[:, 1] <= ly).all()
    gr = np.asarray(state.soa.attrs["gid_rank"]).ravel()
    gc = np.asarray(state.soa.attrs["gid_count"]).ravel()
    v = np.asarray(state.soa.valid).ravel()
    keys = gr[v].astype(np.int64) * (1 << 32) + gc[v]
    assert len(np.unique(keys)) == n


# ---------------------------------------------------------------------------
# overlapped interior/boundary split vs the monolithic sweep
# ---------------------------------------------------------------------------

def split_vs_monolithic(eng, state, backend):
    """(overlapped, monolithic) accumulators for one engine state, built
    exactly the way ``Engine.local_step`` builds them: ``soa_pre`` is the
    ring-invalidated SoA (the interior pass's input) and ``soa_post`` the
    SoA after a full-refresh LocalComm aura exchange (wrap fill on
    toroidal axes, cleared ring on closed ones)."""
    geom, beh = eng.geom, eng.behavior
    soa_pre = clear_ring(state.soa)
    idx0 = (0,) * geom.ndim
    refs = {d: {f: v[idx0] for f, v in slab.items()}
            for d, slab in state.refs.items()}
    comm = LocalComm(toroidal=geom.toroidal)
    soa_post, _, _, _ = halo_exchange(
        geom, soa_pre, comm, refs, eng.delta_cfg, True, None)

    fn = jax.jit(lambda pre, post: (
        sweep_accumulate_overlapped(
            geom, pre, post, beh.pair_fn, beh.pair_attrs, beh.radius,
            beh.params, backend=backend),
        sweep_accumulate(
            geom, post, beh.pair_fn, beh.pair_attrs, beh.radius,
            beh.params, backend=backend)))
    return fn(soa_pre, soa_post)


@pytest.mark.parametrize("name", sorted(SIM_BEHAVIORS))
@pytest.mark.parametrize("backend", ["reference", "tiled", "pallas"])
def test_overlapped_split_bitexact_vs_monolithic(name, backend):
    """The interior/boundary split is a pure re-schedule: on the equal
    split every interior cell's accumulators must match the monolithic
    sweep bit-for-bit, per backend, for every bundled sim."""
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        beh, boundary = SIM_BEHAVIORS[name]
        eng, state = make_state(beh, boundary)
        got, want = split_vs_monolithic(eng, state, backend)
        assert_acc_close(got, want, atol=0)


@pytest.mark.parametrize("backend", ["reference", "tiled", "pallas"])
def test_overlapped_split_bitexact_3d_spheroid(backend):
    """3-D composed spheroid stack: the split recomputes 6 faces whose
    3-plane bands overlap at edges and corners — the idempotent-overwrite
    argument must hold in 3-D too, bit-for-bit."""
    from repro.sims import tumor_spheroid

    with warnings.catch_warnings():
        warnings.simplefilter("error")
        beh = tumor_spheroid.behavior()
        geom = Domain(cell_size=2.0, interior=(3, 4, 5),
                      mesh_shape=(1, 1, 1), cap=12, boundary="closed")
        eng = Engine(geom=geom, behavior=beh, dt=0.1)
        rng = np.random.default_rng(7)
        n = 150
        size = geom.domain_size
        pos = rng.uniform([0.5] * 3, [s - 0.5 for s in size], (n, 3)
                          ).astype(np.float32)
        attrs = {"diameter": rng.uniform(0.6, 1.4, n).astype(np.float32),
                 "ctype": np.ones((n,), np.int32),
                 "nutrient": rng.uniform(0.0, 1.0, n).astype(np.float32)}
        state = eng.init_state(pos, attrs, seed=0)
        got, want = split_vs_monolithic(eng, state, backend)
        assert_acc_close(got, want, atol=0)


ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(code: str, devices: int = 4, timeout: int = 1800,
            **env_extra: str) -> str:
    env = dict(os.environ, **env_extra)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_engine_overlap_sharded_matches_sequential():
    """Full driven runs on a 2x2 mesh, overlap on vs off, all three
    backends, equal split and uneven RCB ownership, delta-by-default.

    Equal split: the boundary faces cover every ring-adjacent plane, so
    the whole run (positions, gids, validity) is pinned bit-exact.
    Uneven RCB: the face index is traced (the owned extent), XLA fuses
    the dynamic-sliced band differently, and FMA contraction can flip
    the last bits of float force chains — positions are pinned to 1e-5,
    ids and population exactly."""
    out = run_sub("""
import numpy as np, jax.numpy as jnp
from repro.core import AgentSchema, Behavior, Partition
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.sims.common import make_sim

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.4,
                                   "same_type_only": 1.0, "max_step": 0.5})
rng = np.random.default_rng(0)
n = 300
pos = rng.uniform(0.5, 31.5, size=(n, 2)).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, size=(n,)).astype(np.int32)}

def key(state):
    v = np.asarray(state.soa.valid).ravel()
    p = np.asarray(state.soa.attrs["pos"]).reshape(-1, 2)[v]
    gr = np.asarray(state.soa.attrs["gid_rank"]).ravel()[v]
    gc = np.asarray(state.soa.attrs["gid_count"]).ravel()[v]
    o = np.lexsort((gc, gr))
    return p[o], gr[o], gc[o]

def run(overlap, backend, part=None):
    kw = (dict(partition=part) if part is not None
          else dict(interior=(8, 8), mesh_shape=(2, 2)))
    sim = make_sim(beh, cap=24, dt=0.5, overlap=overlap,
                   sweep_backend=backend, **kw)
    sim.init(pos, attrs)
    sim.run(6)
    return key(sim.state)

import warnings
part = Partition(cuts=((0, 6, 16), (0, 9, 16)))
for backend in ("reference", "tiled", "pallas"):
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        seq = run("off", backend)
        ovl = run("on", backend)
        for a, b in zip(seq, ovl):
            np.testing.assert_array_equal(a, b)   # equal split: bit-exact
        sequ = run("off", backend, part)
        ovlu = run("on", backend, part)
        np.testing.assert_array_equal(sequ[1], ovlu[1])
        np.testing.assert_array_equal(sequ[2], ovlu[2])
        np.testing.assert_allclose(sequ[0], ovlu[0], atol=1e-5)
    print("OK", backend)
print("OK overlap sharded")
""", devices=4)
    assert "OK overlap sharded" in out


def test_step_program_is_independent_of_the_hash_seed():
    """The compiled step is the persistent compile cache's key, so two
    processes must lower one program to one text.  Iterating a ``set`` of
    column names orders the sweep's ops by string hash, which differs from
    process to process."""
    code = """
import hashlib
from repro.sims import cell_clustering
from repro.sims.common import make_sim
from repro.analysis.jaxpr_audit import probe_state
import jax, jax.numpy as jnp
for backend in ("reference", "tiled"):
    sim = make_sim(cell_clustering.behavior(), interior=(6, 6), cap=8,
                   sweep_backend=backend)
    state = jax.eval_shape(lambda: probe_state(sim.engine))
    text = sim.engine.make_segment_runner(None).programs[True].lower(
        state, jax.ShapeDtypeStruct((), jnp.int32)).as_text()
    print(backend, hashlib.sha256(text.encode()).hexdigest())
"""
    outs = [run_sub(code, devices=1, PYTHONHASHSEED=seed)
            for seed in ("1", "2")]
    assert outs[0] == outs[1], outs
