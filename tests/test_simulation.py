"""Simulation facade + behavior composition tests.

Parity tests pin the facade's contract: it is a zero-semantics wrapper —
bit-exact with the raw engine loop locally and on a sharded mesh, and
``compose`` of a single behavior is bit-exact with that behavior alone.
The re-shard tests pin the headline API fix: ``sim.engine``/``sim.state``
stay consistent across a mid-run mass migration with no stale-handle
warning on any facade path.

Sharded cases run in subprocesses (XLA placeholder devices must be
configured before jax initializes), same pattern as test_distributed_abm.
"""

import os
import subprocess
import sys
import textwrap
import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AgentSchema, Behavior, Checkpoint, Engine, Domain, Rebalance,
    Simulation, compose, operations, total_agents,
)
from repro.core.behaviors import displacement_update, soft_repulsion_adhesion
from repro.core.reshard import estimate_device_runtimes

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = AgentSchema.create({
    "diameter": ((), jnp.float32),
    "ctype": ((), jnp.int32),
})


def make_behavior(**over):
    params = {"repulsion": 2.0, "adhesion": 0.4, "same_type_only": 1.0,
              "max_step": 0.5}
    params.update(over.pop("params", {}))
    return Behavior(
        schema=SCHEMA, pair_fn=soft_repulsion_adhesion,
        pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
        radius=over.pop("radius", 2.0), params=params, **over)


def make_inputs(n=250, seed=0, domain=16.0):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.5, domain - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return pos, attrs


def sorted_positions(state):
    v = np.asarray(state.soa.valid).ravel()
    p = np.asarray(state.soa.attrs["pos"]).reshape(-1, 2)[v]
    return p[np.lexsort(p.T)]


# ---------------------------------------------------------------------------
# facade parity (local)
# ---------------------------------------------------------------------------

def test_facade_matches_raw_engine_bit_exact():
    pos, attrs = make_inputs()
    beh = make_behavior()
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(1, 1),
                    cap=24)

    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    s = eng.init_state(pos, attrs, seed=0)
    step = eng.make_local_step()
    for _ in range(8):
        s = step(s, full_halo=True)

    sim = Simulation(geom, beh, dt=0.1).init(pos, attrs, seed=0).run(8)
    np.testing.assert_array_equal(np.asarray(sim.state.soa.attrs["pos"]),
                                  np.asarray(s.soa.attrs["pos"]))
    np.testing.assert_array_equal(np.asarray(sim.state.soa.valid),
                                  np.asarray(s.soa.valid))
    assert sim.iteration == 8 and sim.mesh is None


def test_facade_matches_deprecated_run_sim():
    from repro.sims import common

    pos, attrs = make_inputs()
    beh = make_behavior()
    with pytest.warns(DeprecationWarning):
        eng = common.make_engine(beh, interior=(8, 8))
    s = eng.init_state(pos, attrs, seed=0)
    with pytest.warns(DeprecationWarning):
        s, series = common.run_sim(eng, s, 6,
                                   collect=lambda st: total_agents(st))

    sim = common.make_sim(beh, interior=(8, 8)).init(pos, attrs, seed=0)
    sim.run(6, collect=lambda st: total_agents(st))
    assert sim.series["collect"] == series
    np.testing.assert_array_equal(sorted_positions(sim.state),
                                  sorted_positions(s))


# ---------------------------------------------------------------------------
# behavior composition
# ---------------------------------------------------------------------------

def test_compose_single_behavior_bit_exact():
    pos, attrs = make_inputs()
    beh = make_behavior()
    geom = dict(interior=(8, 8), cap=24)

    sim1 = Simulation(geom, beh, dt=0.1).init(pos, attrs, seed=0).run(8)
    simc = Simulation(geom, compose(beh), dt=0.1).init(
        pos, attrs, seed=0).run(8)
    np.testing.assert_array_equal(np.asarray(simc.state.soa.attrs["pos"]),
                                  np.asarray(sim1.state.soa.attrs["pos"]))


def test_compose_single_spawning_behavior_bit_exact():
    from repro.sims import cell_proliferation as cp

    sims = []
    for behs in (cp.behavior(), compose(cp.behavior())):
        sim = Simulation(dict(interior=(8, 8), cap=32), behs, dt=0.1)
        cp.init(sim, 40, seed=0)
        sims.append(sim.run(10))
    assert sims[0].n_agents() == sims[1].n_agents() > 40
    np.testing.assert_array_equal(sorted_positions(sims[0].state),
                                  sorted_positions(sims[1].state))


def test_compose_merges_schema_params_radius_and_spawn():
    from repro.sims import cell_proliferation as cp, epidemiology as epi

    c = compose(cp.behavior(radius=2.0), epi.behavior(radius=1.5))
    assert c.schema.names() == ("ctype", "diameter", "state")
    assert c.radius == 2.0
    assert c.can_spawn
    assert c.params["b0.repulsion"] == 2.0 and "b1.beta" in c.params
    assert set(c.pair_attrs) == {"ctype", "diameter", "state"}
    with pytest.raises(ValueError):
        compose()
    # conflicting attribute spec across schemas
    other = AgentSchema.create({"diameter": ((), jnp.int32)})
    bad = Behavior(schema=other, pair_fn=c.pair_fn, pair_attrs=(),
                   update_fn=c.update_fn, radius=1.0)
    with pytest.raises(ValueError):
        compose(cp.behavior(), bad)


def test_compose_gates_smaller_radius_kernel():
    """A sub-behavior's pair kernel must not see pairs beyond its own
    radius even though the composed sweep uses the max radius."""

    def count_pair(ai, aj, disp, dist2, params):
        return {"n": jnp.ones_like(dist2)}

    def keep(attrs, valid, acc, key, params, dt):
        return dict(attrs), valid, jnp.zeros_like(valid), None

    near = Behavior(schema=SCHEMA, pair_fn=count_pair, pair_attrs=(),
                    update_fn=keep, radius=1.0)
    far = Behavior(schema=SCHEMA, pair_fn=count_pair, pair_attrs=(),
                   update_fn=keep, radius=2.0)
    comp = compose(near, far)

    # two agents 1.5 apart: only the far kernel may count the pair
    pos = np.asarray([[4.0, 4.0], [5.5, 4.0]], np.float32)
    attrs = {"diameter": np.ones(2, np.float32),
             "ctype": np.zeros(2, np.int32)}
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(1, 1), cap=8)
    eng = Engine(geom=geom, behavior=comp, dt=0.1)
    state = eng.init_state(pos, attrs, seed=0)

    from repro.core.neighbors import pair_accumulate
    acc = pair_accumulate(geom, state.soa, comp.pair_fn, comp.pair_attrs,
                          comp.radius, comp.params)
    assert float(jnp.sum(acc["b0.n"])) == 0.0   # gated at radius 1.0
    assert float(jnp.sum(acc["b1.n"])) == 2.0   # one pair, both directions


def test_compose_completes_partial_child_to_union_schema():
    """A spawner whose child dict covers only its own schema must still
    work when composed with a schema-extending behavior: compose fills the
    missing child attributes (e.g. the SIR state) from the parent."""
    from repro.core.agent_soa import POS
    from repro.sims import epidemiology as epi

    schema_a = AgentSchema.create({"diameter": ((), jnp.float32)})

    def no_pair(ai, aj, disp, dist2, params):
        return {"z": jnp.zeros_like(dist2)}

    def spawn_update(attrs, valid, acc, key, params, dt):
        new = dict(attrs)
        child = {POS: new[POS] + 0.05,
                 "diameter": new["diameter"] * 0.5}   # own schema only
        return new, valid, valid, child

    a = Behavior(schema=schema_a, pair_fn=no_pair, pair_attrs=(),
                 update_fn=spawn_update, radius=1.0, can_spawn=True)
    comp = compose(a, epi.behavior(sigma=0.1))

    n = 20
    rng = np.random.default_rng(0)
    pos = rng.uniform(2.0, 14.0, (n, 2)).astype(np.float32)
    st = np.zeros((n,), np.int32)
    st[:5] = epi.I
    sim = Simulation(dict(interior=(8, 8), cap=16), comp, dt=0.1)
    sim.init(pos, {"diameter": np.full((n,), 1.0, np.float32),
                   "state": st}, seed=0)
    sim.run(1)
    assert sim.n_agents() == 2 * n       # every agent spawned one child
    soa = sim.state.soa
    states = np.asarray(soa.attrs["state"]).ravel()[
        np.asarray(soa.valid).ravel()]
    assert set(np.unique(states)) <= {epi.S, epi.I, epi.R}  # inherited


def test_composed_sir_mechanics_sim():
    from repro.sims import sir_mechanics

    state, m = sir_mechanics.run(n_agents=300, steps=30, seed=0)
    ser = m["series"].astype(float)
    assert (ser.sum(axis=1) == 300).all()          # conservation
    assert (np.diff(ser[:, 2]) >= 0).all()         # R monotone
    assert ser[-1, 2] > ser[0, 2] + 50             # epidemic spread
    assert m["same_frac_final"] > m["same_frac_initial"] + 0.15  # clustering
    assert np.isfinite(np.asarray(state.soa.attrs["pos"])).all()


# ---------------------------------------------------------------------------
# scheduled operations
# ---------------------------------------------------------------------------

def test_scheduled_op_cadence_and_series():
    pos, attrs = make_inputs()
    sim = Simulation(dict(interior=(8, 8), cap=24), make_behavior(), dt=0.1)
    sim.init(pos, attrs, seed=0)
    pre_ticks, post_its = [], []
    sim.every(3, lambda s: pre_ticks.append(s.iteration), pre=True,
              record=False)
    sim.every(3, lambda s: s.iteration, name="it")
    sim.every(1, operations.agent_count)
    sim.run(7)
    assert pre_ticks == [0, 3, 6]            # before steps 0, 3, 6
    assert sim.series["it"] == [3, 6]        # after 3 and 6 completed steps
    assert sim.series["agent_count"] == [len(pos)] * 7
    # cadence continues across run() calls
    sim.run(2)
    assert sim.series["it"] == [3, 6, 9]


def test_checkpoint_op_and_elastic_restore_roundtrip(tmp_path):
    pos, attrs = make_inputs(n=120)
    beh = make_behavior()
    sim = Simulation(dict(interior=(8, 8), cap=24), beh, dt=0.1,
                     checkpoint=Checkpoint(str(tmp_path), every=4))
    sim.init(pos, attrs, seed=0)
    sim.run(8)
    from repro.distributed.checkpoint import latest_step
    assert latest_step(str(tmp_path)) == 8     # saved after steps 4 and 8

    sim2 = Simulation.restore(str(tmp_path), beh, n_devices=1)
    assert sim2.n_agents() == sim.n_agents()
    assert sim2.iteration == 8
    np.testing.assert_array_equal(sorted_positions(sim2.state),
                                  sorted_positions(sim.state))
    sim2.run(3)                                # restored facade keeps running
    assert sim2.iteration == 11


# ---------------------------------------------------------------------------
# measured runtime attribution (weighted rebalance signal)
# ---------------------------------------------------------------------------

def test_estimate_device_runtimes_weights_dense_devices():
    rng = np.random.default_rng(0)
    n = 300
    # all agents clustered on device (0,0) of a 2x2 mesh; a few elsewhere
    pos = np.concatenate([
        rng.uniform(1.0, 6.0, (n - 10, 2)),
        rng.uniform(17.0, 30.0, (10, 2))]).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": np.zeros((n,), np.int32)}
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2),
                    cap=64)
    eng = Engine(geom=geom, behavior=make_behavior(), dt=0.1)
    state = eng.init_state(pos, attrs, seed=0)

    rt = estimate_device_runtimes(geom, state, wall_s=1.0)
    assert rt.shape == (2, 2)
    assert rt.sum() == pytest.approx(1.0)
    # the dense device dominates the measured-work attribution, and
    # super-linearly vs its agent share (quadratic pair-work signal)
    assert rt[0, 0] > 0.9
    assert rt[0, 0] / max(rt[1, 1], 1e-12) > (n - 10) / 10

    # empty state falls back to a uniform split
    empty = eng.init_state(np.zeros((0, 2), np.float32),
                           {"diameter": np.zeros(0, np.float32),
                            "ctype": np.zeros(0, np.int32)}, seed=0)
    np.testing.assert_allclose(
        estimate_device_runtimes(geom, empty, 1.0), 0.25)


# ---------------------------------------------------------------------------
# sharded execution through the facade (subprocess: needs devices)
# ---------------------------------------------------------------------------

def run_sub(code: str, devices: int = 4, timeout: int = 1200) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}")
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    p = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_facade_matches_raw_sharded_loop():
    """Facade on a 2x2 mesh is bit-exact with the hand-built shard_map
    loop — and the facade built its own mesh from the geometry."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import AgentSchema, Behavior, Engine, Domain, Simulation
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.launch.mesh import make_abm_mesh

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
         "ctype": rng.integers(0, 2, n).astype(np.int32)}

geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=16)
eng = Engine(geom=geom, behavior=beh, dt=0.1)
s = eng.init_state(pos, attrs, seed=0)
step = eng.make_sharded_step(make_abm_mesh((2, 2)))
for _ in range(8):
    s = step(s, full_halo=True)

sim = Simulation(geom, beh, dt=0.1).init(pos, attrs, seed=0).run(8)
assert sim.mesh is not None and sim.mesh.devices.shape == (2, 2)
np.testing.assert_array_equal(np.asarray(sim.state.soa.attrs["pos"]),
                              np.asarray(s.soa.attrs["pos"]))
np.testing.assert_array_equal(np.asarray(sim.state.soa.valid),
                              np.asarray(s.soa.valid))
print("OK")
""")
    assert "OK" in out


def test_reshard_through_facade_keeps_engine_state_consistent():
    """Mid-run re-shard via the facade: no stale-engine warning anywhere,
    sim.engine/sim.state/sim.mesh all agree on the new mesh, and the
    trajectory still matches the single-device oracle."""
    out = run_sub("""
import warnings, numpy as np, jax, jax.numpy as jnp
from repro.core import (AgentSchema, Behavior, Engine, Domain, Rebalance,
                        Simulation)
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.core.reshard import current_imbalance

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.4,
                                   "same_type_only": 1.0, "max_step": 0.5})
rng = np.random.default_rng(0)
n = 400
c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, n).astype(np.int32)}

def sorted_positions(state):
    v = np.asarray(state.soa.valid).ravel()
    p = np.asarray(state.soa.attrs["pos"]).reshape(-1, 2)[v]
    return p[np.lexsort(p.T)]

# single-device oracle
geom1 = Domain(cell_size=2.0, interior=(16, 16), mesh_shape=(1, 1), cap=32)
s1 = Simulation(geom1, beh, dt=0.1).init(pos, attrs, seed=0).run(10)

# facade on the pathological 2x2 split, weighted re-shard allowed at step 5
geom4 = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
sim = Simulation(geom4, beh, dt=0.1,
                 rebalance=Rebalance(every=5, threshold=0.3, weighted=True))
sim.init(pos, attrs, seed=0)
before = current_imbalance(sim.geom, sim.state)
with warnings.catch_warnings():
    warnings.simplefilter("error")      # any stale-engine warning -> fail
    sim.run(10)
assert any(r["applied"] for r in sim.rebalancer.history), \
    sim.rebalancer.history
assert sim.engine.geom.mesh_shape != (2, 2)
assert sim.mesh.devices.shape == sim.engine.geom.mesh_shape
assert sim.state.it.shape == sim.engine.geom.mesh_shape
assert sim.n_agents() == n
after = current_imbalance(sim.geom, sim.state)
assert after * 2 <= before, (before, after)
# Tolerance: a 2x2 split cannot be bit-exact with one device.  Migrants
# re-bin after a cell's resident agents on their new device, where one
# device orders a cell's slots by their old cell, so each agent's pair
# sums add in another order.  Those ulp differences grow through the
# soft-sphere dynamics (and flip adhesion/repulsion at overlap == 0):
# 2.7e-4 after these 10 steps with jax 0.9.0 on CPU.  1e-3 bounds that,
# and is 1/1000 of the agent diameter.
err = np.max(np.abs(sorted_positions(s1.state) - sorted_positions(sim.state)))
assert err < 1e-3, f"divergence {err}"
# facade keeps running on the new mesh without any caller-side fixup
sim.run(3)
assert sim.iteration == 13
print("OK", before, "->", after, "err", err)
""")
    assert "OK" in out


def _host_spans(trace_dir):
    """(name, start_ns, end_ns) of every ``sim.*`` host span the profiler
    recorded under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    return sorted(
        (ev.name, ev.start_ns, ev.end_ns)
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name.startswith("sim."))


def test_run_names_its_host_work_on_the_profilers_clock(tmp_path):
    """With guards on, each dispatch, the wait for its outputs, the guards'
    host check and each scheduled op is a span, and the check starts only
    once the wait has ended."""
    import jax

    pos, attrs = make_inputs()
    sim = Simulation(dict(interior=(8, 8), cap=24), make_behavior(), dt=0.1,
                     guards="warn")
    sim.init(pos, attrs, seed=0)
    sim.every(2, operations.agent_count, name="count")
    sim.run(1)                                   # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        sim.run(4)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    names = [n for n, _, _ in spans]
    n = names.count("sim.dispatch")             # one per fused segment
    assert 2 <= n < 4
    assert names.count("sim.wait") == n
    assert names.count("sim.guards.host_check") == n
    assert names.count("sim.op.count") == 2
    waits = [(s, e) for n, s, e in spans if n == "sim.wait"]
    checks = [(s, e) for n, s, e in spans if n == "sim.guards.host_check"]
    for (_, wait_end), (check_start, _) in zip(waits, checks):
        assert check_start >= wait_end


def test_run_without_guards_or_codec_moves_nothing_to_the_host():
    """The spans add no host sync: with guards and the delta codec off a
    run never reads the device."""
    import jax

    pos, attrs = make_inputs()
    sim = Simulation(dict(interior=(8, 8), cap=24), make_behavior(), dt=0.1)
    sim.init(pos, attrs, seed=0)
    sim.run(1)
    with jax.transfer_guard_device_to_host("disallow"):
        sim.run(3)
        sim.run(2, fused=False)
