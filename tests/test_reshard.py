"""Re-shard runtime tests: occupancy extraction, planner gains, mid-run
mass migration, and the elastic ABM restore path.

Sharded-mesh cases run in subprocesses (XLA placeholder devices must be
configured before jax initializes), same pattern as test_distributed_abm.
"""

import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    AgentSchema, Behavior, Engine, Domain, Rebalancer, total_agents,
)
from repro.core.behaviors import displacement_update, soft_repulsion_adhesion
from repro.core.load_balance import equal_split_loads, imbalance
from repro.core.reshard import (
    current_imbalance,
    flatten_state,
    occupancy_histogram,
    plan_reshard,
    reshard_state,
)
from repro.distributed import checkpoint as ck
from repro.distributed.elastic import elastic_restore_abm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCHEMA = AgentSchema.create({
    "diameter": ((), jnp.float32),
    "ctype": ((), jnp.int32),
})


def make_behavior():
    return Behavior(
        schema=SCHEMA, pair_fn=soft_repulsion_adhesion,
        pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
        radius=2.0,
        params={"repulsion": 2.0, "adhesion": 0.4, "same_type_only": 1.0,
                "max_step": 0.5})


def clustered_positions(rng, n, domain, centers, sigma=3.0):
    c = np.asarray(centers)[rng.integers(0, len(centers), n)]
    pos = c + rng.normal(0.0, sigma, (n, 2))
    return np.clip(pos, 0.5, domain - 0.5).astype(np.float32)


def make_skewed_state(mesh_shape=(2, 2), n=400, cap=32, seed=0):
    """Gaussian-clustered density: two diagonal clusters on a 32x32 domain —
    pathological for the static 2x2 equal split, near-perfect for a 1-D
    4-way split."""
    gx = gy = 16
    geom = Domain(cell_size=2.0,
                    interior=(gx // mesh_shape[0], gy // mesh_shape[1]),
                    mesh_shape=mesh_shape, cap=cap)
    eng = Engine(geom=geom, behavior=make_behavior(), dt=0.1)
    rng = np.random.default_rng(seed)
    pos = clustered_positions(rng, n, 32.0, [(8.0, 8.0), (24.0, 24.0)])
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return eng, eng.init_state(pos, attrs, seed=seed)


def gid_set(state):
    v = np.asarray(state.soa.valid).ravel()
    r = np.asarray(state.soa.attrs["gid_rank"]).ravel()[v]
    c = np.asarray(state.soa.attrs["gid_count"]).ravel()[v]
    return set(zip(r.tolist(), c.tolist()))


# ---------------------------------------------------------------------------
# occupancy histogram
# ---------------------------------------------------------------------------

def test_occupancy_histogram_counts_interior_agents_exactly():
    eng, state = make_skewed_state()
    hist = occupancy_histogram(eng.geom, state)
    assert hist.shape == eng.geom.box_grid
    assert hist.sum() == total_agents(state)
    loads = equal_split_loads(hist, eng.geom.mesh_shape)
    # diagonal clusters: the two off-diagonal quadrants are near-empty
    assert loads.min() < 0.05 * loads.max()


def test_occupancy_histogram_excludes_aura_copies():
    """After a step the halo ring holds neighbor copies; the histogram must
    still sum to the live agent count."""
    eng, state = make_skewed_state(mesh_shape=(1, 1))
    step = eng.make_local_step()
    state = step(state, full_halo=True)
    hist = occupancy_histogram(eng.geom, state)
    assert hist.sum() == total_agents(state)


def test_occupancy_histogram_runtime_weighting():
    eng, state = make_skewed_state()
    n = total_agents(state)
    base = occupancy_histogram(eng.geom, state)
    rt = np.asarray([[10.0, 1.0], [1.0, 1.0]])
    weighted = occupancy_histogram(eng.geom, state, runtimes=rt)
    assert weighted.sum() == pytest.approx(n)
    bx, by = eng.geom.box_grid
    per_agent_00 = (weighted[:bx // 2, :by // 2].sum()
                    / base[:bx // 2, :by // 2].sum())
    per_agent_11 = (weighted[bx // 2:, by // 2:].sum()
                    / base[bx // 2:, by // 2:].sum())
    # device (0,0) measured 10x slower -> its boxes weigh ~10x more per agent
    assert per_agent_00 / per_agent_11 == pytest.approx(10.0, rel=0.3)


# ---------------------------------------------------------------------------
# planning
# ---------------------------------------------------------------------------

def test_plan_reshard_reduces_imbalance_on_skewed_density():
    eng, state = make_skewed_state()
    hist = occupancy_histogram(eng.geom, state)
    plan = plan_reshard(hist, eng.geom)
    assert plan.current > 1.0
    assert plan.imbalance * 2 <= plan.current
    assert plan.mesh_shape != eng.geom.mesh_shape
    # box-granular RCB bound is also a strict improvement on the static split
    assert plan.rcb_bound is not None and plan.rcb_bound < plan.current


def test_plan_reshard_reports_diffusive_bound_on_1d_mesh():
    """One diffusive step over a heavily end-loaded 1-D chain must move
    load toward balance (it is iterative, so near-balanced densities may
    oscillate — that is the planner's documented behavior, not a bug)."""
    gx = gy = 16
    geom = Domain(cell_size=2.0, interior=(4, 16), mesh_shape=(4, 1),
                    cap=48)
    eng = Engine(geom=geom, behavior=make_behavior(), dt=0.1)
    rng = np.random.default_rng(0)
    n = 400
    pos = clustered_positions(rng, n, 32.0, [(4.0, 16.0)], sigma=3.0)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    state = eng.init_state(pos, attrs)
    hist = occupancy_histogram(eng.geom, state)
    plan = plan_reshard(hist, eng.geom)
    assert plan.diffusive_bound is not None
    assert plan.diffusive_bound < plan.current


# ---------------------------------------------------------------------------
# mass migration (host path; mesh-sharded execution covered below)
# ---------------------------------------------------------------------------

def test_reshard_preserves_agents_gids_iteration_and_drop_count():
    eng, state = make_skewed_state()
    state.dropped = state.dropped.at[1, 1].add(jnp.int32(3))
    gids_before = gid_set(state)
    n = total_agents(state)
    eng2, state2 = reshard_state(eng, state, (1, 4))
    assert eng2.geom.mesh_shape == (1, 4)
    assert eng2.geom.interior == (16, 4)
    assert total_agents(state2) == n
    assert gid_set(state2) == gids_before
    assert int(np.asarray(state2.dropped).sum()) == 3
    assert int(np.max(np.asarray(state2.it))) == int(
        np.max(np.asarray(state.it)))


def test_reshard_spawn_counters_never_reissue_gids():
    """Per-rank counters after a re-shard must exceed every carried id of
    that rank, so post-reshard spawns cannot collide."""
    eng, state = make_skewed_state()
    eng2, state2 = reshard_state(eng, state, (4, 1))
    counters = np.asarray(state2.gid_counter).ravel()
    v = np.asarray(state2.soa.valid).ravel()
    ranks = np.asarray(state2.soa.attrs["gid_rank"]).ravel()[v]
    counts = np.asarray(state2.soa.attrs["gid_count"]).ravel()[v]
    for r in range(counters.size):
        mine = counts[ranks == r]
        if mine.size:
            assert counters[r] > mine.max()


def test_gid_floors_survive_mesh_downsize():
    """Counters are exact issuance trackers: restoring onto a smaller mesh
    must keep every new rank's counter above the *global* floor bound, so
    ids issued by dropped ranks (even to since-dead agents) are never
    reissued after a later re-expansion."""
    geom = Domain(cell_size=2.0, interior=(8, 16), mesh_shape=(2, 1),
                    cap=32)
    eng = Engine(geom=geom, behavior=make_behavior(), dt=0.1)
    rng = np.random.default_rng(0)
    n = 20
    pos = rng.uniform(0.5, 31.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32),
             "gid_rank": np.zeros(n, np.int32),
             "gid_count": np.arange(n, dtype=np.int32)}
    # floors from a previous 4-rank mesh; rank 3 issued up to id 38
    state = eng.init_state(pos, attrs,
                           gid_counters=np.asarray([5, 5, 5, 39]))
    assert (np.asarray(state.gid_counter) >= 39).all()


def test_rebalancer_acceptance_two_x_reduction_and_conservation():
    """Acceptance demo: Gaussian-clustered density on a 2x2 mesh — the
    Rebalancer must cut imbalance() by >= 2x vs the static equal split and
    conserve the agent population."""
    eng, state = make_skewed_state(mesh_shape=(2, 2))
    n = total_agents(state)
    before = current_imbalance(eng.geom, state)
    rb = Rebalancer(every=1, threshold=0.2)
    eng2, state2, resharded = rb.maybe_reshard(eng, state)
    assert resharded
    after = current_imbalance(eng2.geom, state2)
    assert after * 2 <= before
    assert total_agents(state2) == n
    rec = rb.history[-1]
    assert rec["applied"] and rec["mesh_to"] == eng2.geom.mesh_shape


def test_rebalancer_declines_below_threshold_and_without_gain():
    # uniform density: already balanced -> below threshold, no re-shard
    gx = gy = 16
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
    eng = Engine(geom=geom, behavior=make_behavior(), dt=0.1)
    rng = np.random.default_rng(1)
    n = 400
    pos = rng.uniform(0.5, 31.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    state = eng.init_state(pos, attrs)
    rb = Rebalancer(every=1, threshold=0.5)
    eng2, state2, resharded = rb.maybe_reshard(eng, state)
    assert not resharded and eng2 is eng
    assert rb.history[-1]["applied"] is False
    # skewed but no realizable gain (threshold 0 + huge min_gain) -> declined
    eng, state = make_skewed_state()
    rb = Rebalancer(every=1, threshold=0.0, min_gain=1e9)
    _, _, resharded = rb.maybe_reshard(eng, state)
    assert not resharded


def test_reshard_transport_validation():
    eng, state = make_skewed_state()
    with pytest.raises(ValueError, match="transport"):
        reshard_state(eng, state, (1, 4), transport="carrier-pigeon")
    # explicit device transport without enough real devices must refuse
    # loudly, never silently fall back to the host round trip
    with pytest.raises(ValueError, match="use the host path"):
        reshard_state(eng, state, (1, 4), transport="device")


def test_reshard_auto_transport_falls_back_to_host_when_unrealizable():
    """auto on a single real device (this test process) must take the host
    path and still produce the full re-shard result."""
    eng, state = make_skewed_state()
    gids = gid_set(state)
    eng2, state2 = reshard_state(eng, state, (4, 1), transport="auto")
    assert eng2.geom.mesh_shape == (4, 1)
    assert gid_set(state2) == gids


def test_flatten_state_roundtrip_single_device():
    eng, state = make_skewed_state(mesh_shape=(1, 1))
    flat = flatten_state(eng.geom, state)
    assert flat.positions.shape == (total_agents(state), 2)
    eng2, state2 = reshard_state(eng, state, (1, 1))
    p1 = np.sort(flat.positions, axis=0)
    flat2 = flatten_state(eng2.geom, state2)
    np.testing.assert_array_equal(p1, np.sort(flat2.positions, axis=0))


# ---------------------------------------------------------------------------
# elastic ABM restore
# ---------------------------------------------------------------------------

def test_elastic_abm_restore_onto_different_device_count(tmp_path):
    eng, state = make_skewed_state(mesh_shape=(1, 1))
    step = eng.make_local_step()
    for _ in range(3):
        state = step(state, full_halo=True)
    n = total_agents(state)
    ck.save_abm(str(tmp_path), 3, eng, state)

    eng4, state4, step_ = elastic_restore_abm(str(tmp_path),
                                              make_behavior(), n_devices=4)
    assert step_ == 3
    assert int(np.prod(eng4.geom.mesh_shape)) == 4
    assert total_agents(state4) == n
    assert gid_set(state4) == gid_set(state)
    assert int(np.max(np.asarray(state4.it))) == 3
    # the chosen mesh beats the naive 2x2 equal split on this density
    hist = occupancy_histogram(eng4.geom, state4)
    assert imbalance(equal_split_loads(hist, eng4.geom.mesh_shape)) <= \
        imbalance(equal_split_loads(hist, (2, 2)))

    # degraded, non-power-of-two survivor counts factorize too
    eng3, state3, _ = elastic_restore_abm(str(tmp_path),
                                          make_behavior(), n_devices=2)
    assert int(np.prod(eng3.geom.mesh_shape)) == 2
    assert total_agents(state3) == n


# ---------------------------------------------------------------------------
# sharded execution across a mid-run re-shard (subprocess: needs devices)
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


def test_mid_run_reshard_matches_single_device_oracle():
    """A distributed sim re-sharded mid-run conserves the population and
    tracks the single-device oracle's positions."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import AgentSchema, Behavior, Engine, Domain, Rebalancer, total_agents
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.core.reshard import current_imbalance
from repro.launch.mesh import make_abm_mesh

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
eng1 = Engine(geom=geom1, behavior=beh, dt=0.1)
s1 = eng1.init_state(pos, attrs, seed=0)
step1 = eng1.make_local_step()
for _ in range(10):
    s1 = step1(s1, full_halo=True)

# distributed on the pathological 2x2 split, re-shard allowed at step 5
geom4 = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
eng4 = Engine(geom=geom4, behavior=beh, dt=0.1)
s4 = eng4.init_state(pos, attrs, seed=0)
before = current_imbalance(eng4.geom, s4)
rb = Rebalancer(every=5, threshold=0.3)
step4 = eng4.make_sharded_step(make_abm_mesh((2, 2)))
eng_out, s4, _ = eng4.drive(s4, 10, step_fn=step4, rebalancer=rb)
assert any(r["applied"] for r in rb.history), rb.history
assert eng_out.geom.mesh_shape != (2, 2)
after = current_imbalance(eng_out.geom, s4)
assert total_agents(s4) == n, "agent loss across re-shard"
# Tolerance: a 2x2 split cannot be bit-exact with one device.  Migrants
# re-bin after a cell's resident agents on their new device, where one
# device orders a cell's slots by their old cell, so each agent's pair
# sums add in another order.  Those ulp differences grow through the
# soft-sphere dynamics (and flip adhesion/repulsion at overlap == 0):
# 3.0e-4 after these 10 steps with jax 0.9.0 on CPU.  1e-3 bounds that,
# and is 1/1000 of the agent diameter.
err = np.max(np.abs(sorted_positions(s1) - sorted_positions(s4)))
assert err < 1e-3, f"divergence {err}"
assert after * 2 <= before, (before, after)
print("OK", before, "->", after, "err", err)
""")
    assert "OK" in out


def test_device_reshard_bit_exact_vs_host_and_zero_host_bytes():
    """The device-to-device transport must reproduce the host path
    bit-for-bit (slots, carry, RNG lineage) on fresh AND stepped states,
    for equal-split and uneven-partition targets — and must never call
    ``flatten_state`` (no agent bytes through host)."""
    out = run_sub("""
import numpy as np, jax.numpy as jnp
import repro.core.reshard as rs
from repro.core import AgentSchema, Behavior, Engine, Domain
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.core.reshard import (occupancy_histogram, plan_reshard,
                                reshard_state)

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.4,
                                   "same_type_only": 1.0, "max_step": 0.5})

def make(seed=0):
    geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(seed)
    n = 400
    c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
    pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
    attrs = {"diameter": np.full((n,), 1.0, np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    return eng, eng.init_state(pos, attrs, seed=seed)

for stepped in (False, True):
    for target in ("equal", "partition"):
        eng, st = make()
        if stepped:
            eng, st, _ = eng.drive(st, 3)
        if target == "equal":
            kw = dict(mesh_shape=(4, 1))
        else:
            plan = plan_reshard(occupancy_histogram(eng.geom, st), eng.geom)
            kw = dict(partition=plan.partition)
        eh, sh = reshard_state(eng, st, transport="host", **kw)

        orig, calls = rs.flatten_state, []
        rs.flatten_state = lambda *a, **k: calls.append(1)
        try:
            ed, sd = reshard_state(eng, st, transport="device", **kw)
        finally:
            rs.flatten_state = orig
        assert not calls, "device path touched flatten_state"
        assert eh.geom == ed.geom
        np.testing.assert_array_equal(np.asarray(sh.soa.valid),
                                      np.asarray(sd.soa.valid))
        for name in sh.soa.attrs:
            np.testing.assert_array_equal(np.asarray(sh.soa.attrs[name]),
                                          np.asarray(sd.soa.attrs[name]),
                                          err_msg=name)
        for f in ("it", "key", "gid_counter", "dropped"):
            np.testing.assert_array_equal(np.asarray(getattr(sh, f)),
                                          np.asarray(getattr(sd, f)),
                                          err_msg=f)
        print("bit-exact", "stepped" if stepped else "fresh", target)
print("OK")
""")
    assert "OK" in out


def test_deferred_rebalance_overlaps_plan_with_device_migration():
    """defer=True: the snapshot tick returns without re-sharding (the old
    mesh keeps stepping), the decision lands one step later, applied
    migrations ride the device transport, and the population is
    conserved."""
    out = run_sub("""
import numpy as np, jax.numpy as jnp
import repro.core.reshard as rs
from repro.core import AgentSchema, Behavior, Engine, Domain, Rebalancer, total_agents
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update

schema = AgentSchema.create({"diameter": ((), jnp.float32),
                             "ctype": ((), jnp.int32)})
beh = Behavior(schema=schema, pair_fn=soft_repulsion_adhesion,
               pair_attrs=("diameter", "ctype"), update_fn=displacement_update,
               radius=2.0, params={"repulsion": 2.0, "adhesion": 0.4,
                                   "same_type_only": 1.0, "max_step": 0.5})
geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
eng = Engine(geom=geom, behavior=beh, dt=0.1)
rng = np.random.default_rng(0)
n = 400
c = np.asarray([(8.0, 8.0), (24.0, 24.0)])[rng.integers(0, 2, n)]
pos = np.clip(c + rng.normal(0, 3.0, (n, 2)), 0.5, 31.5).astype(np.float32)
attrs = {"diameter": np.full((n,), 1.0, np.float32),
         "ctype": rng.integers(0, 2, n).astype(np.int32)}
st = eng.init_state(pos, attrs, seed=0)

orig, calls = rs.flatten_state, []
rs.flatten_state = lambda *a, **k: calls.append(1)
try:
    rb = Rebalancer(every=4, threshold=0.2, min_gain=1.05,
                    ownership="rcb", defer=True)
    e2, s2, _ = eng.drive(st, 12, rebalancer=rb)
finally:
    rs.flatten_state = orig
applied = [h for h in rb.history if h["applied"]]
assert applied, rb.history
# phase 2 lands one step after the every=4 snapshot ticks
assert all(h["it"] % 4 == 1 for h in rb.history), rb.history
assert all(h.get("deferred") for h in rb.history)
assert all(h["transport"] == "device" for h in applied)
assert not calls, "deferred device migration touched flatten_state"
assert total_agents(s2) + int(np.sum(np.asarray(s2.dropped))) == n
print("OK")
""")
    assert "OK" in out


def test_mid_run_reshard_with_delta_encoding_forces_full_refresh():
    """Re-shard zeroes the delta references; the driver must force a full
    aura refresh so the run stays bounded-drift."""
    out = run_sub("""
import numpy as np, jax, jax.numpy as jnp
from repro.core import (AgentSchema, Behavior, DeltaConfig, Engine, Domain,
                        Rebalancer, total_agents)
from repro.core.behaviors import soft_repulsion_adhesion, displacement_update
from repro.launch.mesh import make_abm_mesh

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

geom = Domain(cell_size=2.0, interior=(8, 8), mesh_shape=(2, 2), cap=32)
cfg = DeltaConfig(enabled=True, qdtype=jnp.int16, refresh_interval=8)
eng = Engine(geom=geom, behavior=beh, delta_cfg=cfg, dt=0.1)
s = eng.init_state(pos, attrs, seed=0)
rb = Rebalancer(every=3, threshold=0.3)
step = eng.make_sharded_step(make_abm_mesh((2, 2)))
eng_out, s, _ = eng.drive(s, 9, step_fn=step, rebalancer=rb)
assert any(r["applied"] for r in rb.history)
assert total_agents(s) == n
pos_f = np.asarray(s.soa.attrs["pos"]).reshape(-1, 2)[
    np.asarray(s.soa.valid).ravel()]
assert np.isfinite(pos_f).all()
print("OK")
""")
    assert "OK" in out
