"""The torus seam: a position that wraps must land on the half-open
[0, L).  ``jnp.mod(p, L)`` rounds a float32 ``p`` just below 0 up to
exactly ``L``; the agent then bins into the high ring cell, and the next
aura rebuild clears it.  These tests plant agents in that sliver, on every
seam, and check that they survive, that a whole epidemic keeps its
population, and that the guards count the loss the old wrap caused."""

import warnings

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import AgentSchema, Behavior, GUARD_NAMES, POS, total_agents
from repro.core import engine as engine_mod
from repro.core.domain import wrap_torus
from repro.sims import epidemiology
from repro.sims.common import make_sim

L = 1024.0                    # 512 cells of 2.0
CONSERVATION = GUARD_NAMES.index("conservation")

# every agent moves by its own `dx` once, then stays
SCHEMA = AgentSchema.create({"dx": ((2,), jnp.float32)})


def _no_pair(ai, aj, disp, dist2, params):
    return {"n": jnp.zeros_like(dist2)}


def _move_once(attrs, valid, acc, key, params, dt):
    new = dict(attrs)
    new[POS] = attrs[POS] + jnp.where(valid[..., None], attrs["dx"], 0.0)
    new["dx"] = jnp.zeros_like(attrs["dx"])
    return new, valid, jnp.zeros_like(valid), None


MOVE = Behavior(schema=SCHEMA, pair_fn=_no_pair, pair_attrs=(),
                update_fn=_move_once, radius=2.0, max_displacement=1.0)


def _seam_population(axis: int, target: float):
    """Agents that one step puts at ``target`` (below 0, or past L) on
    ``axis`` of a 512-cell axis, plus one that stays inside."""
    start = np.float32(1e-5) if target < 0 else np.float32(L - 1e-3)
    pos = np.full((2, 2), 3.0, np.float32)
    dx = np.zeros((2, 2), np.float32)
    pos[0, axis] = start
    dx[0, axis] = np.float32(target) - start
    pos[1] = (5.0, 5.0)
    return pos, dx


@pytest.mark.parametrize("p", [-1e-6, -1e-5, -3e-5, -3.05e-5, -1e-7,
                               -0.0, L, L + 1e-4, -L - 1e-3, 2 * L - 1e-4])
def test_wrap_lands_on_the_half_open_torus(p):
    x = jnp.asarray([[p, 3.0]], jnp.float32)
    got = np.asarray(wrap_torus(x, (L, 8.0), (True, False)))
    assert 0.0 <= got[0, 0] < L, got
    assert got[0, 1] == np.float32(3.0)      # a closed axis passes through
    # the same point of the torus, to float32 rounding
    d = (float(got[0, 0]) - float(np.float32(p))) % L
    assert min(d, L - d) <= 2 * np.spacing(np.float32(L))


def test_the_plain_mod_rounds_the_sliver_to_L():
    """What the old wrap did: the reason for the fold."""
    for p in (-1e-6, -1e-5, -3e-5):
        assert float(jnp.mod(jnp.float32(p), jnp.float32(L))) == L


@pytest.mark.parametrize("axis", [0, 1])
@pytest.mark.parametrize("target", [-1e-6, -1e-5, L, L + 1e-4])
def test_an_agent_planted_at_a_seam_survives_the_aura_rebuild(axis, target):
    interior = (512, 8) if axis == 0 else (8, 512)
    sim = make_sim(MOVE, interior=interior, cap=8, boundary="toroidal",
                   dt=1.0, guards="error", check="off")
    pos, dx = _seam_population(axis, target)
    sim.init(pos, {"dx": dx}, seed=0)
    sim.run(3)                          # the move, then two aura rebuilds
    assert total_agents(sim.state) == 2
    v = np.asarray(sim.state.soa.valid)
    p = np.asarray(sim.state.soa.attrs[POS])[v]
    assert ((p >= 0.0) & (p < np.asarray(sim.geom.domain_size))).all(), p
    assert int(np.asarray(sim.state.dropped).sum()) == 0


def test_a_loss_at_the_seam_trips_the_conservation_guard(monkeypatch):
    """Planted with the old wrap, the agent rounds to L, bins into the
    ring and is cleared: the guards' conservation word counts it."""
    def old_wrap(p, lsz, toroidal):
        return jnp.where(jnp.asarray(toroidal),
                         jnp.mod(p, jnp.asarray(lsz, jnp.float32)), p)

    for name in ("_cached_segment_runner", "_cached_local_step"):
        getattr(engine_mod, name).cache_clear()
    monkeypatch.setattr(engine_mod, "wrap_torus", old_wrap)
    try:
        sim = make_sim(MOVE, interior=(512, 8), cap=8, boundary="toroidal",
                       dt=1.0, guards="warn", check="off")
        pos, dx = _seam_population(0, -1e-5)
        sim.init(pos, {"dx": dx}, seed=0)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            sim.run(2)
        assert total_agents(sim.state) == 1
        assert int(np.asarray(sim.state.health).reshape(-1, len(GUARD_NAMES))
                   [:, CONSERVATION].max()) == 1
        assert any("conservation=+1" in str(w.message) for w in caught)
    finally:
        for name in ("_cached_segment_runner", "_cached_local_step"):
            getattr(engine_mod, name).cache_clear()


@pytest.mark.parametrize("seed", range(8))
def test_an_epidemic_keeps_its_population_on_a_64x64_torus(seed):
    n = 6 * 64 * 64
    rng = np.random.default_rng(seed)
    sim = make_sim(epidemiology.behavior(), interior=(64, 64), cap=28,
                   boundary="toroidal", dt=1.0, guards="error")
    pos = rng.uniform(0.0, 128.0, (n, 2)).astype(np.float32)
    pos = np.minimum(pos, np.nextafter(np.float32(128.0), np.float32(0)))
    st = np.where(rng.random(n) < 0.05, epidemiology.I,
                  epidemiology.S).astype(np.int32)
    sim.init(pos, {"state": st}, seed=seed)
    sim.run(20)
    assert total_agents(sim.state) == n
    assert int(np.asarray(sim.state.dropped).sum()) == 0
    assert not np.asarray(sim.state.health).any()
