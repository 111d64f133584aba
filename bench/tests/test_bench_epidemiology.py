"""The epidemiology cell's comparison on a 32x32 torus on the CPU, three
steps checked: the program matches its plain reference within the cell's
limits, with agents planted where one step puts them just below 0 on the
torus seam; the bfloat16 control and each planted fault (the old seam
wrap, draws keyed by slot, the ring fill left out) fail a limit."""

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import calibrate
from benchlib import compare, run, spec, traffic
from conftest import make_root

CELL = "epidemiology.walk-512"
SEED = 20260201
PLANTED = 16


@pytest.fixture
def root(tmp_path):
    return make_root(str(tmp_path), cells=32, check_steps=3)


def _seam_population(real, cell):
    """The drawn population, with PLANTED agents moved to where their own
    first step takes them just below 0 on the first axis: the sliver that
    a plain ``mod`` rounds up to ``L``."""
    ref = cell.reference

    def draw(config, mix, seed):
        pos, attrs = real(config, mix, seed)
        L = np.float32(traffic.domain_size(config, mix)[0])
        root = jax.random.split(jax.random.PRNGKey(
            traffic.engine_seed(seed)))[0]
        keys = ref._draws(jax.random.fold_in(root, 0),
                          jnp.asarray(attrs["gid_rank"]),
                          jnp.asarray(attrs["gid_count"]))
        z = jax.vmap(lambda k: jax.random.normal(k, (2,)))(keys[:, 0])
        dx = np.asarray(jnp.float32(config["behavior"]["sigma"]) * z)[:, 0]
        pos = pos.copy()
        moved = 0
        for i in np.flatnonzero((dx < -0.5) & (dx > -3.0)):
            x0 = np.float32(-dx[i] - 1e-6)
            lands = np.float32(x0 + dx[i])
            if -np.spacing(L) / 2 < lands < 0 and \
                    float(jnp.mod(jnp.float32(lands), L)) == L:
                pos[i, 0] = x0
                moved += 1
            if moved == PLANTED:
                break
        assert moved == PLANTED
        return pos, attrs

    return draw


def _run(root, monkeypatch):
    cell = spec.load_cell(CELL, root)
    monkeypatch.setattr(traffic, "draw_agents",
                        _seam_population(traffic.draw_agents, cell))
    return run.run(cell, SEED, 0.5, False, t_start=time.perf_counter(),
                   require_chip=False, root=root)


def test_the_sir_program_matches_its_reference(root, fresh_programs,
                                               monkeypatch):
    res = _run(root, monkeypatch)
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 3 and res["failed"] == 0
    assert res["checks"]["pos_mismatch_share"]["value"] == 0.0
    assert res["checks"]["state_mismatch_share"]["value"] == 0.0


def test_the_bfloat16_control_fails_a_limit(root, fresh_programs):
    cell = spec.load_cell(CELL, root)
    cfg, mix = cell.config, cell.traffic
    pos, attrs = traffic.draw_agents(cfg, mix, SEED)
    sim = run.build_sim(cell)
    sim.init(pos, attrs, seed=traffic.engine_seed(SEED))
    kept = [sim.state]
    for _ in range(mix["check_steps"]):
        sim.run(1)
        kept.append(sim.state)
    answers = [compare.answer_of(s, 2, ["state"]) for s in kept]
    ctrl = calibrate.control_numbers(cell, pos, attrs,
                                     traffic.engine_seed(SEED), answers)
    limits = {k: v for k, v in cell.limits["limits"].items() if k in ctrl}
    judged = compare.judge(ctrl, limits)
    assert not all(c["ok"] for c in judged.values()), judged


def _old_seam_wrap(engine, monkeypatch):
    def plain_mod(p, lsz, toroidal):
        return jnp.where(jnp.asarray(toroidal),
                         jnp.mod(p, jnp.asarray(lsz, jnp.float32)), p)

    monkeypatch.setattr(engine, "wrap_torus", plain_mod)


def _slot_draws(engine, monkeypatch):
    from repro.sims import epidemiology

    monkeypatch.setattr(epidemiology, "behavior", functools.partial(
        epidemiology.behavior, draws="slot"))


def _no_exchange(engine, monkeypatch):
    monkeypatch.setattr(engine, "halo_exchange", engine.halo_exchange)
    calibrate.plant_no_exchange()


@pytest.mark.parametrize("plant, caught_by", [
    (_old_seam_wrap, "agents_missing"),
    (_slot_draws, "pos_mismatch_share"),
    (_no_exchange, "state_mismatch_share"),
])
def test_a_planted_fault_fails_a_limit(plant, caught_by, root,
                                       fresh_programs, monkeypatch):
    plant(fresh_programs, monkeypatch)
    res = _run(root, monkeypatch)
    assert not res["correct"], res["checks"]
    assert not res["checks"][caught_by]["ok"], res["checks"]
