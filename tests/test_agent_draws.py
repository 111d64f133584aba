"""Random draws that belong to the agent.  The epidemiology behavior draws
each agent's walk step and its two uniforms from a key of the engine's
root key, the step and the agent's global id alone, so the epidemic does
not depend on the slot an agent sits in: one chip and a 2x2 mesh, cell
capacity 28 and 32, and any input order of the agents give bit-identical
positions and states.  Compositions with slot-keyed behaviors keep their
per-device draws."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.core import compose
from repro.sims import cell_proliferation, epidemiology, sir_mechanics

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

RUN = """
import json, sys
import numpy as np
from repro.sims import epidemiology
from repro.sims.common import make_sim

CELLS, N, STEPS = 16, 6 * 16 * 16, 10


def population(seed):
    rng = np.random.default_rng(seed)
    pos = rng.uniform(0.0, 2.0 * CELLS, (N, 2)).astype(np.float32)
    pos = np.minimum(pos, np.nextafter(np.float32(2.0 * CELLS),
                                       np.float32(0)))
    st = np.where(rng.random(N) < 0.05, epidemiology.I,
                  epidemiology.S).astype(np.int32)
    return pos, {"state": st, "gid_rank": np.zeros(N, np.int32),
                 "gid_count": np.arange(N, dtype=np.int32)}


def epidemic(mesh, cap, order, seed=7):
    pos, attrs = population(seed)
    sim = make_sim(epidemiology.behavior(),
                   interior=(CELLS // mesh, CELLS // mesh),
                   mesh_shape=(mesh, mesh), cap=cap, boundary="toroidal",
                   dt=1.0, delta="off", guards="error")
    sim.init(pos[order], {k: v[order] for k, v in attrs.items()},
             seed=seed)
    sim.run(STEPS)
    v = np.asarray(sim.state.soa.valid).ravel()
    ids = np.asarray(sim.state.soa.attrs["gid_count"]).ravel()[v]
    got_pos = np.asarray(sim.state.soa.attrs["pos"]).reshape(-1, 2)[v]
    got_st = np.asarray(sim.state.soa.attrs["state"]).ravel()[v]
    at = np.argsort(ids)
    assert (ids[at] == np.arange(N)).all()
    return got_pos[at], got_st[at]


runs = {name: epidemic(*args) for name, args in {
    "1x1 cap 28": (1, 28, np.arange(N)),
    "1x1 cap 32": (1, 32, np.arange(N)),
    "1x1 permuted": (1, 28, np.random.default_rng(1).permutation(N)),
    "2x2 cap 28": (2, 28, np.arange(N)),
    "2x2 permuted": (2, 28, np.random.default_rng(2).permutation(N)),
}.items()}
base_pos, base_st = runs["1x1 cap 28"]
out = {name: {"pos": bool(np.array_equal(p.view(np.uint32),
                                         base_pos.view(np.uint32))),
              "state": bool(np.array_equal(s, base_st))}
       for name, (p, s) in runs.items()}
out["moved"] = int((base_st != epidemiology.S).sum())
print(json.dumps(out))
"""


def test_one_chip_a_mesh_any_cap_and_any_order_give_one_epidemic():
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"), JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", textwrap.dedent(RUN)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert p.returncode == 0, p.stderr[-4000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    assert got.pop("moved") > 0
    assert all(v["pos"] and v["state"] for v in got.values()), got


def test_the_root_key_is_the_same_for_every_number_of_devices():
    """The engine's root is device 0's key of split(PRNGKey(seed), n):
    the counter-based threefry split makes it one key for every n."""
    k = jax.random.PRNGKey(20261019)
    first = [np.asarray(jax.random.split(k, n)[0]) for n in (1, 2, 4, 8)]
    assert all((f == first[0]).all() for f in first)


def test_with_state_replays_the_same_epidemic():
    sim = epidemiology.simulation(n_agents=600, initial_infected=30, seed=3)
    s0 = sim.state
    sim.run(5)
    first = np.asarray(sim.state.soa.attrs["pos"]), \
        np.asarray(sim.state.soa.attrs["state"])
    sim.with_state(sim.engine, s0)
    sim.run(5)
    np.testing.assert_array_equal(np.asarray(sim.state.soa.attrs["pos"]),
                                  first[0])
    np.testing.assert_array_equal(np.asarray(sim.state.soa.attrs["state"]),
                                  first[1])


@pytest.mark.parametrize("draws, agent_keys", [("agent", True),
                                                ("slot", False)])
def test_epidemiology_declares_its_draws(draws, agent_keys):
    assert epidemiology.behavior(draws=draws).agent_keys is agent_keys
    with pytest.raises(ValueError):
        epidemiology.behavior(draws="cell")


def test_a_composition_takes_agent_keys_only_where_every_part_does():
    epi = epidemiology.behavior()
    assert compose(epi).agent_keys
    assert not compose(cell_proliferation.behavior(), epi).agent_keys
    # sir_mechanics keeps its slot-keyed draws
    assert not sir_mechanics.behavior().agent_keys
    assert all(not b.agent_keys for b in sir_mechanics.behavior().children)


def test_the_per_agent_draws_are_jax_random_of_each_agent_key():
    """``per_agent_keys``, ``split_agent_keys``, ``agent_normal`` and
    ``agent_uniform`` work on one uint32 array per key word; each equals,
    bit for bit, what ``jax.random`` draws from that agent's own key."""
    from repro.core.behaviors import (
        agent_normal, agent_uniform, per_agent_keys, split_agent_keys,
    )

    root = jax.random.fold_in(jax.random.PRNGKey(123), 4)
    rng = np.random.default_rng(0)
    rank = jax.numpy.asarray(rng.integers(0, 5, (7, 9)), "int32")
    count = jax.numpy.asarray(rng.integers(0, 2 ** 31 - 1, (7, 9)), "int32")
    words = per_agent_keys(root, {"gid_rank": rank, "gid_count": count})
    keys = jax.vmap(lambda r, c: jax.random.fold_in(
        jax.random.fold_in(root, r), c))(rank.ravel(), count.ravel())
    np.testing.assert_array_equal(
        np.stack([np.asarray(w).ravel() for w in words], 1), keys)
    halves = jax.vmap(jax.random.split)(keys)
    z = np.asarray(agent_normal(split_agent_keys(words, 0), 2))
    u = np.asarray(agent_uniform(split_agent_keys(words, 1), 3))
    z_ref = jax.vmap(lambda k: jax.random.normal(k, (2,)))(halves[:, 0])
    u_ref = jax.vmap(lambda k: jax.random.uniform(k, (3,)))(halves[:, 1])
    np.testing.assert_array_equal(z.reshape(-1, 2).view(np.uint32),
                                  np.asarray(z_ref).view(np.uint32))
    np.testing.assert_array_equal(u.reshape(-1, 3).view(np.uint32),
                                  np.asarray(u_ref).view(np.uint32))


def test_the_draws_are_named_under_the_update_scope():
    sim = epidemiology.simulation(n_agents=200, initial_infected=10, seed=1)
    prog = sim.engine.make_segment_runner(None).programs[True]
    text = prog.lower(sim.state, np.int32(1)).as_text(debug_info=True)
    assert "sim.update/sim.update.draws/" in text
