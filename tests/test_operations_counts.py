"""``operations.attr_counts``: one compiled reduction over every listed
value, one transfer to the host a call, and the counts the per-value sums
gave, under the device scope ``sim.op.<name>`` of the scheduled
operation."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src import array as jax_array

from repro.core import operations
from repro.sims import epidemiology


def _per_value_sums(sim, attr, values):
    """The reducer as it was: one eager sum and one host read a value."""
    soa = sim.state.soa
    a = soa.attrs[attr]
    return tuple(int(jnp.sum((a == v) & soa.valid)) for v in values)


@pytest.fixture
def sim():
    s = epidemiology.simulation(n_agents=300, initial_infected=40,
                                interior=(8, 8), seed=5)
    s.run(6)
    return s


def test_counts_equal_the_per_value_sums(sim):
    vals = (epidemiology.S, epidemiology.I, epidemiology.R, 7)
    op = operations.attr_counts("state", vals)
    got = op(sim)
    assert got == _per_value_sums(sim, "state", vals)
    assert sum(got) == 300 and got[-1] == 0
    assert all(isinstance(c, int) for c in got)


def test_one_call_makes_one_host_transfer(sim, monkeypatch):
    op = operations.attr_counts("state", (0, 1, 2))
    op(sim)                                   # compile outside the count
    reads = []
    value = jax_array.ArrayImpl._value

    def counted(self):
        reads.append(self.shape)
        return value.fget(self)

    monkeypatch.setattr(jax_array.ArrayImpl, "_value", property(counted))
    op(sim)
    assert reads == [(3,)]
    reads.clear()
    _per_value_sums(sim, "state", (0, 1, 2))
    assert len(reads) == 3                    # what the old reducer did


def test_the_reduction_runs_under_the_scheduled_operation_scope():
    prog = operations._counts_program((0, 1, 2), "sim.op.sir")
    text = prog.lower(jnp.zeros((8, 8, 4), jnp.int32),
                      jnp.ones((8, 8, 4), bool)).as_text(debug_info=True)
    assert "sim.op.sir/" in text
    with operations.running("sir"):
        assert operations._RUNNING.get() == "sir"
    assert operations._RUNNING.get() is None


def test_the_scheduled_reducer_records_the_series(monkeypatch):
    scopes = []
    real = operations._counts_program

    def seen(values, scope):
        scopes.append(scope)
        return real(values, scope)

    monkeypatch.setattr(operations, "_counts_program", seen)
    sim = epidemiology.simulation(n_agents=200, initial_infected=20,
                                  interior=(8, 8), seed=2)
    sim.run(3)
    assert scopes == ["sim.op.sir"] * 3
    ser = np.asarray(sim.series["sir"])
    assert ser.shape == (3, 3) and (ser.sum(axis=1) == 200).all()
    assert jax.device_get(sim.state.it).max() == 3
