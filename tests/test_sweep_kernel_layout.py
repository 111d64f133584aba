"""The Pallas sweep kernel's arithmetic layout.

Vector-valued pair math runs inside the kernel as one lane-dense (1, K, NK)
plane per component: no value carries a trailing component axis, which
Mosaic would pad to the 128-lane axis.  These tests read the kernel body's
jaxpr (``jax.make_jaxpr`` of the sweep; nothing runs) for every bundled
behavior, pin the re-stacking fallback against the reference sweep, and
pin that a pair function with scalar results traces to no larger a body
than before the split evaluator.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import Behavior, Domain, Engine
from repro.core.behaviors import displacement_update
from repro.core.neighbors import sweep_accumulate
from repro.kernels import neighbor_interaction
from repro.sims import (
    cell_clustering, cell_proliferation, epidemiology, oncology,
    sir_mechanics, tumor_spheroid,
)

BEHAVIORS = {
    "cell_clustering": cell_clustering.behavior(),
    "cell_proliferation": cell_proliferation.behavior(),
    "epidemiology": epidemiology.behavior(),
    "oncology": oncology.behavior(),
    "sir_mechanics": sir_mechanics.behavior(),
    "tumor_spheroid": tumor_spheroid.behavior(),
}
DIMS = pytest.mark.parametrize("ndim,k", [(2, 24), (3, 16)],
                               ids=["2d", "3d"])
CELLS = 8


def columns(beh, ndim, width):
    specs = beh.schema.all_specs(ndim)
    names = set(beh.pair_attrs) | {"pos", "gid_rank", "gid_count"}
    return {n: jax.ShapeDtypeStruct((CELLS, width) + tuple(specs[n][0]),
                                    specs[n][1]) for n in names}


def sub_jaxprs(value):
    if hasattr(value, "eqns"):
        yield value
    elif hasattr(value, "jaxpr") and hasattr(value.jaxpr, "eqns"):
        yield value.jaxpr
    elif isinstance(value, (list, tuple)):
        for v in value:
            yield from sub_jaxprs(v)


def equations(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in sub_jaxprs(value):
                yield from equations(sub)


def kernel_body(beh, ndim, k):
    """The body jaxpr of the sweep's one ``pallas_call``."""
    nk = 3 ** ndim * k

    def sweep(ai, aj, vi, vj):
        return neighbor_interaction.pair_sweep_kernel(
            ai, aj, vi, vj, pair_fn=beh.pair_fn,
            radius=beh.radius, params=beh.params, block_cells=CELLS)

    jaxpr = jax.make_jaxpr(sweep)(
        columns(beh, ndim, k), columns(beh, ndim, nk),
        jax.ShapeDtypeStruct((CELLS, k), jnp.bool_),
        jax.ShapeDtypeStruct((CELLS, nk), jnp.bool_))
    calls = [e for e in equations(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 1
    assert calls[0].params["name"] == "sim_sweep_pairs"
    return calls[0].params["jaxpr"]


@pytest.mark.parametrize("name", sorted(BEHAVIORS))
@DIMS
def test_kernel_holds_no_pair_block_vector_temporaries(name, ndim, k):
    """No value inside the kernel has rank 4: the pair block is (1, K, NK)
    and a (1, K, NK, D) value would pad D to 128 lanes.  Every equation of
    the bundled pair functions has a per-component rule."""
    beh = BEHAVIORS[name]
    body = kernel_body(beh, ndim, k)
    wide = [(e.primitive.name, tuple(v.aval.shape))
            for e in equations(body) for v in e.outvars
            if len(getattr(v.aval, "shape", ())) >= 4]
    assert not wide, wide
    nk = 3 ** ndim * k
    split, restacked = neighbor_interaction.split_counts(
        beh.pair_fn, columns(beh, ndim, k), columns(beh, ndim, nk),
        beh.params)
    assert restacked == 0
    assert (split > 0) == (name != "epidemiology")


# The kernel body of epidemiology's pair function, equations counted with
# those of nested jaxprs, before the split evaluator (stacked ``disp`` and
# vector columns): 69 at 2-D K 24, 83 at 3-D K 16.
SCALAR_BODY_BEFORE = {2: 69, 3: 83}


@DIMS
def test_scalar_pair_function_body_is_not_larger(ndim, k):
    """A pair function with only scalar results gets no split work: its
    kernel body is no larger than the stacked one was, and no equation is
    split or re-stacked."""
    beh = BEHAVIORS["epidemiology"]
    body = kernel_body(beh, ndim, k)
    assert len(list(equations(body))) <= SCALAR_BODY_BEFORE[ndim]
    nk = 3 ** ndim * k
    assert neighbor_interaction.split_counts(
        beh.pair_fn, columns(beh, ndim, k), columns(beh, ndim, nk),
        beh.params) == (0, 0)


def _unsplittable_pair(ai, aj, disp, dist2, params):
    """Soft repulsion along ``disp`` times a constant (D, D) matrix (a
    ``dot_general``, which has no per-component rule), scaled by
    ``jnp.linalg.norm`` over the component axis, plus a count.  The matrix
    is built in the trace: a kernel body may not capture an array."""
    mix = 0.9 * jnp.eye(disp.shape[-1], dtype=disp.dtype) + 0.2
    dist = jnp.linalg.norm(disp, axis=-1)
    push = jnp.maximum(1.5 - dist, 0.0) * ai["diameter"] * aj["diameter"]
    return {"force": -(disp @ mix) * push[..., None],
            "count": jnp.ones_like(dist2)}


def test_restacked_fallback_matches_reference():
    """Primitives without a per-component rule get re-stacked operands and
    run as written: the kernel agrees with the reference sweep to the
    kernel tolerance of the other parity tests."""
    beh = Behavior(schema=cell_clustering.SCHEMA,
                   pair_fn=_unsplittable_pair, pair_attrs=("diameter",),
                   update_fn=displacement_update, radius=2.0,
                   params={"max_step": 0.5})
    geom = Domain(cell_size=2.0, interior=(6, 6), mesh_shape=(1, 1),
                  cap=16)
    eng = Engine(geom=geom, behavior=beh, dt=0.1)
    rng = np.random.default_rng(4)
    lx, ly = geom.domain_size
    n = 260
    pos = rng.uniform(0.5, lx - 0.5, (n, 2)).astype(np.float32)
    attrs = {"diameter": rng.uniform(0.6, 1.4, n).astype(np.float32),
             "ctype": rng.integers(0, 2, n).astype(np.int32)}
    state = eng.init_state(pos, attrs, seed=0)

    def run(backend):
        return jax.jit(lambda soa: sweep_accumulate(
            geom, soa, beh.pair_fn, beh.pair_attrs, beh.radius,
            beh.params, backend=backend))(state.soa)

    want, got = run("reference"), run("pallas")
    assert set(got) == set(want)
    assert float(np.abs(np.asarray(want["force"])).max()) > 0
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]),
                                   atol=1e-5, rtol=1e-5, err_msg=name)

    nk = 9 * geom.cap
    split, restacked = neighbor_interaction.split_counts(
        beh.pair_fn, columns(beh, 2, geom.cap), columns(beh, 2, nk),
        beh.params)
    assert restacked > 0 and split > 0
