"""Composable agent behaviors — the user-facing modeling API.

Mirrors the paper's three-step model structure (§1): define what an agent is
(an AgentSchema), define its behaviors (a Behavior: a pair-interaction kernel
plus a pointwise update), and define the initial condition (an initializer).
The same Behavior runs unchanged on one device or on a multi-pod mesh —
the paper's "seamless transition from a laptop to a supercomputer" (§3.4).

Behaviors form a composition algebra (BioDynaMo attaches a *list* of
behaviors to each agent): :func:`compose` merges several behaviors into one
— schemas are unioned, every pair kernel runs over the same neighborhood
gather (each gated to its own radius), accumulator names are namespaced per
sub-behavior, and the pointwise updates chain in order, each seeing the
previous one's attribute writes.  ``compose(b)`` of a single behavior is
bit-exact with ``b`` itself, which is the property the parity tests pin.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend.random import threefry2x32_p

from repro.core.agent_soa import AgentSchema, GID_COUNT, GID_RANK, POS
from repro.core.neighbors import PairFn

Array = jax.Array

# update(attrs, valid, acc, key, params, dt) ->
#   (new_attrs, alive_mask, spawn_mask, child_attrs_or_None)
UpdateFn = Callable[..., Tuple[Dict[str, Array], Array, Array,
                               Optional[Dict[str, Array]]]]


# eq=False: a Behavior hashes/compares by identity (its pair_fn/update_fn
# closures and params dict have no structural equality), which makes the
# enclosing frozen Engine hashable — the key for the compiled step-function
# caches in core.engine.
@dataclasses.dataclass(frozen=True, eq=False)
class Behavior:
    """A full agent behavior: local interaction + pointwise update."""

    schema: AgentSchema
    pair_fn: PairFn                      # neighbor contribution kernel
    pair_attrs: Tuple[str, ...]          # attrs the pair kernel reads
    update_fn: UpdateFn                  # pointwise state transition
    radius: float                        # max interaction distance
    params: dict = dataclasses.field(default_factory=dict)
    can_spawn: bool = False              # statically enables the spawn path
    acc_spec: Dict[str, Tuple[Tuple[int, ...], object]] = dataclasses.field(
        default_factory=dict
    )
    # Declared worst-case per-step displacement (world units at dt=1) for
    # the one-hop migration contract (analysis.contracts).  When None the
    # checker infers a bound from recognized params (max_step, sigma,
    # div_offset); declare it for custom kinematics the inference can't
    # see.  Purely advisory metadata — the engine never reads it.
    max_displacement: Optional[float] = None
    # Sub-behaviors this one was composed from (empty for leaves).  The
    # contract checker and hot-path lint walk this to analyze leaf kernels
    # instead of the synthesized compose() wrappers.
    children: Tuple["Behavior", ...] = ()
    # Which key the engine hands update_fn.  False: a per-device step key,
    # ``fold_in(fold_in(device_key, t), rank)``, from which the update
    # draws over the padded slot grid, so a draw follows the slot.  True:
    # ``fold_in(root, t)`` with ``root`` the engine's root key, the same on
    # every device, from which :func:`per_agent_keys` derives one key per
    # agent from its global id: an agent's draws then do not depend on
    # its slot, the cell capacity, the order of agents or the mesh.
    agent_keys: bool = False

    # Behavior.stack(a, b, ...) — alias of compose(); bound as a class
    # attribute after compose() is defined below (not a dataclass field).


def _merge_schemas(behaviors: Tuple[Behavior, ...]) -> AgentSchema:
    spec: Dict[str, Tuple[Tuple[int, ...], object]] = {}
    for b in behaviors:
        for name, shape, dtype in b.schema.fields:
            if name in spec and spec[name] != (shape, dtype):
                raise ValueError(
                    f"compose: attribute {name!r} declared with conflicting "
                    f"specs {spec[name]} vs {(shape, dtype)}")
            spec[name] = (shape, dtype)
    return AgentSchema.create(spec)


def _broadcast_mask(mask: Array, like: Array) -> Array:
    while mask.ndim < like.ndim:
        mask = mask[..., None]
    return mask


def compose(*behaviors: Behavior) -> Behavior:
    """Merge several behaviors into one (BioDynaMo's per-agent behavior list).

    Semantics:
      * **schema** — union of the sub-schemas (conflicting attribute specs
        are an error).
      * **pair kernels** — all run over one neighborhood gather with the
        max radius; a sub-behavior with a smaller radius has its
        contributions gated to its own ``dist2 <= radius**2`` so composition
        never widens an interaction.  Accumulators are namespaced
        ``"b{i}.{name}"`` and un-namespaced before reaching each update.
      * **updates** — chained in order; update ``i`` sees the attribute
        writes of updates ``< i`` (accumulators were all computed from the
        *pre-update* state, exactly as in a monolithic behavior).  Alive
        masks AND together; spawn masks OR together with the later
        behavior's child winning contested slots.  Behavior 0 receives the
        step key unchanged (bit-exact single-behavior parity); behavior
        ``i>0`` receives ``fold_in(key, i)``.  The composition takes
        per-agent keys (``agent_keys``) only where every sub-behavior
        does; otherwise every sub-behavior gets the per-device key, and a
        per-agent one then draws by global id, but per device.
      * **params** — each sub-kernel closes over its own params; the merged
        ``params`` dict (namespaced the same way) is carried for
        introspection only.
    """
    behs = tuple(behaviors)
    if not behs:
        raise ValueError("compose() needs at least one Behavior")
    for b in behs:
        if not isinstance(b, Behavior):
            raise TypeError(f"compose() takes Behaviors, got {type(b)!r}")

    schema = _merge_schemas(behs)
    radius = max(float(b.radius) for b in behs)
    pair_attrs = tuple(sorted({a for b in behs for a in b.pair_attrs}))
    can_spawn = any(b.can_spawn for b in behs)
    params = {f"b{i}.{k}": v
              for i, b in enumerate(behs) for k, v in b.params.items()}
    acc_spec = {f"b{i}.{k}": v
                for i, b in enumerate(behs) for k, v in b.acc_spec.items()}

    def pair(attrs_i, attrs_j, disp, dist2, _params):
        out: Dict[str, Array] = {}
        for i, b in enumerate(behs):
            sub = b.pair_fn(attrs_i, attrs_j, disp, dist2, b.params)
            gate = None
            if float(b.radius) < radius:
                gate = dist2 <= jnp.float32(float(b.radius) ** 2)
            for k, v in sub.items():
                if gate is not None:
                    v = jnp.where(_broadcast_mask(gate, v), v,
                                  jnp.zeros_like(v))
                out[f"b{i}.{k}"] = v
        return out

    def update(attrs, valid, acc, key, _params, dt):
        cur = dict(attrs)
        alive = valid
        spawn = jnp.zeros_like(valid)
        child: Optional[Dict[str, Array]] = None
        for i, b in enumerate(behs):
            pfx = f"b{i}."
            acc_i = {k[len(pfx):]: v for k, v in acc.items()
                     if k.startswith(pfx)}
            ki = key if i == 0 else jax.random.fold_in(key, i)
            cur, alive_i, spawn_i, child_i = b.update_fn(
                cur, valid, acc_i, ki, b.params, dt)
            cur = dict(cur)
            alive = alive & alive_i
            if b.can_spawn and child_i is not None:
                # complete the child to the union schema: attributes the
                # spawning behavior doesn't know about are inherited from
                # the parent's current state (the `child = dict(new)` idiom)
                child_i = {**cur, **child_i}
                if child is None:
                    child, spawn = child_i, spawn_i
                else:
                    child = {k: jnp.where(
                        _broadcast_mask(spawn_i, child_i[k]),
                        child_i[k], child[k]) for k in child}
                    spawn = spawn | spawn_i
        return cur, alive, spawn, child

    return Behavior(
        schema=schema, pair_fn=pair, pair_attrs=pair_attrs,
        update_fn=update, radius=radius, params=params,
        can_spawn=can_spawn, acc_spec=acc_spec, children=behs,
        agent_keys=all(b.agent_keys for b in behs))


Behavior.stack = staticmethod(compose)


# Per-agent draws, on key words of the slot grid's shape.  Each is the
# draw ``jax.random`` makes from one agent's key, bit for bit (the
# counter-based threefry split; tests/test_agent_draws.py pins them), but
# computed on one uint32 array per key word: a batched key array of shape
# (..., 2) would put its two words on the chip's lane axis.

def _block(words, data):
    """The threefry-2x32 block of key ``words`` on the counter
    ``(0, data)``: the key ``fold_in(key, data)``, which is also
    ``split(key)[data]``."""
    k0, k1 = words
    d = jnp.broadcast_to(jnp.asarray(data).astype(jnp.uint32), k0.shape)
    return tuple(threefry2x32_p.bind(k0, k1, jnp.zeros_like(d), d))


def per_agent_keys(key: Array, attrs: Dict[str, Array]) -> Tuple[Array, Array]:
    """Each agent slot's key ``fold_in(fold_in(key, gid_rank), gid_count)``
    as two uint32 word arrays of the slot grid's shape.  With the step key
    of an ``agent_keys`` behavior an agent's draws in step ``t`` are a
    function of the engine's root key, ``t`` and its global id alone.
    A behavior traces its draws under the ``sim.update.draws`` scope."""
    rank = attrs[GID_RANK]
    words = tuple(jnp.broadcast_to(key[i], rank.shape) for i in (0, 1))
    return _block(_block(words, rank), attrs[GID_COUNT])


def split_agent_keys(words, i: int) -> Tuple[Array, Array]:
    """``split(key)[i]`` of every agent's key."""
    return _block(words, i)


def _bits(words, n: int) -> Array:
    """``random_bits(key, 32, (n,))`` of every agent's key: shape
    ``words[0].shape + (n,)``."""
    k0, k1 = (jnp.broadcast_to(w[..., None], w.shape + (n,)) for w in words)
    y0, y1 = _block((k0, k1), jax.lax.broadcasted_iota(jnp.int32, k0.shape,
                                                       k0.ndim - 1))
    return y0 ^ y1


def agent_uniform(words, n: int, minval=0.0, maxval=1.0) -> Array:
    """``uniform(key, (n,), float32, minval, maxval)`` of every agent's
    key: shape ``words[0].shape + (n,)``."""
    bits = _bits(words, n) >> jnp.uint32(9) | jnp.uint32(0x3F800000)
    floats = jax.lax.bitcast_convert_type(bits, jnp.float32) \
        - jnp.float32(1.0)
    lo, hi = jnp.float32(minval), jnp.float32(maxval)
    return jnp.maximum(lo, floats * (hi - lo) + lo)


def agent_normal(words, n: int) -> Array:
    """``normal(key, (n,), float32)`` of every agent's key: shape
    ``words[0].shape + (n,)``."""
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = agent_uniform(words, n, lo, 1.0)
    return jnp.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


# ---------------------------------------------------------------------------
# Standard mechanical interactions shared by the biology-flavoured sims.
# ---------------------------------------------------------------------------

def soft_repulsion_adhesion(attrs_i, attrs_j, disp, dist2, params):
    """BioDynaMo-style mechanical force: short-range soft-sphere repulsion plus
    type-aware adhesion within the interaction radius.

    Expects attrs to carry ``diameter`` (float) and ``ctype`` (int32).
    ``params``: repulsion, adhesion, same_type_only (0/1).
    """
    eps = jnp.float32(1e-6)
    dist = jnp.sqrt(dist2 + eps)
    unit = disp / dist[..., None]
    r_sum = 0.5 * (attrs_i["diameter"] + attrs_j["diameter"])
    overlap = r_sum - dist
    rep = jnp.where(overlap > 0, params["repulsion"] * overlap, 0.0)
    same = (attrs_i["ctype"] == attrs_j["ctype"]).astype(jnp.float32)
    gate = jnp.where(
        jnp.float32(params.get("same_type_only", 1.0)) > 0, same, 1.0
    )
    adh = jnp.where(overlap <= 0, params["adhesion"] * gate, 0.0)
    force = (rep - adh)[..., None] * unit  # + pushes apart, - pulls together
    return {"force": -force}  # force ON i points from j towards i


def displacement_update(attrs, valid, acc, key, params, dt):
    """Overdamped dynamics: dx = F * dt, speed-clamped to < one NSG cell."""
    f = acc["force"]
    max_step = jnp.float32(params["max_step"])
    norm = jnp.sqrt(jnp.sum(f * f, axis=-1, keepdims=True) + 1e-12)
    step = f * jnp.minimum(max_step / norm, dt)
    new = dict(attrs)
    new[POS] = attrs[POS] + jnp.where(valid[..., None], step, 0.0)
    alive = valid
    spawn = jnp.zeros_like(valid)
    return new, alive, spawn, None
