"""Mixture-of-Experts FFN with grouped, capacity-bounded token-choice routing.

Dispatch is *grouped* (GShard-style): tokens are grouped by batch row, each
group routes its S tokens independently with per-group capacity
C = ceil(k * S / E * cf).  All gathers/scatters are then batched over the
group dim, which is sharded over the data axes — so GSPMD keeps token
movement local to the data shard and the only cross-device collective is the
expert combine over the "model" (expert-parallel) axis: exactly the
all-to-all-class traffic the paper's byte-minimization insight targets.

Routing semantics: tokens pick top-k experts (normalized weights); each
expert serves at most C tokens per group, selected by router weight
(capacity truncation, overflow dropped — standard Switch/GShard behavior).
"""

from __future__ import annotations

import math
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.distributed.sharding import constrain
from repro.models.params import ParamSpec

Array = jax.Array


def moe_spec(cfg: ArchConfig):
    d = cfg.d_model
    m = cfg.moe
    e, f = m.n_experts, m.expert_d_ff
    return {
        "router": ParamSpec((d, e), ("embed", "experts"), jnp.float32),
        "w_gate": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_up": ParamSpec((e, d, f), ("experts", "embed", "expert_mlp")),
        "w_down": ParamSpec((e, f, d), ("experts", "expert_mlp", "embed")),
    }


def capacity(cfg: ArchConfig, group_tokens: int) -> int:
    m = cfg.moe
    c = int(math.ceil(
        m.top_k * group_tokens / m.n_experts * m.capacity_factor))
    return max(1, min(max(c, 4), group_tokens))


def moe_apply(params, cfg: ArchConfig, x: Array) -> Tuple[Array, Array]:
    """x: (B, S, D) -> (y, aux_loss).  Groups = batch rows."""
    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32),
                        params["router"])
    probs = jax.nn.softmax(logits, axis=-1)                    # (B, S, E)
    topk_p, topk_i = jax.lax.top_k(probs, k)                   # (B, S, k)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, -1, keepdims=True), 1e-9)
    gate = jnp.zeros((b, s, e), jnp.float32)
    gate = gate.at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], topk_i
    ].set(topk_p)                                              # (B, S, E)
    gate = constrain(gate, ("batch", "seq_act", "experts"))

    c = capacity(cfg, s)
    # per group, per expert: top-C tokens by gate weight
    w_ec, idx_ec = jax.lax.top_k(gate.swapaxes(1, 2), c)       # (B, E, C)
    live = (w_ec > 0.0).astype(x.dtype)

    # batched gather within each group: xe[g, e, c] = x[g, idx[g, e, c]]
    xe = jnp.take_along_axis(
        x[:, None, :, :],                                      # (B, 1, S, D)
        idx_ec[..., None],                                     # (B, E, C, 1)
        axis=2,
    )                                                          # (B, E, C, D)
    xe = constrain(xe, ("batch", "experts", "capacity", "embed_act"))

    g = jnp.einsum("becd,edf->becf", xe, params["w_gate"])
    u = jnp.einsum("becd,edf->becf", xe, params["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    ye = jnp.einsum("becf,efd->becd", h, params["w_down"])     # (B, E, C, D)
    ye = constrain(ye, ("batch", "experts", "capacity", "embed_act"))
    ye = ye * (w_ec * live.astype(jnp.float32))[..., None].astype(ye.dtype)

    # batched scatter-add back to token order (combine over experts)
    y = jnp.zeros((b, s, d), ye.dtype)
    y = y.at[
        jnp.arange(b)[:, None, None, None],
        idx_ec[..., None],
        jnp.arange(d)[None, None, None, :],
    ].add(ye)
    y = constrain(y, ("batch", "seq_act", "embed_act"))

    # Switch-style load-balancing auxiliary loss.
    me = jnp.mean(probs, axis=(0, 1))                          # (E,)
    assigned = jnp.zeros((b, s, e), jnp.float32).at[
        jnp.arange(b)[:, None, None], jnp.arange(s)[None, :, None], topk_i
    ].set(1.0)
    fe = jnp.mean(assigned, axis=(0, 1))
    aux = m.router_aux_weight * e * jnp.sum(me * fe)
    return y, aux


# ---------------------------------------------------------------------------
# Expert-parallel dispatch via shard_map + all_to_all (production path)
# ---------------------------------------------------------------------------
#
# GSPMD cannot shard the general scatter in the grouped combine (it
# replicates the full-batch (B, S, D) tensor and all-reduces it per layer —
# measured 17 GB x 94 layers on qwen3).  The production path therefore
# expresses expert parallelism explicitly: tokens are routed locally within
# each data shard, dispatched to expert-owning model shards with a single
# all_to_all, processed, and returned with the inverse all_to_all.  This is
# the minimal-bytes collective schedule (2 x dispatched-token bytes per
# layer) — the paper's "minimize exchanged bytes" insight applied to MoE.

def _moe_shard_body(x, router, w_gate, w_up, w_down, *, cfg: ArchConfig,
                    ep: int, fsdp_axes, model_axis: str):
    """Runs per-device inside shard_map.

    x: (B_loc, S/ep, D) — batch sharded over the data axes AND sequence
    sharded over the model axis, so every device routes a disjoint token
    slice (routing replicated over model would multiply dispatch bytes and
    expert FLOPs by ep — measured 16x on qwen3 before this layout).
    router: (D, E) replicated.  w_*: (E/ep, D, F) local expert blocks.
    """
    import jax

    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    e_loc = e // ep
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, k)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, -1, keepdims=True), 1e-9)
    gate = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], topk_i].set(topk_p)

    c = capacity(cfg, t)
    w_ec, idx_ec = jax.lax.top_k(gate.T, c)            # (E, C) local tokens
    live = w_ec > 0.0
    xe = xt[idx_ec]                                    # (E, C, D) local gather
    xe = xe * live[..., None].astype(xe.dtype)

    # dispatch: (E, C, D) -> (ep, e_loc, C, D) --a2a--> (peer, e_loc, C, D)
    # (all_to_all with split_axis=concat_axis=0 is the self-inverse
    # "transpose over the mesh axis" — verified in tests)
    xa = xe.reshape(ep, e_loc, c, d)
    xa = jax.lax.all_to_all(xa, model_axis, split_axis=0, concat_axis=0)
    xa = xa.transpose(1, 0, 2, 3).reshape(e_loc, ep * c, d)

    g = jnp.einsum("ecd,edf->ecf", xa, w_gate)
    u = jnp.einsum("ecd,edf->ecf", xa, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xa.dtype) * u
    ya = jnp.einsum("ecf,efd->ecd", h, w_down)         # (e_loc, ep*C, D)

    # return: inverse all_to_all -> (E, C, D) back on the owning data shard
    ya = ya.reshape(e_loc, ep, c, d).transpose(1, 0, 2, 3)
    ye = jax.lax.all_to_all(ya, model_axis, split_axis=0, concat_axis=0)
    ye = ye.reshape(e, c, d)
    ye = ye * (w_ec * live.astype(jnp.float32))[..., None].astype(ye.dtype)

    y = jnp.zeros((t, d), ye.dtype).at[idx_ec.reshape(-1)].add(
        ye.reshape(e * c, d))

    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(
        jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], topk_i].set(1.0), axis=0)
    aux = m.router_aux_weight * e * jnp.sum(me * fe)
    aux = jax.lax.pmean(aux, (model_axis,) + tuple(fsdp_axes))
    return y.reshape(b, s, d), aux


def _moe_dense_decode_body(x, router, w_gate, w_up, w_down, *,
                           cfg: ArchConfig, ep: int, model_axis: str,
                           fsdp_axes=()):
    """Tiny-token path (decode): every model shard runs its local experts
    densely over all local tokens and psums the gated partials — cheaper
    than any dispatch when tokens-per-device is O(1)."""
    import jax

    m = cfg.moe
    b, s, d = x.shape
    e, k = m.n_experts, m.top_k
    e_loc = e // ep
    t = b * s
    xt = x.reshape(t, d)

    logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    topk_p, topk_i = jax.lax.top_k(probs, k)
    topk_p = topk_p / jnp.maximum(jnp.sum(topk_p, -1, keepdims=True), 1e-9)
    gate = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], topk_i].set(topk_p)
    eidx = jax.lax.axis_index(model_axis) * e_loc + jnp.arange(e_loc)
    gate_loc = gate[:, eidx]                               # (T, e_loc)

    g = jnp.einsum("td,edf->tef", xt, w_gate)
    u = jnp.einsum("td,edf->tef", xt, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    ye = jnp.einsum("tef,efd->ted", h, w_down)             # (T, e_loc, D)
    y = jnp.einsum("ted,te->td", ye.astype(jnp.float32), gate_loc)
    y = jax.lax.psum(y, model_axis).astype(x.dtype)

    me = jnp.mean(probs, axis=0)
    fe = jnp.mean(
        jnp.zeros((t, e), jnp.float32).at[
            jnp.arange(t)[:, None], topk_i].set(1.0), axis=0)
    aux = m.router_aux_weight * e * jnp.sum(me * fe)
    aux = jax.lax.pmean(aux, (model_axis,) + tuple(fsdp_axes))
    return y.reshape(b, s, d), aux


def moe_apply_ep(params, cfg: ArchConfig, x: Array) -> Tuple[Array, Array]:
    """Expert-parallel MoE via shard_map; requires an active
    activation_sharding context with a mesh that has a 'model' axis dividing
    n_experts.  Falls back to the GSPMD grouped path otherwise."""
    import functools

    import jax
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shlib

    active = getattr(shlib._ACTIVE, "v", None)
    if active is None:
        return moe_apply(params, cfg, x)
    mesh, _ = active
    if "model" not in mesh.shape or cfg.moe.n_experts % mesh.shape["model"]:
        return moe_apply(params, cfg, x)

    ep = mesh.shape["model"]
    fsdp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    b, s, d = x.shape
    # expert weights enter sharded over (experts=model, embed=fsdp); the
    # body receives the fsdp-gathered block (XLA inserts the all-gather at
    # the shard_map boundary, once per layer scan step).
    w_spec = P("model", None, None)

    if s % ep != 0:
        # decode / tiny sequences: dense-local-experts + psum
        body = functools.partial(
            _moe_dense_decode_body, cfg=cfg, ep=ep, model_axis="model",
            fsdp_axes=fsdp_axes)
        spec = P(fsdp_axes, None, None)
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(spec, P(None, None), w_spec, w_spec, w_spec),
            out_specs=(spec, P()), check_vma=False,
        )(x, params["router"], params["w_gate"], params["w_up"],
          params["w_down"])

    body = functools.partial(
        _moe_shard_body, cfg=cfg, ep=ep, fsdp_axes=fsdp_axes,
        model_axis="model")
    # tokens: batch over data axes, sequence over model — disjoint routing
    seq_spec = P(fsdp_axes, "model", None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(seq_spec, P(None, None), w_spec, w_spec, w_spec),
        out_specs=(seq_spec, P()), check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    return out
