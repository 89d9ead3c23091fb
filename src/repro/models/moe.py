"""Mixture-of-Experts block: shared experts + routed top-k experts.

The router scores every one of ``num_experts`` experts (float32 softmax,
top-k, renormalised only where ``norm_topk_prob``); a layer holds
``MoEConfig.held`` of them, ``[expert_offset, expert_offset + held)``.

Implementations:

- held-expert layer (one device): computes its held experts' part of the
  result for the (token, expert) pairs routed to them, with no capacity
  and no drops.  Pairs routed elsewhere add nothing: on one chip of an
  expert-parallel deployment they are the other chips' part.  Prefill
  groups the pairs by expert (``_moe_grouped``, a ragged matmul over the
  held experts); a decode step, a few tokens, runs every held expert on
  every token (``_moe_dense``), which reads each expert's weights once.
  Holding every expert, it is the whole layer.
- ``ep``:     shard_map expert-parallel training path.  Router runs in
  plain SPMD; dispatch/compute/combine run per-device with static capacity
  buffers; partial outputs are summed with a ``psum`` over the model axis.
  Works with expert-sharded weights when ``E % model == 0`` (deepseek) and
  falls back to ff-sharded weights otherwise (granite's 40 experts on a
  16-way axis).  Token dropping follows the standard static-capacity
  discipline (capacity_factor in the config); dropped tokens fall through
  on the residual.  It holds every expert.

Named scopes: ``router``, ``experts`` and ``shared``.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh
from repro.models.param import ParamDef
from repro.models.layers import mlp_defs, mlp_fwd

Array = jax.Array


def moe_defs(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    m = cfg.moe
    E, f = m.held, m.d_ff_expert
    defs = {
        "router": ParamDef((d, m.num_experts), ("d_model", "experts"),
                           scale=0.02),
        "w_gate": ParamDef((E, d, f), ("experts", "d_model", "expert_ff")),
        "w_up": ParamDef((E, d, f), ("experts", "d_model", "expert_ff")),
        "w_down": ParamDef((E, f, d), ("experts", "expert_ff", "d_model")),
    }
    if m.num_shared:
        defs["shared"] = mlp_defs(d, m.num_shared * f)
    return defs


def _route(p: Dict, x: Array, cfg: ModelConfig) -> Tuple[Array, Array, Array]:
    """Router in fp32: returns (topw (T,k), topi (T,k), aux_loss scalar)."""
    m = cfg.moe
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)
    if m.norm_topk_prob:
        topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balancing aux loss
    E = m.num_experts
    dispatch = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    f_e = dispatch.mean(0)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return topw, topi.astype(jnp.int32), aux


def _held_choices(topi: Array, cfg: ModelConfig) -> Array:
    """(T, k, held) bool: choice j of token t is held expert e."""
    m = cfg.moe
    return (topi - m.expert_offset)[..., None] == jnp.arange(m.held)


def routed_counts(topi: Array, cfg: ModelConfig) -> Array:
    """(held,) int32: the tokens routed to each held expert."""
    return _held_choices(topi, cfg).sum((0, 1), dtype=jnp.int32)


# ---------------------------------------------------------------------------
# held-expert layer (one device)
# ---------------------------------------------------------------------------


def _moe_dense(p: Dict, x: Array, topw: Array, topi: Array, cfg: ModelConfig
               ) -> Array:
    """(T, d) tokens; every held expert on every token, combined with the
    routed weights (zero where not routed)."""
    g = jnp.einsum("td,edf->tef", x, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])   # (T, held, d)
    w_held = jnp.where(_held_choices(topi, cfg), topw[..., None], 0).sum(1)
    return jnp.einsum("ted,te->td", y_all, w_held.astype(x.dtype))


def _moe_grouped(p: Dict, x: Array, topw: Array, topi: Array,
                 cfg: ModelConfig) -> Array:
    """(T, d) tokens; each held expert on the tokens routed to it: the
    (token, choice) pairs sorted by held expert, one ragged matmul over
    the groups, and each pair's output weighted back onto its token.
    Pairs routed elsewhere sort last, outside every group."""
    m = cfg.moe
    T, k = topi.shape
    local = topi.reshape(-1) - m.expert_offset
    held = (local >= 0) & (local < m.held)
    order = jnp.argsort(jnp.where(held, local, m.held), stable=True)
    sizes = jnp.bincount(jnp.where(held, local, m.held),
                         length=m.held + 1)[:m.held].astype(jnp.int32)
    xs = x[order // k]
    g = jax.lax.ragged_dot(xs, p["w_gate"], sizes)
    u = jax.lax.ragged_dot(xs, p["w_up"], sizes)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y = jax.lax.ragged_dot(h, p["w_down"], sizes).astype(jnp.float32)
    y = jnp.where(held[order, None], y * topw.reshape(-1)[order, None], 0.0)
    return y[jnp.argsort(order)].reshape(T, k, -1).sum(1).astype(x.dtype)


# ---------------------------------------------------------------------------
# static-capacity dispatch/combine (per-device, local shapes)
# ---------------------------------------------------------------------------


def _dispatch(x: Array, topi: Array, capacity: int, n_buckets: int,
              bucket_offset: int = 0) -> Tuple[Array, Array, Array, Array]:
    """Scatter tokens into (n_buckets, capacity, d) by expert choice.

    Only choices with bucket id in [bucket_offset, bucket_offset+n_buckets)
    participate; everything else lands in trash rows/slots that get sliced
    off.  Returns (buf, eid, slot, valid) where eid/slot/valid are per-choice
    (T*k,) in the ORIGINAL choice order (for combine).
    """
    T, k = topi.shape
    d = x.shape[-1]
    flat = topi.reshape(-1) - bucket_offset
    inside = (flat >= 0) & (flat < n_buckets)
    eid = jnp.where(inside, flat, n_buckets)             # trash bucket id
    order = jnp.argsort(eid, stable=True)
    sorted_e = eid[order]
    counts = jnp.bincount(eid, length=n_buckets + 1)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(T * k) - starts[sorted_e]
    slot_sorted = jnp.where((pos < capacity) & (sorted_e < n_buckets),
                            pos, capacity)               # trash slot
    buf = jnp.zeros((n_buckets + 1, capacity + 1, d), x.dtype)
    buf = buf.at[sorted_e, slot_sorted].set(x[order // k])
    # per-choice mapping back in original order
    inv = jnp.zeros_like(order).at[order].set(jnp.arange(T * k))
    slot = slot_sorted[inv]
    valid = (slot < capacity) & inside
    return buf[:n_buckets, :capacity], eid, slot, valid


def _combine(y_buf: Array, eid: Array, slot: Array, valid: Array,
             topw: Array) -> Array:
    """Gather per-choice outputs and sum weighted over k."""
    T, k = topw.shape
    n_buckets, capacity, d = y_buf.shape
    e = jnp.minimum(eid, n_buckets - 1)
    s = jnp.minimum(slot, capacity - 1)
    y = y_buf[e, s] * valid[:, None].astype(y_buf.dtype)
    y = y.reshape(T, k, d) * topw[..., None].astype(y_buf.dtype)
    return y.sum(axis=1)


def _expert_ffn(buf: Array, w_gate: Array, w_up: Array, w_down: Array) -> Array:
    """(E_loc, C, d) x per-expert weights -> (E_loc, C, d)."""
    g = jnp.einsum("ecd,edf->ecf", buf, w_gate)
    u = jnp.einsum("ecd,edf->ecf", buf, w_up)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(buf.dtype) * u
    return jnp.einsum("ecf,efd->ecd", h, w_down)


# ---------------------------------------------------------------------------
# shard_map expert-parallel path
# ---------------------------------------------------------------------------


def _moe_ep(p: Dict, x: Array, topw: Array, topi: Array, cfg: ModelConfig,
            mesh) -> Array:
    """Expert-parallel MoE via shard_map + psum over the model axis."""
    from jax.sharding import PartitionSpec as P

    m = cfg.moe
    E = m.num_experts
    M = mesh.shape.get("model", 1)
    batch_axes = tuple(n for n in mesh.axis_names if n != "model")
    expert_sharded = (E % M == 0) and M > 1
    E_loc = E // M if expert_sharded else E
    T = x.shape[0]
    n_batch_shards = 1
    for n in batch_axes:
        n_batch_shards *= mesh.shape[n]
    T_loc = max(T // max(n_batch_shards, 1), 1)
    capacity = max(int(T_loc * m.top_k / E * m.capacity_factor) + 1, 4)

    bd = P(batch_axes if len(batch_axes) > 1 else batch_axes[0])
    w_spec = P("model") if expert_sharded else P(None, None, "model")
    w_down_spec = P("model") if expert_sharded else P(None, "model", None)

    def body(x_l, topw_l, topi_l, wg, wu, wd):
        if expert_sharded:
            ridx = jax.lax.axis_index("model")
            offset = ridx * E_loc
        else:
            offset = 0
        buf, eid, slot, valid = _dispatch(x_l, topi_l, capacity, E_loc, offset)
        y_buf = _expert_ffn(buf, wg, wu, wd)
        y = _combine(y_buf, eid, slot, valid, topw_l)
        return jax.lax.psum(y, "model")

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bd[0], None), P(bd[0], None), P(bd[0], None),
                  w_spec, w_spec, w_down_spec),
        out_specs=P(bd[0], None),
    )(x, topw, topi, p["w_gate"], p["w_up"], p["w_down"])


def _experts(p: Dict, xt: Array, cfg: ModelConfig, every_token: bool
             ) -> Tuple[Array, Array, Array]:
    """Routed part of the layer on (T, d) tokens: (y, topi, aux_loss)."""
    with jax.named_scope("router"):
        topw, topi, aux = _route(p, xt, cfg)
    with jax.named_scope("experts"):
        mesh = current_mesh()
        if mesh is not None and "model" in mesh.axis_names:
            if cfg.moe.held != cfg.moe.num_experts:
                raise NotImplementedError("the expert-parallel path holds "
                                          "every expert")
            y = _moe_ep(p, xt, topw, topi, cfg, mesh)
        elif every_token:
            y = _moe_dense(p, xt, topw, topi, cfg)
        else:
            y = _moe_grouped(p, xt, topw, topi, cfg)
    return y, topi, aux


def _with_shared(p: Dict, x: Array, y: Array, cfg: ModelConfig) -> Array:
    y = y.reshape(x.shape)
    if cfg.moe.num_shared:
        with jax.named_scope("shared"):
            y = y + mlp_fwd(p["shared"], x)
    return y


def moe_fwd(p: Dict, x: Array, cfg: ModelConfig, *,
            every_token: bool = False) -> Tuple[Array, Array]:
    """Full MoE layer on (B, S, d).  Returns (y, aux_loss).
    ``every_token`` (decode) runs every held expert on every token."""
    y, _, aux = _experts(p, x.reshape(-1, x.shape[-1]), cfg, every_token)
    y = _with_shared(p, x, y, cfg)
    return constrain(y, "batch", "seq", "d_model"), aux


def moe_decode(p: Dict, x: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """One decode step's MoE layer on (B, S, d), as ``moe_fwd`` with
    ``every_token``: (y, the tokens routed to each held expert (held,))."""
    y, topi, _ = _experts(p, x.reshape(-1, x.shape[-1]), cfg, True)
    return _with_shared(p, x, y, cfg), routed_counts(topi, cfg)
