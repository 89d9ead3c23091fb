"""Mixture-of-Experts block: shared experts + routed top-k experts.

Implementations:

- ``dense``:  every expert processes every token, masked combine.  Exact
  oracle; used for CPU smoke tests and as the correctness reference for the
  distributed paths (tiny configs only — compute is O(E) per token).
- ``ep``:     shard_map expert-parallel production path.  Router runs in
  plain SPMD; dispatch/compute/combine run per-device with static capacity
  buffers; partial outputs are summed with a ``psum`` over the model axis.
  Works with expert-sharded weights when ``E % model == 0`` (deepseek) and
  falls back to ff-sharded weights otherwise (granite's 40 experts on a
  16-way axis).  An all-to-all variant is a recorded §Perf hillclimb.

Token dropping follows the standard static-capacity discipline
(capacity_factor in the config); dropped tokens fall through on the residual.
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
    E, f = m.num_experts, m.d_ff_expert
    defs = {
        "router": ParamDef((d, E), ("d_model", "experts"), scale=0.02),
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
    topw = topw / jnp.maximum(topw.sum(-1, keepdims=True), 1e-9)
    # switch-style load-balancing aux loss
    E = m.num_experts
    dispatch = jax.nn.one_hot(topi[:, 0], E, dtype=jnp.float32)
    f_e = dispatch.mean(0)
    p_e = probs.mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return topw, topi.astype(jnp.int32), aux


# ---------------------------------------------------------------------------
# dense oracle
# ---------------------------------------------------------------------------


def _moe_dense(p: Dict, x: Array, topw: Array, topi: Array, cfg: ModelConfig
               ) -> Array:
    """(T, d) tokens; computes every expert then combines.  Oracle only."""
    m = cfg.moe
    E = m.num_experts
    g = jnp.einsum("td,edf->tef", x, p["w_gate"])
    u = jnp.einsum("td,edf->tef", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    y_all = jnp.einsum("tef,efd->ted", h, p["w_down"])   # (T, E, d)
    w_full = jnp.zeros((x.shape[0], E), x.dtype)
    w_full = w_full.at[jnp.arange(x.shape[0])[:, None], topi].set(
        topw.astype(x.dtype))
    return jnp.einsum("ted,te->td", y_all, w_full)


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


def moe_fwd(p: Dict, x: Array, cfg: ModelConfig) -> Tuple[Array, Array]:
    """Full MoE layer on (B, S, d).  Returns (y, aux_loss)."""
    B, S, d = x.shape
    m = cfg.moe
    xt = x.reshape(B * S, d)
    topw, topi, aux = _route(p, xt, cfg)
    mesh = current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        y = _moe_ep(p, xt, topw, topi, cfg, mesh)
    else:
        y = _moe_dense(p, xt, topw, topi, cfg)
    y = y.reshape(B, S, d)
    if m.num_shared:
        y = y + mlp_fwd(p["shared"], x)
    return constrain(y, "batch", "seq", "d_model"), aux
