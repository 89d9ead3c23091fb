"""Compressed cross-replica gradient reduction (int8 ring emulation).

Used for the data-parallel all-reduce of LoRA-adapter gradients (the
training mode this paper cares about): adapters are small, but at 1000+
concurrent fine-tunes the aggregate DP traffic matters, and int8 is
standard practice (1-bit Adam / PowerSGD lineage — we implement the simple
deterministic int8 variant).

``compressed_psum`` must run inside shard_map with `axis_name` bound.  The
wire format is int8 chunks moved with all_to_all (reduce-scatter phase) and
all_gather (broadcast phase): 4x less traffic than fp32 psum, ~1e-2 relative
error (bounded by 2/127 per hop).
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp


Array = jax.Array


def _quant(x: Array, scale: Array) -> Array:
    return jnp.clip(jnp.round(x / jnp.maximum(scale, 1e-30) * 127.0),
                    -127, 127).astype(jnp.int8)


def _dequant(q: Array, scale: Array) -> Array:
    return q.astype(jnp.float32) * scale / 127.0


def compressed_psum(x: Array, axis_name: str) -> Array:
    """int8 reduce-scatter + all-gather emulation of psum over axis_name."""
    g = jax.lax.axis_size(axis_name)
    if g == 1:
        return x
    shape = x.shape
    flat = x.reshape(-1).astype(jnp.float32)
    pad = (-flat.size) % g
    flat = jnp.pad(flat, (0, pad))
    chunks = flat.reshape(g, -1)
    # phase 1: shared scale (pmax keeps quantization consistent across peers)
    scale = jax.lax.pmax(jnp.max(jnp.abs(flat)), axis_name)
    q = _quant(chunks, scale)                              # (g, n/g) int8
    # reduce-scatter: everyone sends chunk j to peer j
    recv = jax.lax.all_to_all(q, axis_name, split_axis=0, concat_axis=0,
                              tiled=False)                 # (g, n/g) int8
    part = jnp.sum(_dequant(recv, scale), axis=0)          # my reduced chunk
    # broadcast phase: requantize the reduced chunk and all-gather
    scale2 = jax.lax.pmax(jnp.max(jnp.abs(part)), axis_name)
    q2 = _quant(part, scale2)
    full = jax.lax.all_gather(q2, axis_name, axis=0, tiled=False)  # (g, n/g)
    out = _dequant(full.reshape(-1), scale2)
    if pad:
        out = out[:-pad]
    return out.reshape(shape).astype(x.dtype)


def compressed_psum_tree(tree: Any, axis_name: str) -> Any:
    return jax.tree.map(lambda x: compressed_psum(x, axis_name), tree)


def make_compressed_dp_allreduce(mesh, axes=("pod", "data")):
    """shard_map wrapper reducing a (replicated-over-dp) gradient tree with
    int8 traffic.  Grads enter sharded over their natural spec; we reduce
    over the dp axes only."""
    from jax.sharding import PartitionSpec as P
    names = tuple(a for a in axes if a in mesh.shape)
    if not names:
        return lambda tree: tree

    def reducer(tree):
        def body(t):
            out = t
            for a in names:
                out = jax.tree.map(
                    lambda x: compressed_psum(x, a) / jax.lax.axis_size(a),
                    out)
            return out

        return jax.shard_map(body, mesh=mesh,
                             in_specs=P(*names),
                             out_specs=P(*names))(tree)

    return reducer
