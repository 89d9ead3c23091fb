"""Fused compressed-LoRA (JD) forward kernel.

The paper's serving insight (App. D) in MXU terms: `U Sigma_i V^T x` needs
per-adapter state only in the tiny Sigma stage; `V^T x` and `U(.)` are dense
matmuls shared by all tokens of a cluster.  This kernel fuses the shrink
matmul with the per-token diagonal-Sigma scale (JD-Diag) so the (T, r)
intermediate never round-trips HBM; JD-Full uses `sgmv.sigma_bmm` between the
two dense stages instead.

Tokens are grouped by *cluster* (k clusters, each with its own V/U), with
per-token sigma rows pre-gathered into (T, r) — that gather is tiny and
stays outside the kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sgmv import _interpret, _pick_block, sgmv_expand, sigma_bmm

Array = jax.Array


def _shrink_scale_kernel(cids_ref, x_ref, v_ref, sig_ref, o_ref):
    """o[tile, r] = (x[tile, :] @ V[cluster]) * sigma_tok[tile, r].

    Accumulates over d blocks; applies the per-token scale on the last one.
    """
    j = pl.program_id(1)
    nd = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jnp.dot(x_ref[...], v_ref[0],
                          preferred_element_type=jnp.float32)

    @pl.when(j == nd - 1)
    def _scale():
        o_ref[...] = o_ref[...] * sig_ref[...].astype(jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_d", "interpret"))
def jd_shrink_scale(x: Array, V: Array, sigma_tok: Array, tile_cids: Array, *,
                    block_t: int = 128, block_d: int = 512,
                    interpret: bool | None = None) -> Array:
    """x: (T_pad, d_in); V: (k, d_in, r); sigma_tok: (T_pad, r) pre-gathered
    diag sigmas; tile_cids: (T_pad/block_t,) cluster per tile -> (T_pad, r)."""
    T, d_in = x.shape
    k, _, r = V.shape
    bt = _pick_block(T, block_t)
    bd = _pick_block(d_in, block_d)
    grid = (T // bt, d_in // bd)
    return pl.pallas_call(
        _shrink_scale_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bd), lambda i, j, ids: (i, j)),
                pl.BlockSpec((1, bd, r), lambda i, j, ids: (ids[i], j, 0)),
                pl.BlockSpec((bt, r), lambda i, j, ids: (i, 0)),
            ],
            out_specs=pl.BlockSpec((bt, r), lambda i, j, ids: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, r), jnp.float32),
        interpret=_interpret(interpret),
    )(tile_cids, x, V, sigma_tok)


def jd_apply(x: Array, U: Array, V: Array, sigma: Array, cluster_of: Array,
             ids: Array, tile_cids: Array, tile_ids: Array, *,
             block_t: int = 128, block_d: int = 512,
             interpret: bool | None = None) -> Array:
    """Full compressed delta for grouped tokens.

    JD-Diag: fused shrink+scale, then expand with cluster U.
    JD-Full: shrink (scale=1), sigma_bmm by adapter tiles, then expand.
    Tokens must be grouped so each tile has one adapter (and hence one
    cluster — adapters of a tile share their cluster by construction).
    """
    T = x.shape[0]
    r = V.shape[-1]
    assert T % tile_cids.shape[0] == 0
    bt = T // tile_cids.shape[0]          # tile size fixed by the grouping
    assert block_t % bt == 0 or bt <= block_t
    if sigma.ndim == 2:  # diagonal
        sig_tok = sigma[ids].astype(x.dtype)            # (T, r) tiny gather
        t = jd_shrink_scale(x, V, sig_tok, tile_cids, block_t=bt,
                            block_d=block_d, interpret=interpret)
    else:
        ones = jnp.ones((T, r), x.dtype)
        t = jd_shrink_scale(x, V, ones, tile_cids, block_t=bt,
                            block_d=block_d, interpret=interpret)
        t = sigma_bmm(t.astype(x.dtype), sigma, tile_ids, block_t=bt,
                      interpret=interpret)
    # expand with per-cluster U: same SGMV pattern with cluster ids
    return sgmv_expand(t.astype(x.dtype), U, tile_cids, block_t=bt,
                       block_d=block_d, interpret=interpret)
