"""SGMV (segmented-gather matrix multiply) Pallas kernels — the TPU
adaptation of Punica's multi-LoRA CUDA kernels (DESIGN.md §2).

Tokens arrive *pre-grouped by adapter* and padded so every token tile maps to
exactly one adapter (``ref.group_tokens_by_adapter``).  The per-tile adapter
id is a scalar-prefetch operand: the BlockSpec index_map reads it to stream
the right adapter block HBM->VMEM, turning per-token weight gathers into a
block-diagonal grouped GEMM that the MXU actually likes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

Array = jax.Array


def _pick_block(dim: int, target: int) -> int:
    """Largest divisor of `dim` that is <= target (keeps BlockSpecs exact)."""
    b = min(dim, target)
    while dim % b:
        b -= 1
    return b


def _interpret(interpret: bool | None) -> bool:
    """Every kernel wrapper's ``interpret`` default (None): the compiled
    kernel on a TPU backend, the Pallas interpreter anywhere else.  An
    explicit bool always wins."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# -- rank-tile cost model (pure; no jax) -------------------------------------
#
# The SGMV kernels contract over the rank axis in hardware tiles: the f32
# minimum TPU tile is 8 sublanes x 128 lanes, so a shrink/expand pass moves
# the rank dimension through the MXU in multiples of the slice's native
# tile width.  A rank-r adapter therefore pays for ceil(r / tile) * tile
# rank lanes — rank 4 on a tile-8 pipeline streams and multiplies 8 lanes,
# half of them zeros.  These two functions surface that padding as a pure
# cost model the router scores replicas with (mirrored jax-free in
# serving/router.py; tests/test_hetero.py asserts the mirror agrees) and
# benchmarks/hetero_placement.py validates against a wall-clock microbench
# of the kernels themselves.  Note the kernels above run interpret=True on
# CPU where padding is invisible — the microbench validates the affine
# rank backbone (time linear in r), and tile_rank=1 reduces both functions
# to the unpadded identity.


def sgmv_tile_cost(rank: int, tile_rank: int = 8) -> int:
    """Rank lanes one SGMV contraction actually occupies: `rank` padded
    up to the next multiple of the hardware's native `tile_rank`."""
    if rank < 1:
        raise ValueError("rank must be >= 1")
    if tile_rank < 1:
        raise ValueError("tile_rank must be >= 1")
    return tile_rank * -(-rank // tile_rank)


def sgmv_rank_efficiency(rank: int, tile_rank: int = 8) -> float:
    """Useful fraction of the occupied rank lanes, in (0, 1]: 1.0 when
    `rank` is a tile multiple, 1/tile_rank at its worst (rank 1 on a wide
    pipeline).  The Fleet's rank-aware routing divides a replica's
    effective throughput by this."""
    return rank / sgmv_tile_cost(rank, tile_rank)


def _shrink_kernel(ids_ref, x_ref, a_ref, o_ref):
    """o[tile, r] += x[tile, d_blk] @ A[id, :, d_blk]^T."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], a_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_d", "interpret"))
def sgmv_shrink(x: Array, A: Array, tile_ids: Array, *,
                block_t: int = 128, block_d: int = 512,
                interpret: bool | None = None) -> Array:
    """x: (T_pad, d_in) grouped tokens; A: (n, r, d_in); tile_ids:
    (T_pad/block_t,) adapter id per tile.  Returns (T_pad, r) fp32."""
    T, d_in = x.shape
    n, r, _ = A.shape
    bt = _pick_block(T, block_t)
    bd = _pick_block(d_in, block_d)
    assert tile_ids.shape[0] == T // bt, (tile_ids.shape, T, bt)
    grid = (T // bt, d_in // bd)
    return pl.pallas_call(
        _shrink_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, bd), lambda i, j, ids: (i, j)),
                pl.BlockSpec((1, r, bd), lambda i, j, ids: (ids[i], 0, j)),
            ],
            out_specs=pl.BlockSpec((bt, r), lambda i, j, ids: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, r), jnp.float32),
        interpret=_interpret(interpret),
    )(tile_ids, x, A)


def _expand_kernel(ids_ref, t_ref, b_ref, o_ref):
    """o[tile, d_blk] = t[tile, r] @ B[id, d_blk, :]^T."""
    o_ref[...] = jax.lax.dot_general(
        t_ref[...], b_ref[0],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("block_t", "block_d", "interpret"))
def sgmv_expand(t: Array, B: Array, tile_ids: Array, *,
                block_t: int = 128, block_d: int = 512,
                interpret: bool | None = None) -> Array:
    """t: (T_pad, r); B: (n, d_out, r); returns (T_pad, d_out) in t.dtype."""
    T, r = t.shape
    n, d_out, _ = B.shape
    bt = _pick_block(T, block_t)
    bd = _pick_block(d_out, block_d)
    assert tile_ids.shape[0] == T // bt, (tile_ids.shape, T, bt)
    grid = (T // bt, d_out // bd)
    return pl.pallas_call(
        _expand_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bt, r), lambda i, j, ids: (i, 0)),
                pl.BlockSpec((1, bd, r), lambda i, j, ids: (ids[i], j, 0)),
            ],
            out_specs=pl.BlockSpec((bt, bd), lambda i, j, ids: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, d_out), t.dtype),
        interpret=_interpret(interpret),
    )(tile_ids, t, B)


def _sigma_bmm_kernel(ids_ref, t_ref, s_ref, o_ref):
    """o[tile, r] = t[tile, r] @ Sigma[id]  (JD-Full middle stage)."""
    o_ref[...] = jnp.dot(t_ref[...], s_ref[0],
                         preferred_element_type=jnp.float32).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def sigma_bmm(t: Array, sigma: Array, tile_ids: Array, *,
              block_t: int = 128, interpret: bool | None = None) -> Array:
    """t: (T_pad, r); sigma: (n, r, r); per-tile adapter ids."""
    T, r = t.shape
    bt = _pick_block(T, block_t)
    assert tile_ids.shape[0] == T // bt, (tile_ids.shape, T, bt)
    return pl.pallas_call(
        _sigma_bmm_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T // bt,),
            in_specs=[
                pl.BlockSpec((bt, r), lambda i, ids: (i, 0)),
                pl.BlockSpec((1, r, r), lambda i, ids: (ids[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((bt, r), lambda i, ids: (i, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, r), t.dtype),
        interpret=_interpret(interpret),
    )(tile_ids, t, sigma)
