"""Flash-decode attention kernel: one query token per sequence against a long
KV cache, online-softmax over KV blocks (FlashDecoding-style, TPU tiling).

Grid is (B, Kv, S_blocks); the S dimension is the minor (sequential on TPU)
axis so fp32 scratch accumulators persist across KV blocks of one (b, head).
Used by the serving engine's decode step and by the sequence-sharded
long-context path (each shard runs this kernel over its KV slice, partial
(m, l, o) stats are merged across shards — see distributed/collectives.py).

Two layouts share one kernel body:

- :func:`flash_decode` — contiguous KV, ``k/v: (B, S, Kv, hd)``.
- :func:`flash_decode_paged` — unified-paging KV (S-LoRA/Punica): each
  sequence's cache lives in non-contiguous :data:`PAGE_TOKENS`-token pages
  of a shared pool, ``k/v: (P, page_t, Kv, hd)``, addressed through a per-
  sequence page table.  The page table rides in as a SECOND scalar-prefetch
  operand (the adapter-id pattern of ``sgmv.py``): the k/v BlockSpec index
  maps read ``pt[b, s]`` to fetch logical block ``s``'s physical page, so
  the gather costs nothing extra — it is just block addressing.  The body
  is the *same function* as the contiguous kernel, so the two are bit-exact
  given equal logical content (asserted in tests/test_paged.py against the
  ``kernels/ref.py`` oracle over permuted page tables).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .sgmv import _interpret, _pick_block

Array = jax.Array
NEG_INF = -1e30


def _flat_kv(x: Array) -> Array:
    """(B|P, S, Kv, hd) -> (B|P, S, Kv*hd), a free reshape.  Mosaic tiles
    the two minor dims of a block; with the heads folded into the lane
    axis, one kv-head's block is (tokens, hd) at lane offset h*hd instead
    of a 1-wide slice of the Kv axis, which the TPU cannot tile."""
    return x.reshape(x.shape[0], x.shape[1], -1)


def _kv_block(bs: int, hd: int):
    """Block shape of one (sequence or page, kv-head) K/V tile over
    :func:`_flat_kv` layout; index maps address it as (b, s, h)."""
    return (None, bs, hd)


def _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                   acc_ref, m_sc, l_sc):
    b = pl.program_id(0)
    s = pl.program_id(2)
    ns = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)                  # (G, hd)
    q = q * (q.shape[-1] ** -0.5)
    k = k_ref[...].astype(jnp.float32)                   # (bs, hd)
    v = v_ref[...].astype(jnp.float32)                   # (bs, hd)
    bs = k.shape[0]
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (G, bs)
    pos = s * bs + jax.lax.broadcasted_iota(jnp.int32, (1, bs), 1)
    valid = pos < kvlen_ref[b]
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_sc[...]                                   # (G, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + p.sum(-1, keepdims=True)
    m_sc[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)

    @pl.when(s == ns - 1)
    def _done():
        o_ref[0, 0] = (acc_ref[...] / jnp.maximum(l_sc[...], 1e-30)
                       ).astype(o_ref.dtype)
        l_ref[0, 0] = l_sc[...]
        m_ref[0, 0] = m_sc[...]


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def flash_decode(q: Array, k: Array, v: Array, kv_len: Array, *,
                 block_s: int = 512, interpret: bool | None = None):
    """q: (B, H, hd); k/v: (B, S, Kv, hd); kv_len: (B,) int32.

    Returns (out (B, H, hd), l (B, Kv, G, 1), m (B, Kv, G, 1)) — the (l, m)
    stats allow cross-shard softmax merging for sequence-sharded KV.
    """
    B, H, hd = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    bs = _pick_block(S, block_s)
    grid = (B, Kv, S // bs)
    qg = q.reshape(B, Kv, G, hd)
    out, l, m = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, s, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, kl: (b, s, h)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, kl: (b, s, h)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, G, hd), lambda b, h, s, kl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, G, 1), lambda b, h, s, kl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, G, 1), lambda b, h, s, kl: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
        ],
        interpret=_interpret(interpret),
    )(kv_len, qg, _flat_kv(k), _flat_kv(v))
    return out.reshape(B, H, hd), l, m


def _decode_paged_kernel(pt_ref, kvlen_ref, q_ref, k_ref, v_ref, o_ref,
                         l_ref, m_ref, acc_ref, m_sc, l_sc):
    # pt_ref is consumed by the k/v BlockSpec index maps (physical page
    # lookup); the softmax body is the contiguous kernel, unchanged — that
    # sharing is what makes paged vs contiguous bit-exact.
    del pt_ref
    _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                   acc_ref, m_sc, l_sc)


@functools.partial(jax.jit, static_argnames=("interpret",))
def flash_decode_paged(q: Array, k_pages: Array, v_pages: Array,
                       page_table: Array, kv_len: Array, *,
                       interpret: bool | None = None):
    """Gathered-page flash decode over a unified paged KV pool.

    q: (B, H, hd); k_pages/v_pages: (P, page_t, Kv, hd) — the pool's
    physical pages; page_table: (B, n_blocks) int32 — sequence b's logical
    KV block s lives in page ``page_table[b, s]``; kv_len: (B,) int32.

    Entries of `page_table` beyond ``ceil(kv_len[b] / page_t)`` must be
    valid page indices (e.g. 0) — their tokens are masked by `kv_len` but
    the blocks are still fetched.  Returns (out (B, H, hd),
    l (B, Kv, G, 1), m (B, Kv, G, 1)) exactly like :func:`flash_decode`.
    """
    B, H, hd = q.shape
    page_t, Kv = k_pages.shape[1], k_pages.shape[2]
    n_blocks = page_table.shape[1]
    G = H // Kv
    grid = (B, Kv, n_blocks)
    qg = q.reshape(B, Kv, G, hd)
    out, l, m = pl.pallas_call(
        _decode_paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, pt, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, kl: (pt[b, s], 0, h)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, kl: (pt[b, s], 0, h)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, pt, kl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, G, 1),
                             lambda b, h, s, pt, kl: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, G, 1),
                             lambda b, h, s, pt, kl: (b, h, 0, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((G, hd), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
                pltpu.VMEM((G, 1), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Kv, G, hd), q.dtype),
            jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
        ],
        interpret=_interpret(interpret),
    )(page_table, kv_len, qg, _flat_kv(k_pages), _flat_kv(v_pages))
    return out.reshape(B, H, hd), l, m
