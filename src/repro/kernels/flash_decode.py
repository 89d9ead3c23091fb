"""Flash-decode attention kernel: one query token per sequence against a long
KV cache, online-softmax over KV blocks (FlashDecoding-style, TPU tiling).

Grid is (B, S_blocks); the S dimension is the minor (sequential on TPU)
axis so fp32 scratch accumulators persist across the KV blocks of one
sequence.  Used by the serving engine's decode step and by the
sequence-sharded long-context path (each shard runs this kernel over its
KV slice, partial (m, l, o) stats are merged across shards — see
distributed/collectives.py).

K/V are read in the cache's own layout: one grid step fetches a block of
``bs`` tokens with every kv head, ``(bs, Kv, hd)``, which is a contiguous
run of the cache, and scores all ``H`` query heads against it at once
(:func:`_attend`).  Nothing is sliced or relaid out before the call.

Three layouts share one kernel body:

- :func:`flash_decode` — contiguous KV, ``k/v: (B, S, Kv, hd)``, or the
  layer-stacked cache ``(L, B, S, Kv, hd)`` read at ``layer``; a static
  ``window`` attends only the first ``window`` tokens, and the grid covers
  those blocks alone.  The layer rides in as the first scalar-prefetch
  operand, read by the k/v index maps, so one compiled kernel serves every
  layer of a step.
- :func:`flash_decode_paged` — unified-paging KV (S-LoRA/Punica): each
  sequence's cache lives in non-contiguous :data:`PAGE_TOKENS`-token pages
  of a shared pool, ``k/v: (P, page_t, Kv, hd)``, addressed through a per-
  sequence page table.  The page table rides in as a SECOND scalar-prefetch
  operand (the adapter-id pattern of ``sgmv.py``) in the layer's place:
  the k/v BlockSpec index maps read ``pt[b, s]`` to fetch logical block
  ``s``'s physical page, so the gather costs nothing extra — it is just
  block addressing.  The kernel is the *same function* as the contiguous
  one, so the two are bit-exact given equal logical content (asserted in
  tests/test_paged.py against the ``kernels/ref.py`` oracle over permuted
  page tables).
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
# VMEM for one grid step's K/V working set: both blocks double-buffered in
# the cache dtype, their f32 upcasts, and the (H, bs*Kv) scores
_KV_VMEM_BYTES = 8 << 20


def _window(k: Array, layer, window: int | None) -> int:
    """Tokens attended per sequence: the first ``window`` (default all S)
    of a (B, S, Kv, hd) operand, or of layer ``layer`` of a stacked
    (L, B, S, Kv, hd) cache."""
    if (k.ndim == 5) != (layer is not None):
        raise ValueError("a stacked (L, B, S, Kv, hd) cache is read at a "
                         f"layer; got k{k.shape} with layer={layer}")
    S = k.shape[-3]
    window = S if window is None else window
    if not 0 < window <= S:
        raise ValueError(f"window {window} outside the cache's {S} tokens")
    return window


def _block_tokens(window: int, k: Array, H: int, block_s: int) -> int:
    """Tokens per K/V block: the largest divisor of ``window`` that is at
    most ``block_s`` and whose working set fits :data:`_KV_VMEM_BYTES`."""
    Kv, hd = k.shape[-2:]
    per_token = Kv * (hd * (4 * k.dtype.itemsize + 2 * 4) + 3 * H * 4)
    return _pick_block(window, max(1, min(block_s,
                                          _KV_VMEM_BYTES // per_token)))


def _layer_operand(layer) -> Array:
    """The first scalar-prefetch operand of a contiguous call: the layer
    of a stacked cache (0, unread, for a (B, S, Kv, hd) operand)."""
    return jnp.asarray(0 if layer is None else layer, jnp.int32).reshape(1)


def _kv_spec(k: Array, bs: int) -> pl.BlockSpec:
    """One sequence's ``bs``-token K/V block with every kv head, addressed
    in the cache's own layout (grid (b, s)); a stacked cache at the layer
    in the first scalar-prefetch operand."""
    tail = k.shape[-2:]
    if k.ndim == 5:
        return pl.BlockSpec((None, None, bs) + tail,
                            lambda b, s, lay, *_: (lay[0], b, s, 0, 0))
    return pl.BlockSpec((None, bs) + tail, lambda b, s, *_: (b, s, 0, 0))


def _paged_kv_spec(k_pages: Array) -> pl.BlockSpec:
    """Logical block ``s`` of sequence ``b``: physical page ``pt[b, s]``
    (the page table is the first scalar-prefetch operand)."""
    return pl.BlockSpec((None,) + k_pages.shape[1:],
                        lambda b, s, pt, *_: (pt[b, s], 0, 0, 0))


def _q_spec(H: int, hd: int) -> pl.BlockSpec:
    return pl.BlockSpec((None, H, hd), lambda b, s, *_: (b, 0, 0))


def _attn_outs(B: int, H: int, hd: int, dtype):
    """Specs and shapes of (out (B, H, hd), l (B, H, 1), m (B, H, 1))."""
    out_specs = [pl.BlockSpec((None, H, hd), lambda b, s, *_: (b, 0, 0)),
                 pl.BlockSpec((None, H, 1), lambda b, s, *_: (b, 0, 0)),
                 pl.BlockSpec((None, H, 1), lambda b, s, *_: (b, 0, 0))]
    out_shape = [jax.ShapeDtypeStruct((B, H, hd), dtype),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32),
                 jax.ShapeDtypeStruct((B, H, 1), jnp.float32)]
    return out_specs, out_shape


def _attn_scratch(H: int, hd: int):
    return [pltpu.VMEM((H, hd), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32),
            pltpu.VMEM((H, 1), jnp.float32)]


def _grouped_stats(l: Array, m: Array, Kv: int):
    """(B, H, 1) softmax stats -> (B, Kv, G, 1), head h = kv head h // G."""
    B, H, _ = l.shape
    return l.reshape(B, Kv, H // Kv, 1), m.reshape(B, Kv, H // Kv, 1)


def _attend(kvlen, q_ref, k_ref, v_ref, acc_ref, m_sc, l_sc):
    """One K/V block's online-softmax update for every head of one sequence.

    q_ref: (H, hd); k_ref/v_ref: (bs, Kv, hd).  The block's rows, token-
    major (row ``t * Kv + j`` is token t of kv head j), are scored against
    all H query heads in one product; query head i keeps only the rows of
    its own kv head ``i // G`` and of tokens below ``kvlen``.  Per head it
    is the single-head update: f32 scores of the ``hd ** -0.5``-scaled
    query, f32 running max ``m_sc``, sum ``l_sc`` and output ``acc_ref``.
    """
    s = pl.program_id(1)
    bs, Kv, hd = k_ref.shape
    H = q_ref.shape[0]
    q = q_ref[...].astype(jnp.float32)
    q = q * (hd ** -0.5)
    k = k_ref[...].astype(jnp.float32).reshape(bs * Kv, hd)
    v = v_ref[...].astype(jnp.float32).reshape(bs * Kv, hd)
    logits = jax.lax.dot_general(
        q, k, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (H, bs*Kv)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, bs * Kv), 1)
    head = jax.lax.broadcasted_iota(jnp.int32, (H, 1), 0)
    valid = ((row % Kv == head // (H // Kv))
             & (s * bs + row // Kv < kvlen))
    logits = jnp.where(valid, logits, NEG_INF)
    m_prev = m_sc[...]                                   # (H, 1)
    m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
    p = jnp.exp(logits - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_sc[...] = l_sc[...] * alpha + p.sum(-1, keepdims=True)
    m_sc[...] = m_new
    acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)


def _attend_blocks(kvlen, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                   acc_ref, m_sc, l_sc):
    """The shared body: init on the first block, :func:`_attend` on each,
    and on the last write out (H, hd) and the (H, 1) stats.  Returns
    whether this is the last block, for the fused kernels' epilogues."""
    s = pl.program_id(1)

    @pl.when(s == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    _attend(kvlen, q_ref, k_ref, v_ref, acc_ref, m_sc, l_sc)
    last = s == pl.num_programs(1) - 1

    @pl.when(last)
    def _done():
        o_ref[...] = _finalized(acc_ref, l_sc).astype(o_ref.dtype)
        l_ref[...] = l_sc[...]
        m_ref[...] = m_sc[...]

    return last


def _finalized(acc_ref, l_sc) -> Array:
    """(H, hd) f32 attention output of one sequence."""
    return acc_ref[...] / jnp.maximum(l_sc[...], 1e-30)


def _decode_kernel(where_ref, kvlen_ref, *refs):
    # where_ref (the layer, or the page table) is consumed by the k/v
    # BlockSpec index maps; contiguous and paged calls share this kernel,
    # which is what makes them bit-exact.
    del where_ref
    _attend_blocks(kvlen_ref[pl.program_id(0)], *refs)


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "interpret"))
def flash_decode(q: Array, k: Array, v: Array, kv_len: Array, *,
                 layer=None, window: int | None = None,
                 block_s: int = 512, interpret: bool | None = None):
    """q: (B, H, hd); k/v: (B, S, Kv, hd), or the stacked (L, B, S, Kv, hd)
    cache read at ``layer`` (an int, traced); kv_len: (B,) int32.  Attends
    the first ``window`` tokens (default S).

    Returns (out (B, H, hd), l (B, Kv, G, 1), m (B, Kv, G, 1)) — the (l, m)
    stats allow cross-shard softmax merging for sequence-sharded KV.
    """
    B, H, hd = q.shape
    Kv = k.shape[-2]
    window = _window(k, layer, window)
    bs = _block_tokens(window, k, H, block_s)
    out_specs, out_shape = _attn_outs(B, H, hd, q.dtype)
    out, l, m = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, window // bs),
            in_specs=[_q_spec(H, hd), _kv_spec(k, bs), _kv_spec(v, bs)],
            out_specs=out_specs,
            scratch_shapes=_attn_scratch(H, hd),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(_layer_operand(layer), kv_len, q, k, v)
    return (out,) + _grouped_stats(l, m, Kv)


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
    Kv = k_pages.shape[2]
    out_specs, out_shape = _attn_outs(B, H, hd, q.dtype)
    out, l, m = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, page_table.shape[1]),
            in_specs=[_q_spec(H, hd), _paged_kv_spec(k_pages),
                      _paged_kv_spec(v_pages)],
            out_specs=out_specs,
            scratch_shapes=_attn_scratch(H, hd),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(page_table, kv_len, q, k_pages, v_pages)
    return (out,) + _grouped_stats(l, m, Kv)
