"""Fused flash-decode + per-slot adapter delta: one kernel, one HBM pass.

The serving hot path used to be three kernel launches per decode step —
`flash_decode.py` attention, then `sgmv.py` shrink/expand (raw LoRA) or
`jd_apply.py` (compressed shared basis) re-reading the attention output
from HBM.  Punica's observation (PAPERS.md) is that the per-slot adapter
matmul is tiny next to the attention read and belongs in the attention
kernel's epilogue.  These kernels do exactly that:

* The grid, K/V BlockSpecs, and online-softmax body are `flash_decode`'s
  — the attention math is the *same function* (`_attend_blocks`), so fused
  attention output is bit-exact with the unfused kernel.  K/V are read in
  the cache's own (…, S, Kv, hd) layout, from a (B, S, Kv, hd) operand or
  from layer ``layer`` of the stacked (L, B, S, Kv, hd) cache, so the
  serving step hands its whole donated cache to the call and nothing is
  sliced or relaid out per layer.
* Per-slot adapter ids (and cluster ids for the jd path) ride in as
  scalar-prefetch operands, the `sgmv.py` pattern: the adapter-bank
  BlockSpec index maps read ``ids[b]`` so each sequence fetches only its
  own adapter's rows.
* When the attention accumulator of one sequence finalizes (last S
  block), its (H, hd) output is immediately contracted against the LoRA
  ``A`` (or basis ``V``) factor — the "shrink" happens while the
  activation is still in VMEM — then expanded (``Sigma``/``B``/``U``) into
  the (1, d_out) delta output block.
* Int8 banks from `adapter_quant.py` are dequantized *inside* the kernel:
  per-output-channel scales are always passed (ones for fp banks — a
  bit-exact multiply), so one body serves both precisions.

Delta outputs revisit one (1, d_out) block across the s grid axis;
Pallas guarantees revisited output blocks stay resident across contiguous
grid iterations, so only the final visit's write lands — the same
contract `flash_decode` relies on for its own epilogue.

Paged variants mirror `flash_decode_paged`: the page table takes the
layer's place as the first scalar-prefetch operand and the kernels are the
same functions, so paged and contiguous fused results are bit-exact on
equal logical content (asserted in tests/test_kernels.py over permuted
page tables).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_decode import (_attend_blocks, _attn_outs, _attn_scratch,
                           _block_tokens, _finalized, _kv_spec,
                           _layer_operand, _paged_kv_spec, _q_spec, _window)
from .sgmv import _interpret

Array = jax.Array


def _flat_attn(acc_ref, l_sc):
    """(1, H*hd) f32 attention output of one sequence, head-major — the
    same flattening `out.reshape(B, -1)` produces on the unfused path."""
    return _finalized(acc_ref, l_sc).reshape(1, -1)


def _fresh(key_ref, b):
    """Whether sequence ``b``'s expand-bank block differs from sequence
    ``b - 1``'s: its id in ``key_ref`` (adapter or cluster) changed."""
    return (b == 0) | (key_ref[b] != key_ref[jnp.maximum(b - 1, 0)])


def _expand_out(d_ref, t, w_ref, s_ref, wt_sc, st_sc, fresh):
    """delta = (t @ W^T) * scale — W rows are output channels (d_out).

    W's (d_out, r) block is transposed to (r, d_out), and its (d_out, 1)
    scales to a row, into VMEM scratch only when ``fresh``: the grid walks
    the sequences in order, so a run of sequences on one adapter or jd
    cluster reuses them.  The relayouts, not the (1, r) x (r, d_out)
    product, are what an expand costs."""
    @pl.when(fresh)
    def _relayout():
        wt_sc[...] = w_ref[...].astype(jnp.float32).T
        st_sc[...] = s_ref[...].astype(jnp.float32).reshape(1, -1)

    d = jnp.dot(t, wt_sc[...], preferred_element_type=jnp.float32)
    d_ref[...] = d * st_sc[...]                          # (1, d_out)


def _fused_lora_kernel(where_ref, ids_ref, kvlen_ref, q_ref, k_ref, v_ref,
                       a_ref, as_ref, b_ref, bs_ref,
                       o_ref, l_ref, m_ref, d_ref, acc_ref, m_sc, l_sc,
                       wt_sc, st_sc):
    # where_ref (the layer, or the page table) and ids_ref feed the
    # BlockSpec index maps; contiguous and paged calls share this kernel
    del where_ref
    b = pl.program_id(0)
    last = _attend_blocks(kvlen_ref[b], q_ref, k_ref, v_ref,
                          o_ref, l_ref, m_ref, acc_ref, m_sc, l_sc)

    @pl.when(last)
    def _delta():
        # A rows are rank channels: per-row scales rescale the rank axis
        a = a_ref[...].astype(jnp.float32)               # (r, H*hd)
        t = jax.lax.dot_general(
            _flat_attn(acc_ref, l_sc), a,
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # (1, r)
        t = t * as_ref[...].reshape(1, -1).astype(jnp.float32)
        _expand_out(d_ref, t, b_ref, bs_ref, wt_sc, st_sc, _fresh(ids_ref, b))


def _fused_jd_kernel(where_ref, ids_ref, cids_ref, kvlen_ref, q_ref, k_ref,
                     v_ref, vb_ref, vs_ref, sig_ref, u_ref, us_ref,
                     o_ref, l_ref, m_ref, d_ref, acc_ref, m_sc, l_sc,
                     wt_sc, st_sc):
    # ids_ref indexes the per-slot Sigma; cids_ref the shared U/V bases
    del where_ref, ids_ref
    b = pl.program_id(0)
    last = _attend_blocks(kvlen_ref[b], q_ref, k_ref, v_ref,
                          o_ref, l_ref, m_ref, acc_ref, m_sc, l_sc)

    @pl.when(last)
    def _delta():
        vb = vb_ref[...].astype(jnp.float32)             # (H*hd, r)
        t = jnp.dot(_flat_attn(acc_ref, l_sc), vb,
                    preferred_element_type=jnp.float32)
        t = t * vs_ref[...].astype(jnp.float32)          # vs: (1, r)
        sig = sig_ref[...].astype(jnp.float32)           # (1, r) or (r, r)
        if sig.shape[0] == 1:                            # JD-Diag (r=1 alike)
            t = t * sig
        else:                                            # JD-Full
            t = jnp.dot(t, sig, preferred_element_type=jnp.float32)
        _expand_out(d_ref, t, u_ref, us_ref, wt_sc, st_sc,
                    _fresh(cids_ref, b))


def _stacked(bank: Array, ndim: int, layer) -> bool:
    """Whether ``bank`` carries a leading layer axis on its per-layer
    ``ndim``-D form (it is then read at ``layer``)."""
    if bank.ndim == ndim:
        return False
    if bank.ndim != ndim + 1 or layer is None:
        raise ValueError(f"a bank of shape {bank.shape} is neither one "
                         f"layer's ({ndim}-D) nor, with a layer, a "
                         "layer-stacked one")
    return True


# scalar-prefetch operands of every fused call, in order: the layer (or
# the page table), the adapter ids, [the jd cluster ids,] kv_len
_IDS, _CIDS = 1, 2


def _bank_spec(bank: Array, pos: int, stacked: bool) -> pl.BlockSpec:
    """One adapter's (or cluster's) whole matrix of ``bank``: entry ``b``
    of the scalar-prefetch operand at ``pos`` picks it, at the layer in the
    first scalar-prefetch operand of a layer-stacked bank."""
    if not stacked:
        zeros = (0,) * (bank.ndim - 1)
        return pl.BlockSpec((None,) + bank.shape[1:],
                            lambda b, s, *sc: (sc[pos][b],) + zeros)
    zeros = (0,) * (bank.ndim - 2)
    return pl.BlockSpec((None, None) + bank.shape[2:],
                        lambda b, s, *sc: (sc[0][0], sc[pos][b]) + zeros)


def _fused_call(kernel, scalars, q, k, v, kv_specs, n_blocks, banks,
                interpret):
    """One fused pallas_call over a (B, n_blocks) grid.  ``scalars`` are
    the scalar-prefetch operands; ``banks`` ``(array, pos, stacked)``
    triples, each addressed by :func:`_bank_spec`, the expand bank and its
    scales last.  Returns (out (B, H, hd), delta (B, d_out) f32)."""
    B, H, hd = q.shape
    d_out, r = banks[-2][0].shape[-2:]
    out_specs, out_shape = _attn_outs(B, H, hd, q.dtype)
    out, _, _, delta = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B, n_blocks),
            in_specs=[_q_spec(H, hd), *kv_specs]
            + [_bank_spec(*bank) for bank in banks],
            out_specs=out_specs + [pl.BlockSpec(
                (None, 1, d_out), lambda b, s, *_: (b, 0, 0))],
            # the expand bank's transposed block and scale row
            scratch_shapes=_attn_scratch(H, hd) + [
                pltpu.VMEM((r, d_out), jnp.float32),
                pltpu.VMEM((1, d_out), jnp.float32)],
        ),
        out_shape=out_shape + [
            jax.ShapeDtypeStruct((B, 1, d_out), jnp.float32)],
        interpret=_interpret(interpret),
    )(*scalars, q, k, v, *(bank[0] for bank in banks))
    return out, delta[:, 0]


def _ones(shape):
    return jnp.ones(shape, jnp.float32)


def _lora_banks(A, B, a_scale, b_scale, layer):
    """(array, pos, stacked) triples of the raw-LoRA kernel's banks, each
    by adapter id; A/B (and given scales) are one layer's or layer-stacked
    together.  Absent scales are ones (a bit-exact multiply)."""
    st = _stacked(A, 3, layer)
    if _stacked(B, 3, layer) != st:
        raise ValueError("A and B must both be one layer's or both stacked")
    n, r = A.shape[-3:-1]
    d_out = B.shape[-2]

    def scale(x, shape):
        return (_ones(shape), _IDS, False) if x is None else (x, _IDS, st)
    return [(A, _IDS, st), scale(a_scale, (n, r, 1)),
            (B, _IDS, st), scale(b_scale, (n, d_out, 1))]


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "interpret"))
def fused_decode_lora(q: Array, k: Array, v: Array, kv_len: Array,
                      ids: Array, A: Array, B: Array,
                      a_scale: Array | None = None,
                      b_scale: Array | None = None, *,
                      layer=None, window: int | None = None,
                      block_s: int = 512, interpret: bool | None = None):
    """Fused decode attention + raw-LoRA output delta.

    q: (B, H, hd); k/v: (B, S, Kv, hd), or the stacked (L, B, S, Kv, hd)
    cache read at ``layer`` (an int, traced: one compiled kernel serves
    every layer), attended over its first ``window`` tokens (default S);
    kv_len/ids: (B,) int32;
    A: (n, r, H*hd) fp or int8 with a_scale (n, r, 1);
    B: (n, d_out, r) fp or int8 with b_scale (n, d_out, 1); with a
    stacked cache the banks may carry a leading layer axis too, and are
    then read at ``layer`` inside the kernel.

    Returns (out (B, H, hd), delta (B, d_out) f32) where out is bit-exact
    with `flash_decode` and delta is the un-scaled per-slot LoRA delta
    (caller applies `LoRAContext.scaling`).
    """
    H, hd = q.shape[1:]
    if A.shape[-1] != H * hd:
        raise ValueError(f"A maps {A.shape[-1]} dims, attention makes "
                         f"{H * hd}")
    window = _window(k, layer, window)
    bs = _block_tokens(window, k, H, block_s)
    return _fused_call(
        _fused_lora_kernel, (_layer_operand(layer), ids, kv_len), q, k, v,
        [_kv_spec(k, bs), _kv_spec(v, bs)], window // bs,
        _lora_banks(A, B, a_scale, b_scale, layer), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decode_lora_paged(q: Array, k_pages: Array, v_pages: Array,
                            page_table: Array, kv_len: Array, ids: Array,
                            A: Array, B: Array,
                            a_scale: Array | None = None,
                            b_scale: Array | None = None, *,
                            interpret: bool | None = None):
    """Paged-KV variant of :func:`fused_decode_lora` (layout contract of
    `flash_decode_paged`: k/v_pages (P, page_t, Kv, hd) + page_table
    (B, n_blocks))."""
    return _fused_call(
        _fused_lora_kernel, (page_table, ids, kv_len), q, k_pages,
        v_pages, [_paged_kv_spec(k_pages), _paged_kv_spec(v_pages)],
        page_table.shape[1], _lora_banks(A, B, a_scale, b_scale, None),
        interpret)


def _jd_banks(U, V, sigma, cluster_of, ids, u_scale, v_scale, layer):
    """The jd kernel's cluster ids and (array, pos, stacked) bank triples:
    the per-cluster bases by cluster id, the per-slot Sigma as (n, 1, r)
    diag or (n, r, r) full by adapter id — one adapter's block then spans
    the two minor dims whole, which Mosaic can tile.  U/V/Sigma/cluster_of
    (and given scales) are one layer's or layer-stacked together."""
    st = _stacked(U, 3, layer)
    if _stacked(V, 3, layer) != st:
        raise ValueError("U and V must both be one layer's or both stacked")
    kcl, _, r = V.shape[-3:]
    d_out = U.shape[-2]
    if sigma.ndim == (3 if st else 2):                   # diag
        sigma = sigma[..., None, :]
    if st:
        cluster_of = cluster_of[layer]
    cids = cluster_of[ids].astype(jnp.int32)

    def scale(x, shape):
        return (_ones(shape), _CIDS, False) if x is None else (x, _CIDS, st)
    return cids, [(V, _CIDS, st), scale(v_scale, (kcl, 1, r)),
                  (sigma, _IDS, st), (U, _CIDS, st),
                  scale(u_scale, (kcl, d_out, 1))]


@functools.partial(jax.jit, static_argnames=("window", "block_s",
                                             "interpret"))
def fused_decode_jd(q: Array, k: Array, v: Array, kv_len: Array, ids: Array,
                    U: Array, V: Array, sigma: Array, cluster_of: Array,
                    u_scale: Array | None = None,
                    v_scale: Array | None = None, *,
                    layer=None, window: int | None = None,
                    block_s: int = 512, interpret: bool | None = None):
    """Fused decode attention + compressed shared-basis (jd) output delta.

    k/v and ``layer``/``window`` as :func:`fused_decode_lora`.
    U: (k_clusters, d_out, r) / V: (k_clusters, H*hd, r) fp or int8 with
    u_scale (k, d_out, 1) / v_scale (k, 1, r); sigma: per-slot (n, r)
    diag or (n, r, r) full; cluster_of: (n,) int32.  With a stacked cache
    all of them may carry a leading layer axis too, and are then read at
    ``layer`` inside the kernel.  Cluster ids are gathered host-side
    (``cluster_of[ids]``) and prefetched alongside the adapter ids.
    Returns (out (B, H, hd), delta (B, d_out) f32).
    """
    H, hd = q.shape[1:]
    if V.shape[-2] != H * hd:
        raise ValueError(f"V maps {V.shape[-2]} dims, attention makes "
                         f"{H * hd}")
    window = _window(k, layer, window)
    bs = _block_tokens(window, k, H, block_s)
    cids, banks = _jd_banks(U, V, sigma, cluster_of, ids, u_scale, v_scale,
                            layer)
    return _fused_call(
        _fused_jd_kernel, (_layer_operand(layer), ids, cids, kv_len), q, k,
        v, [_kv_spec(k, bs), _kv_spec(v, bs)], window // bs, banks,
        interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decode_jd_paged(q: Array, k_pages: Array, v_pages: Array,
                          page_table: Array, kv_len: Array, ids: Array,
                          U: Array, V: Array, sigma: Array,
                          cluster_of: Array,
                          u_scale: Array | None = None,
                          v_scale: Array | None = None, *,
                          interpret: bool | None = None):
    """Paged-KV variant of :func:`fused_decode_jd`."""
    cids, banks = _jd_banks(U, V, sigma, cluster_of, ids, u_scale, v_scale,
                            None)
    return _fused_call(
        _fused_jd_kernel, (page_table, ids, cids, kv_len), q, k_pages,
        v_pages, [_paged_kv_spec(k_pages), _paged_kv_spec(v_pages)],
        page_table.shape[1], banks, interpret)
