"""Fused flash-decode + per-slot adapter delta: one kernel, one HBM pass.

The serving hot path used to be three kernel launches per decode step —
`flash_decode.py` attention, then `sgmv.py` shrink/expand (raw LoRA) or
`jd_apply.py` (compressed shared basis) re-reading the attention output
from HBM.  Punica's observation (PAPERS.md) is that the per-slot adapter
matmul is tiny next to the attention read and belongs in the attention
kernel's epilogue.  These kernels do exactly that:

* The grid, BlockSpecs, and online-softmax body are `flash_decode`'s —
  the attention math is the *same function* (`_decode_kernel`), so fused
  attention output is bit-exact with the unfused kernel.
* Per-slot adapter ids (and cluster ids for the jd path) ride in as
  scalar-prefetch operands, the `sgmv.py` pattern: the adapter-bank
  BlockSpec index maps read ``ids[b]`` so each sequence fetches only its
  own adapter's rows.
* When the attention accumulator for one (b, kv-head) finalizes (last S
  block), its (G, hd) tile is immediately contracted against that head's
  slice of the LoRA ``A`` (or basis ``V``) factor into a rank-r scratch
  accumulator — the "shrink" happens while the activation is still in
  VMEM.  The last head's iteration runs the expand (``Sigma``/``B``/``U``)
  and writes the (1, d_out) delta output block.
* Int8 banks from `adapter_quant.py` are dequantized *inside* the kernel:
  per-output-channel scales are always passed (ones for fp banks — a
  bit-exact multiply), so one body serves both precisions.

Delta outputs revisit one (1, d_out) block across the (h, s) grid axes;
Pallas guarantees revisited output blocks stay resident across contiguous
grid iterations, so only the final visit's write lands — the same
contract `flash_decode` relies on for its own epilogue.

Paged variants mirror `flash_decode_paged`: the page table is one more
scalar-prefetch operand and the bodies delegate, so paged and contiguous
fused results are bit-exact on equal logical content (asserted in
tests/test_kernels.py over permuted page tables).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_decode import _decode_kernel, _flat_kv, _kv_block
from .sgmv import _interpret, _pick_block

Array = jax.Array


def _finalized_attn(acc_ref, l_sc):
    """(1, G*hd) f32 attention output for this (b, kv-head), flattened to
    its slice of the (H*hd,) activation vector (head-major layout — the
    same flattening `out.reshape(B, -1)` produces on the unfused path)."""
    o = acc_ref[...] / jnp.maximum(l_sc[...], 1e-30)     # (G, hd)
    return o.reshape(1, -1)


def _shrink_into(t_sc, of, w_ref, s_ref):
    """t += (of @ W[head_slice]^T) * scale — W rows are rank channels, so
    per-row scales rescale the rank axis after the contraction."""
    w = w_ref[0].astype(jnp.float32)                     # (r, G*hd)
    t = jax.lax.dot_general(
        of, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (1, r)
    t_sc[...] += t * s_ref[0].reshape(1, -1).astype(jnp.float32)


def _expand_out(d_ref, t, w_ref, s_ref):
    """delta = (t @ W^T) * scale — W rows are output channels (d_out)."""
    w = w_ref[0].astype(jnp.float32)                     # (d_out, r)
    d = jax.lax.dot_general(
        t, w, dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)              # (1, d_out)
    d_ref[...] = d * s_ref[0].reshape(1, -1).astype(jnp.float32)


def _fused_lora_kernel(ids_ref, kvlen_ref, q_ref, k_ref, v_ref,
                       a_ref, as_ref, b_ref, bs_ref,
                       o_ref, l_ref, m_ref, d_ref,
                       acc_ref, m_sc, l_sc, t_sc):
    # ids_ref is consumed by the A/B/scale BlockSpec index maps
    del ids_ref
    h, s = pl.program_id(1), pl.program_id(2)
    nh, ns = pl.num_programs(1), pl.num_programs(2)

    @pl.when((h == 0) & (s == 0))
    def _init_t():
        t_sc[...] = jnp.zeros_like(t_sc)

    _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                   acc_ref, m_sc, l_sc)

    @pl.when(s == ns - 1)
    def _shrink():
        _shrink_into(t_sc, _finalized_attn(acc_ref, l_sc), a_ref, as_ref)

    @pl.when((h == nh - 1) & (s == ns - 1))
    def _expand():
        _expand_out(d_ref, t_sc[...], b_ref, bs_ref)


def _fused_jd_kernel(ids_ref, cids_ref, kvlen_ref, q_ref, k_ref, v_ref,
                     vb_ref, vs_ref, sig_ref, u_ref, us_ref,
                     o_ref, l_ref, m_ref, d_ref,
                     acc_ref, m_sc, l_sc, t_sc):
    # ids_ref indexes the per-slot Sigma; cids_ref the shared U/V bases
    del ids_ref, cids_ref
    h, s = pl.program_id(1), pl.program_id(2)
    nh, ns = pl.num_programs(1), pl.num_programs(2)

    @pl.when((h == 0) & (s == 0))
    def _init_t():
        t_sc[...] = jnp.zeros_like(t_sc)

    _decode_kernel(kvlen_ref, q_ref, k_ref, v_ref, o_ref, l_ref, m_ref,
                   acc_ref, m_sc, l_sc)

    @pl.when(s == ns - 1)
    def _shrink():
        of = _finalized_attn(acc_ref, l_sc)
        vb = vb_ref[0].astype(jnp.float32)               # (G*hd, r)
        t = jnp.dot(of, vb, preferred_element_type=jnp.float32)
        t_sc[...] += t * vs_ref[0].astype(jnp.float32)   # vs: (1, r)

    @pl.when((h == nh - 1) & (s == ns - 1))
    def _expand():
        t = t_sc[...]
        sig = sig_ref[...].astype(jnp.float32)           # (1, r) or (r, r)
        if sig.shape[0] == 1:                            # JD-Diag (r=1 alike)
            t = t * sig
        else:                                            # JD-Full
            t = jnp.dot(t, sig, preferred_element_type=jnp.float32)
        _expand_out(d_ref, t, u_ref, us_ref)


def _fused_lora_paged_kernel(pt_ref, ids_ref, kvlen_ref, *refs):
    # pt_ref feeds the k/v index maps; body shared with the contiguous
    # kernel, so paged/contiguous fused results are bit-exact
    del pt_ref
    _fused_lora_kernel(ids_ref, kvlen_ref, *refs)


def _fused_jd_paged_kernel(pt_ref, ids_ref, cids_ref, kvlen_ref, *refs):
    del pt_ref
    _fused_jd_kernel(ids_ref, cids_ref, kvlen_ref, *refs)


def _attn_outs(B, Kv, G, hd, d_out, dtype):
    out_specs = [
        pl.BlockSpec((1, 1, G, hd), lambda b, h, s, *sc: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, G, 1), lambda b, h, s, *sc: (b, h, 0, 0)),
        pl.BlockSpec((1, 1, G, 1), lambda b, h, s, *sc: (b, h, 0, 0)),
        pl.BlockSpec((None, 1, d_out), lambda b, h, s, *sc: (b, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, Kv, G, hd), dtype),
        jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, Kv, G, 1), jnp.float32),
        jax.ShapeDtypeStruct((B, 1, d_out), jnp.float32),
    ]
    return out_specs, out_shape


def _scratch(G, hd, r):
    return [pltpu.VMEM((G, hd), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((1, r), jnp.float32)]


def _ones(shape):
    return jnp.ones(shape, jnp.float32)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def fused_decode_lora(q: Array, k: Array, v: Array, kv_len: Array,
                      ids: Array, A: Array, B: Array,
                      a_scale: Array | None = None,
                      b_scale: Array | None = None, *,
                      block_s: int = 512, interpret: bool | None = None):
    """Fused decode attention + raw-LoRA output delta.

    q: (B, H, hd); k/v: (B, S, Kv, hd); kv_len/ids: (B,) int32;
    A: (n, r, H*hd) fp or int8 with a_scale (n, r, 1);
    B: (n, d_out, r) fp or int8 with b_scale (n, d_out, 1).

    Returns (out (B, H, hd), delta (B, d_out) f32) where out is bit-exact
    with `flash_decode` and delta is the un-scaled per-slot LoRA delta
    (caller applies `LoRAContext.scaling`).
    """
    Bt, H, hd = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    n, r, d_attn = A.shape
    d_out = B.shape[1]
    if d_attn != H * hd:
        raise ValueError(f"A maps {d_attn} dims, attention makes {H * hd}")
    a_scale = _ones((n, r, 1)) if a_scale is None else a_scale
    b_scale = _ones((n, d_out, 1)) if b_scale is None else b_scale
    bs = _pick_block(S, block_s)
    grid = (Bt, Kv, S // bs)
    qg = q.reshape(Bt, Kv, G, hd)
    out_specs, out_shape = _attn_outs(Bt, Kv, G, hd, d_out, q.dtype)
    out, l, m, delta = pl.pallas_call(
        _fused_lora_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, ids, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, ids, kl: (b, s, h)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, ids, kl: (b, s, h)),
                pl.BlockSpec((1, r, G * hd),
                             lambda b, h, s, ids, kl: (ids[b], 0, h)),
                pl.BlockSpec((1, r, 1),
                             lambda b, h, s, ids, kl: (ids[b], 0, 0)),
                pl.BlockSpec((1, d_out, r),
                             lambda b, h, s, ids, kl: (ids[b], 0, 0)),
                pl.BlockSpec((1, d_out, 1),
                             lambda b, h, s, ids, kl: (ids[b], 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=_scratch(G, hd, r),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(ids, kv_len, qg, _flat_kv(k), _flat_kv(v), A, a_scale, B, b_scale)
    del l, m
    return out.reshape(Bt, H, hd), delta[:, 0]


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
    Bt, H, hd = q.shape
    page_t, Kv = k_pages.shape[1], k_pages.shape[2]
    n_blocks = page_table.shape[1]
    G = H // Kv
    n, r, _ = A.shape
    d_out = B.shape[1]
    a_scale = _ones((n, r, 1)) if a_scale is None else a_scale
    b_scale = _ones((n, d_out, 1)) if b_scale is None else b_scale
    grid = (Bt, Kv, n_blocks)
    qg = q.reshape(Bt, Kv, G, hd)
    out_specs, out_shape = _attn_outs(Bt, Kv, G, hd, d_out, q.dtype)
    out, l, m, delta = pl.pallas_call(
        _fused_lora_paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, pt, ids, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, ids, kl: (pt[b, s], 0, h)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, ids, kl: (pt[b, s], 0, h)),
                pl.BlockSpec((1, r, G * hd),
                             lambda b, h, s, pt, ids, kl: (ids[b], 0, h)),
                pl.BlockSpec((1, r, 1),
                             lambda b, h, s, pt, ids, kl: (ids[b], 0, 0)),
                pl.BlockSpec((1, d_out, r),
                             lambda b, h, s, pt, ids, kl: (ids[b], 0, 0)),
                pl.BlockSpec((1, d_out, 1),
                             lambda b, h, s, pt, ids, kl: (ids[b], 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=_scratch(G, hd, r),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(page_table, ids, kv_len, qg, _flat_kv(k_pages),
      _flat_kv(v_pages), A, a_scale, B, b_scale)
    del l, m
    return out.reshape(Bt, H, hd), delta[:, 0]


def _jd_sigma(sigma):
    """Per-slot Sigma as (n, 1, r) diag or (n, r, r) full: one adapter's
    block then spans the two minor dims whole, which Mosaic can tile."""
    return sigma[:, None, :] if sigma.ndim == 2 else sigma


def _jd_sigma_spec(sigma, pos):
    """BlockSpec for one slot's :func:`_jd_sigma` matrix.  ``pos`` is the
    index of `ids` among the scalar-prefetch refs."""
    return pl.BlockSpec((None,) + sigma.shape[1:],
                        lambda b, h, s, *sc: (sc[pos][b], 0, 0))


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def fused_decode_jd(q: Array, k: Array, v: Array, kv_len: Array, ids: Array,
                    U: Array, V: Array, sigma: Array, cluster_of: Array,
                    u_scale: Array | None = None,
                    v_scale: Array | None = None, *,
                    block_s: int = 512, interpret: bool | None = None):
    """Fused decode attention + compressed shared-basis (jd) output delta.

    U: (k_clusters, d_out, r) / V: (k_clusters, H*hd, r) fp or int8 with
    u_scale (k, d_out, 1) / v_scale (k, 1, r); sigma: per-slot (n, r)
    diag or (n, r, r) full; cluster_of: (n,) int32.  Cluster ids are
    gathered host-side (``cluster_of[ids]``) and prefetched alongside the
    adapter ids.  Returns (out (B, H, hd), delta (B, d_out) f32).
    """
    Bt, H, hd = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    kcl, d_attn, r = V.shape
    d_out = U.shape[1]
    if d_attn != H * hd:
        raise ValueError(f"V maps {d_attn} dims, attention makes {H * hd}")
    cids = cluster_of[ids].astype(jnp.int32)
    sigma = _jd_sigma(sigma)
    u_scale = _ones((kcl, d_out, 1)) if u_scale is None else u_scale
    v_scale = _ones((kcl, 1, r)) if v_scale is None else v_scale
    bs = _pick_block(S, block_s)
    grid = (Bt, Kv, S // bs)
    qg = q.reshape(Bt, Kv, G, hd)
    out_specs, out_shape = _attn_outs(Bt, Kv, G, hd, d_out, q.dtype)
    out, l, m, delta = pl.pallas_call(
        _fused_jd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, ids, ci, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, ids, ci, kl: (b, s, h)),
                pl.BlockSpec(_kv_block(bs, hd),
                             lambda b, h, s, ids, ci, kl: (b, s, h)),
                pl.BlockSpec((1, G * hd, r),
                             lambda b, h, s, ids, ci, kl: (ci[b], h, 0)),
                pl.BlockSpec((1, 1, r),
                             lambda b, h, s, ids, ci, kl: (ci[b], 0, 0)),
                _jd_sigma_spec(sigma, 0),
                pl.BlockSpec((1, d_out, r),
                             lambda b, h, s, ids, ci, kl: (ci[b], 0, 0)),
                pl.BlockSpec((1, d_out, 1),
                             lambda b, h, s, ids, ci, kl: (ci[b], 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=_scratch(G, hd, r),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(ids, cids, kv_len, qg, _flat_kv(k), _flat_kv(v), V, v_scale, sigma, U,
      u_scale)
    del l, m
    return out.reshape(Bt, H, hd), delta[:, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_decode_jd_paged(q: Array, k_pages: Array, v_pages: Array,
                          page_table: Array, kv_len: Array, ids: Array,
                          U: Array, V: Array, sigma: Array,
                          cluster_of: Array,
                          u_scale: Array | None = None,
                          v_scale: Array | None = None, *,
                          interpret: bool | None = None):
    """Paged-KV variant of :func:`fused_decode_jd`."""
    Bt, H, hd = q.shape
    page_t, Kv = k_pages.shape[1], k_pages.shape[2]
    n_blocks = page_table.shape[1]
    G = H // Kv
    kcl, _, r = V.shape
    d_out = U.shape[1]
    cids = cluster_of[ids].astype(jnp.int32)
    sigma = _jd_sigma(sigma)
    u_scale = _ones((kcl, d_out, 1)) if u_scale is None else u_scale
    v_scale = _ones((kcl, 1, r)) if v_scale is None else v_scale
    grid = (Bt, Kv, n_blocks)
    qg = q.reshape(Bt, Kv, G, hd)
    out_specs, out_shape = _attn_outs(Bt, Kv, G, hd, d_out, q.dtype)
    out, l, m, delta = pl.pallas_call(
        _fused_jd_paged_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, hd),
                             lambda b, h, s, pt, ids, ci, kl: (b, h, 0, 0)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, ids, ci, kl:
                             (pt[b, s], 0, h)),
                pl.BlockSpec(_kv_block(page_t, hd),
                             lambda b, h, s, pt, ids, ci, kl:
                             (pt[b, s], 0, h)),
                pl.BlockSpec((1, G * hd, r),
                             lambda b, h, s, pt, ids, ci, kl: (ci[b], h, 0)),
                pl.BlockSpec((1, 1, r),
                             lambda b, h, s, pt, ids, ci, kl: (ci[b], 0, 0)),
                _jd_sigma_spec(sigma, 1),
                pl.BlockSpec((1, d_out, r),
                             lambda b, h, s, pt, ids, ci, kl: (ci[b], 0, 0)),
                pl.BlockSpec((1, d_out, 1),
                             lambda b, h, s, pt, ids, ci, kl: (ci[b], 0, 0)),
            ],
            out_specs=out_specs,
            scratch_shapes=_scratch(G, hd, r),
        ),
        out_shape=out_shape,
        interpret=_interpret(interpret),
    )(page_table, ids, cids, kv_len, qg, _flat_kv(k_pages),
      _flat_kv(v_pages), V, v_scale,
      sigma, U, u_scale)
    del l, m
    return out.reshape(Bt, H, hd), delta[:, 0]
