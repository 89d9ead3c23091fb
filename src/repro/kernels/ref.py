"""Pure-jnp oracles for every Pallas kernel (the `ref.py` layer).

Token-level interfaces: the serving engine flattens a continuous batch into
(T, d) tokens with per-token adapter/group metadata.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def sgmv_shrink_ref(x: Array, A: Array, ids: Array) -> Array:
    """y[t] = A[ids[t]] @ x[t].   x: (T, d_in), A: (n, r, d_in) -> (T, r)."""
    return jnp.einsum("trd,td->tr", A[ids].astype(jnp.float32),
                      x.astype(jnp.float32)).astype(x.dtype)


def sgmv_expand_ref(t: Array, B: Array, ids: Array) -> Array:
    """y[i] = B[ids[i]] @ t[i].   t: (T, r), B: (n, d_out, r) -> (T, d_out)."""
    return jnp.einsum("tor,tr->to", B[ids].astype(jnp.float32),
                      t.astype(jnp.float32)).astype(t.dtype)


def lora_apply_ref(x: Array, A: Array, B: Array, ids: Array,
                   scaling: float = 1.0) -> Array:
    """Uncompressed multi-LoRA delta: B[id] @ (A[id] @ x) per token."""
    t = sgmv_shrink_ref(x, A, ids)
    return sgmv_expand_ref(t, B, ids) * scaling


def jd_apply_ref(x: Array, U: Array, V: Array, sigma: Array,
                 cluster_of: Array, ids: Array) -> Array:
    """Compressed (JD) multi-LoRA delta per token.

    x: (T, d_in); U: (k, d_out, r); V: (k, d_in, r);
    sigma: (n, r, r) full or (n, r) diag; cluster_of: (n,); ids: (T,).
    """
    cid = cluster_of[ids]
    Vt = V[cid].astype(jnp.float32)                  # (T, d_in, r)
    Ut = U[cid].astype(jnp.float32)                  # (T, d_out, r)
    t = jnp.einsum("td,tdr->tr", x.astype(jnp.float32), Vt)
    sig = sigma[ids].astype(jnp.float32)
    if sig.ndim == 2:
        t = t * sig
    else:
        t = jnp.einsum("tr,trq->tq", t, sig)
    return jnp.einsum("tq,toq->to", t, Ut).astype(x.dtype)


def sigma_bmm_ref(t: Array, sigma: Array, ids: Array) -> Array:
    """t: (T, r) x sigma[ids]: per-token (r, r) matmul (JD-Full mid stage)."""
    sig = sigma[ids].astype(jnp.float32)
    return jnp.einsum("tr,trq->tq", t.astype(jnp.float32), sig).astype(t.dtype)


def kv_quant_ref(x: Array, bits: int = 8) -> Tuple[Array, Array]:
    """Per-channel symmetric KV quantization oracle.

    x: (T, C) — a KV block, T tokens by C channels.  One f32 scale per
    channel (absmax / qmax); values are round-to-nearest int8 in
    [-qmax, qmax] (int4 values live in int8 storage here — the Pallas
    kernel packs two per byte; see kv_quant.py).
    """
    qmax = {8: 127, 4: 7}[bits]
    absmax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=0, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / qmax, 1.0)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -qmax, qmax)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def kv_dequant_ref(q: Array, scale: Array,
                   out_dtype=jnp.float32) -> Array:
    """Dequantization oracle: values (T, C) int8 x per-channel scales."""
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(out_dtype)


def _layer_window(x: Array, layer: Optional[int] = None,
                 window: Optional[int] = None) -> Array:
    """The (B, window, Kv, hd) K or V a decode kernel attends: layer
    ``layer`` of a stacked (L, B, S, Kv, hd) cache (or ``x`` itself, a
    (B, S, Kv, hd) operand), cut to its first ``window`` tokens."""
    if layer is not None:
        x = x[layer]
    return x if window is None else x[:, :window]


def flash_decode_ref(q: Array, k: Array, v: Array,
                     kv_len: Optional[Array] = None,
                     layer: Optional[int] = None,
                     window: Optional[int] = None) -> Array:
    """Decode attention oracle.  q: (B, H, hd); k/v: (B, S, Kv, hd), or
    the stacked cache read at ``layer`` (:func:`_layer_window`)."""
    k, v = _layer_window(k, layer, window), _layer_window(v, layer, window)
    B, H, hd = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qf = q.reshape(B, Kv, G, hd).astype(jnp.float32) * (hd ** -0.5)
    logits = jnp.einsum("bkgh,bskh->bkgs", qf, k.astype(jnp.float32))
    if kv_len is not None:
        mask = jnp.arange(S)[None, :] < kv_len.reshape(-1, 1)
        logits = jnp.where(mask[:, None, None, :], logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskh->bkgh", w, v.astype(jnp.float32))
    return out.reshape(B, H, hd).astype(q.dtype)


def gather_pages_ref(pages: Array, page_table: Array) -> Array:
    """Materialize a contiguous KV layout from a paged pool.

    pages: (P, page_t, Kv, hd) physical pages; page_table: (B, n_blocks)
    int32 — sequence b's logical block s lives in page ``page_table[b, s]``.
    Returns (B, n_blocks * page_t, Kv, hd): exactly the layout the
    contiguous :func:`flash_decode_ref` / Pallas kernel consume, so the
    gathered-page kernel can be checked against the contiguous oracle.
    """
    B, n_blocks = page_table.shape
    g = pages[page_table]                    # (B, n_blocks, page_t, Kv, hd)
    return g.reshape(B, n_blocks * pages.shape[1], *pages.shape[2:])


def flash_decode_paged_ref(q: Array, k_pages: Array, v_pages: Array,
                           page_table: Array,
                           kv_len: Optional[Array] = None) -> Array:
    """Paged decode-attention oracle: gather to contiguous, then the
    contiguous oracle — the reference the Pallas gathered-page path must
    match bit-for-bit on equal logical content."""
    return flash_decode_ref(q, gather_pages_ref(k_pages, page_table),
                            gather_pages_ref(v_pages, page_table), kv_len)


def group_tokens_by_adapter(ids: Array, n_adapters: int, tile: int
                            ) -> Tuple[Array, Array, Array]:
    """Host-side grouping: sort tokens by adapter and pad each group to a
    multiple of `tile` (the TPU adaptation of Punica's SGMV — see DESIGN.md).

    Returns (perm (T_pad,), tile_ids (T_pad//tile,), valid (T_pad,)):
      - perm: indices into the original token array (arbitrary for padding)
      - tile_ids: adapter id per tile (constant within a tile by construction)
      - valid: 0/1 mask for padding slots.
    Pure numpy-style; runs on host at batch-assembly time (not jitted).
    """
    import numpy as np
    ids_np = np.asarray(ids)
    order = np.argsort(ids_np, kind="stable")
    sorted_ids = ids_np[order]
    perm, valid, tile_ids = [], [], []
    for a in range(n_adapters):
        sel = order[sorted_ids == a]
        if sel.size == 0:
            continue
        pad = (-sel.size) % tile
        perm.extend(sel.tolist() + [int(sel[0])] * pad)
        valid.extend([1] * sel.size + [0] * pad)
        tile_ids.extend([a] * ((sel.size + pad) // tile))
    return (jnp.asarray(perm, jnp.int32), jnp.asarray(tile_ids, jnp.int32),
            jnp.asarray(valid, jnp.int32))


def adapter_quant_ref(w: Array, axis: int = -1) -> Tuple[Array, Array]:
    """Per-output-channel symmetric int8 oracle for adapter/basis banks
    (`adapter_quant.py`): one f32 scale per channel, reduced over the
    matrix's input `axis` (keepdims)."""
    xf = w.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(absmax > 0, absmax / 127.0, 1.0)
    q = jnp.clip(jnp.round(xf / scale), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def adapter_dequant_ref(q: Array, scale: Array,
                        out_dtype=jnp.float32) -> Array:
    return (q.astype(jnp.float32) * scale.astype(jnp.float32)).astype(
        out_dtype)


def _deq(w: Array, scale: Optional[Array]) -> Array:
    wf = w.astype(jnp.float32)
    return wf if scale is None else wf * scale.astype(jnp.float32)


def fused_decode_lora_ref(q: Array, k: Array, v: Array, kv_len, ids: Array,
                          A: Array, B: Array, a_scale=None, b_scale=None,
                          layer: Optional[int] = None,
                          window: Optional[int] = None
                          ) -> Tuple[Array, Array]:
    """Composed oracle for `fused_decode.fused_decode_lora`: decode
    attention, then the per-slot LoRA delta on the flattened (H*hd)
    attention output.  Optional per-channel scales dequantize int8 banks
    (`adapter_quant_ref`); banks with a leading layer axis are read at
    ``layer``.  Returns (out (B,H,hd), delta (B,d_out) f32)."""
    out = flash_decode_ref(q, k, v, kv_len, layer, window)
    if A.ndim == 4:                          # layer-stacked banks
        A, B = A[layer], B[layer]
        a_scale, b_scale = (None if x is None else x[layer]
                            for x in (a_scale, b_scale))
    of = out.reshape(out.shape[0], -1).astype(jnp.float32)
    t = jnp.einsum("bd,brd->br", of, _deq(A, a_scale)[ids])
    delta = jnp.einsum("br,bor->bo", t, _deq(B, b_scale)[ids])
    return out, delta


def fused_decode_jd_ref(q: Array, k: Array, v: Array, kv_len, ids: Array,
                        U: Array, V: Array, sigma: Array, cluster_of: Array,
                        u_scale=None, v_scale=None,
                        layer: Optional[int] = None,
                        window: Optional[int] = None) -> Tuple[Array, Array]:
    """Composed oracle for `fused_decode.fused_decode_jd`: attention, then
    the compressed shared-basis delta (V^T -> Sigma -> U) with per-slot
    sigma and per-cluster bases; layer-stacked banks (4-D U) are read at
    ``layer``."""
    out = flash_decode_ref(q, k, v, kv_len, layer, window)
    if U.ndim == 4:                          # layer-stacked banks
        U, V, sigma, cluster_of = (x[layer] for x in (U, V, sigma,
                                                      cluster_of))
        u_scale, v_scale = (None if x is None else x[layer]
                            for x in (u_scale, v_scale))
    of = out.reshape(out.shape[0], -1).astype(jnp.float32)
    cid = cluster_of[ids]
    t = jnp.einsum("bd,bdr->br", of, _deq(V, v_scale)[cid])
    sig = sigma[ids].astype(jnp.float32)
    if sig.ndim == 2:                        # JD-Diag: (B, r)
        t = t * sig
    else:                                    # JD-Full: (B, r, r)
        t = jnp.einsum("br,brq->bq", t, sig)
    delta = jnp.einsum("br,bor->bo", t, _deq(U, u_scale)[cid])
    return out, delta


def fused_decode_lora_paged_ref(q, k_pages, v_pages, page_table, kv_len,
                                ids, A, B, a_scale=None, b_scale=None):
    """Paged fused oracle: gather pages to contiguous, then the contiguous
    fused oracle (same contract as `flash_decode_paged_ref`)."""
    return fused_decode_lora_ref(
        q, gather_pages_ref(k_pages, page_table),
        gather_pages_ref(v_pages, page_table), kv_len, ids, A, B,
        a_scale, b_scale)


def fused_decode_jd_paged_ref(q, k_pages, v_pages, page_table, kv_len, ids,
                              U, V, sigma, cluster_of,
                              u_scale=None, v_scale=None):
    return fused_decode_jd_ref(
        q, gather_pages_ref(k_pages, page_table),
        gather_pages_ref(v_pages, page_table), kv_len, ids, U, V, sigma,
        cluster_of, u_scale, v_scale)
