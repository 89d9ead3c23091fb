"""Jit'd wrappers dispatching between Pallas kernels and jnp oracles.

``use_pallas='auto'`` picks the Pallas path on TPU backends and interpret
mode in tests; the jnp refs serve CPU execution and the SPMD dry-run (Pallas
TPU kernels do not lower on the forced-host-device CPU backend)."""
from __future__ import annotations


import jax
import jax.numpy as jnp

from . import ref as ref_mod
from .flash_decode import flash_decode as _flash_decode_pallas
from .fused_decode import fused_decode_jd as _fused_jd_pallas
from .fused_decode import fused_decode_lora as _fused_lora_pallas
from .jd_apply import jd_apply as _jd_apply_pallas
from .sgmv import sgmv_expand, sgmv_shrink

Array = jax.Array


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_impl(use_pallas) -> str:
    """'pallas' | 'interpret' | 'ref'."""
    if use_pallas in ("pallas", "interpret", "ref"):
        return use_pallas
    return "pallas" if _on_tpu() else "ref"


def lora_apply(x: Array, A: Array, B: Array, ids: Array, *,
               tile: int = 128, scaling: float = 1.0,
               use_pallas="auto") -> Array:
    """Uncompressed multi-LoRA delta on flattened tokens (the baseline path).

    x: (T, d_in); A: (n, r, d_in); B: (n, d_out, r); ids: (T,)."""
    impl = resolve_impl(use_pallas)
    if impl == "ref":
        return ref_mod.lora_apply_ref(x, A, B, ids, scaling)
    perm, tile_ids, valid = ref_mod.group_tokens_by_adapter(
        ids, A.shape[0], tile)
    xg = x[perm]
    t = sgmv_shrink(xg, A, tile_ids, block_t=tile,
                    interpret=(impl == "interpret"))
    y = sgmv_expand(t.astype(x.dtype), B, tile_ids, block_t=tile,
                    interpret=(impl == "interpret"))
    out = jnp.zeros((x.shape[0], B.shape[1]), x.dtype)
    out = out.at[perm].add(y * valid[:, None].astype(y.dtype))
    return out * scaling


def jd_apply(x: Array, U: Array, V: Array, sigma: Array, cluster_of: Array,
             ids: Array, *, tile: int = 128, use_pallas="auto") -> Array:
    """Compressed (JD) multi-LoRA delta on flattened tokens."""
    impl = resolve_impl(use_pallas)
    if impl == "ref":
        return ref_mod.jd_apply_ref(x, U, V, sigma, cluster_of, ids)
    perm, tile_ids, valid = ref_mod.group_tokens_by_adapter(
        ids, sigma.shape[0], tile)
    xg = x[perm]
    idg = ids[perm]
    tile_cids = cluster_of[tile_ids]
    y = _jd_apply_pallas(xg, U, V, sigma, cluster_of, idg, tile_cids,
                         tile_ids, block_t=tile,
                         interpret=(impl == "interpret"))
    out = jnp.zeros((x.shape[0], U.shape[1]), x.dtype)
    out = out.at[perm].add(y * valid[:, None].astype(y.dtype))
    return out


def decode_attention(q: Array, k: Array, v: Array, kv_len: Array, *,
                     layer=None, window=None, use_pallas="auto") -> Array:
    """Decode attention (one token per sequence).  k/v: (B, S, Kv, hd), or
    the stacked (L, B, S, Kv, hd) cache read at ``layer``; a static
    ``window`` attends the first ``window`` tokens."""
    impl = resolve_impl(use_pallas)
    if impl == "ref":
        return ref_mod.flash_decode_ref(q, k, v, kv_len, layer, window)
    out, _, _ = _flash_decode_pallas(q, k, v, kv_len, layer=layer,
                                     window=window,
                                     interpret=(impl == "interpret"))
    return out


def fused_lora_decode(q: Array, k: Array, v: Array, kv_len: Array,
                      ids: Array, A: Array, B: Array,
                      a_scale=None, b_scale=None, *, layer=None, window=None,
                      use_pallas="auto"):
    """Fused decode attention + per-slot raw-LoRA output delta
    (`fused_decode.fused_decode_lora`): attention and the adapter shrink/
    expand in ONE kernel pass.  k/v, ``layer`` and ``window`` as
    :func:`decode_attention`.  Optional per-channel scales serve int8
    banks from `adapter_quant.py`.  Returns (out (B,H,hd), delta (B,d_out))."""
    impl = resolve_impl(use_pallas)
    if impl == "ref":
        return ref_mod.fused_decode_lora_ref(q, k, v, kv_len, ids, A, B,
                                             a_scale, b_scale, layer, window)
    return _fused_lora_pallas(q, k, v, kv_len, ids, A, B, a_scale, b_scale,
                              layer=layer, window=window,
                              interpret=(impl == "interpret"))


def fused_jd_decode(q: Array, k: Array, v: Array, kv_len: Array, ids: Array,
                    U: Array, V: Array, sigma: Array, cluster_of: Array,
                    u_scale=None, v_scale=None, *, layer=None, window=None,
                    use_pallas="auto"):
    """Fused decode attention + compressed shared-basis output delta
    (`fused_decode.fused_decode_jd`); k/v, ``layer`` and ``window`` as
    :func:`decode_attention`."""
    impl = resolve_impl(use_pallas)
    if impl == "ref":
        return ref_mod.fused_decode_jd_ref(q, k, v, kv_len, ids, U, V,
                                           sigma, cluster_of, u_scale,
                                           v_scale, layer, window)
    return _fused_jd_pallas(q, k, v, kv_len, ids, U, V, sigma, cluster_of,
                            u_scale, v_scale, layer=layer, window=window,
                            interpret=(impl == "interpret"))
