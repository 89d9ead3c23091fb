"""Distributed attention collectives.

``seq_sharded_decode_attention``: FlashDecoding-style decode over a KV cache
whose *sequence* dimension is sharded across the model axis (the layout the
framework falls back to when KV heads don't divide the TP degree — most GQA
archs at TP16).  Each shard computes partial attention over its KV slice with
online-softmax stats (m, l, o); shards merge with pmax/psum instead of
all-gathering the cache.  Beyond-paper optimization recorded in §Perf.

On TPU the per-shard inner loop is `kernels/flash_decode.py`; the jnp path
below is used on CPU and in the dry-run.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


Array = jax.Array
NEG_INF = -1e30


def _partial_decode(q, k, v, start, kv_len):
    """Partial attention over a KV slice.  q: (B,1,H,hd); k/v: (B,S_loc,Kv,hd);
    global positions are start + arange(S_loc); valid when < kv_len.
    Returns (o (B,Kv,G,hd), l (B,Kv,G), m (B,Kv,G)) in fp32."""
    B, _, H, hd = q.shape
    S, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q[:, 0].reshape(B, Kv, G, hd).astype(jnp.float32) * (hd ** -0.5)
    logits = jnp.einsum("bkgh,bskh->bkgs", qg, k.astype(jnp.float32))
    pos = start + jnp.arange(S)
    valid = pos[None, :] < jnp.reshape(kv_len, (-1, 1))
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    m = logits.max(axis=-1)
    p = jnp.exp(logits - m[..., None])
    p = jnp.where(valid[:, None, None, :], p, 0.0)
    l = p.sum(axis=-1)
    o = jnp.einsum("bkgs,bskh->bkgh", p, v.astype(jnp.float32))
    return o, l, m


def seq_sharded_decode_attention(q: Array, keys: Array, vals: Array,
                                 kv_len: Array, mesh,
                                 axis: str = "model") -> Array:
    """q: (B,1,H,hd) replicated over `axis`; keys/vals: (B,S,Kv,hd) sharded on
    S over `axis`; kv_len: (B,).  Returns (B,1,H,hd)."""
    B, _, H, hd = q.shape
    Kv = keys.shape[2]
    batch_axes = tuple(n for n in mesh.axis_names if n != axis)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.shape[a]
    if n_b <= 1 or B % n_b:
        bspec = None

    def body(q_l, k_l, v_l, kvl_l):
        r = jax.lax.axis_index(axis)
        S_loc = k_l.shape[1]
        o, l, m = _partial_decode(q_l, k_l, v_l, r * S_loc, kvl_l)
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g) * l                      # (B,Kv,G)
        o_sum = jax.lax.psum(o * w[..., None], axis)
        l_sum = jax.lax.psum(w, axis)
        out = o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
        return out.reshape(q_l.shape[0], 1, H, hd).astype(q_l.dtype)

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                  P(bspec, axis, None, None), P(bspec)),
        out_specs=P(bspec, None, None, None))(q, keys, vals, kv_len)


def seq_sharded_decode_step(q: Array, cache_k: Array, cache_v: Array,
                            k_new: Array, v_new: Array, idx: Array,
                            mesh, axis: str = "model"):
    """Fused cache-update + partial attention + softmax merge, all inside one
    shard_map so the S-sharded cache never gets resharded (the baseline's
    'involuntary full rematerialization' f32 copies — §Perf cell 3).

    q/k_new/v_new: (B,1,H|Kv,hd) replicated over `axis`; cache_k/v:
    (B,S,Kv,hd) sharded on S; idx: (B,) or scalar current lengths.
    Returns (out (B,1,H,hd), new_cache_k, new_cache_v)."""
    B, _, H, hd = q.shape
    batch_axes = tuple(n for n in mesh.axis_names if n != axis)
    bspec = batch_axes if len(batch_axes) > 1 else (
        batch_axes[0] if batch_axes else None)
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.shape[a]
    if n_b <= 1 or B % n_b:
        bspec = None
    idx_vec = idx if jnp.ndim(idx) == 1 else jnp.full((B,), idx, jnp.int32)

    def body(q_l, ck, cv, kn, vn, idx_l):
        r = jax.lax.axis_index(axis)
        Bl, S_loc = ck.shape[0], ck.shape[1]
        start = r * S_loc
        pos = idx_l - start                              # (B,) local write pos
        ok = (pos >= 0) & (pos < S_loc)
        safe = jnp.clip(pos, 0, S_loc - 1)
        rows = jnp.arange(Bl)
        old_k = ck[rows, safe]
        old_v = cv[rows, safe]
        k_w = jnp.where(ok[:, None, None], kn[:, 0].astype(ck.dtype), old_k)
        v_w = jnp.where(ok[:, None, None], vn[:, 0].astype(cv.dtype), old_v)
        ck = ck.at[rows, safe].set(k_w)
        cv = cv.at[rows, safe].set(v_w)
        o, l, m = _partial_decode(q_l, ck, cv, start, idx_l + 1)
        m_g = jax.lax.pmax(m, axis)
        w = jnp.exp(m - m_g) * l
        o_sum = jax.lax.psum(o * w[..., None], axis)
        l_sum = jax.lax.psum(w, axis)
        out = o_sum / jnp.maximum(l_sum, 1e-30)[..., None]
        return out.reshape(Bl, 1, H, hd).astype(q_l.dtype), ck, cv

    return jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                  P(bspec, axis, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None), P(bspec)),
        out_specs=(P(bspec, None, None, None), P(bspec, axis, None, None),
                   P(bspec, axis, None, None)))(
        q, cache_k, cache_v, k_new, v_new, idx_vec)
