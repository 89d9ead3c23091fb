"""Dense transformer building blocks: norms, RoPE, GQA attention (qk-norm /
qkv-bias / sliding-window / chunked-flash), SwiGLU MLP, embeddings, and
memory-safe cross-entropy.

All functions are pure; parameters are nested dicts produced by the
``*_defs`` companions (see :mod:`repro.models.param`).  Activations carry
logical sharding constraints so the same code lowers on any mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain, current_mesh
from repro.models.param import ParamDef
from repro.models import lora as lora_mod

Array = jax.Array
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# norms & rope
# ---------------------------------------------------------------------------


def rms_norm(x: Array, scale: Array, eps: float = 1e-5) -> Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(dt)


def rope_tables(positions: Array, head_dim: int, theta: float) -> Tuple[Array, Array]:
    """cos/sin tables for given integer positions. positions: (...,S)."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    angles = positions.astype(jnp.float32)[..., None] * freqs  # (...,S,half)
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: Array, cos: Array, sin: Array) -> Array:
    """x: (B, S, H, hd); cos/sin: (B, S, half) or (S, half)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    dt = x.dtype
    x1f, x2f = x1.astype(jnp.float32), x2.astype(jnp.float32)
    return jnp.concatenate(
        [x1f * cos - x2f * sin, x2f * cos + x1f * sin], axis=-1).astype(dt)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------


def attention_defs(cfg: ModelConfig, kv_heads: Optional[int] = None) -> Dict:
    d, H = cfg.d_model, cfg.num_heads
    Kv = kv_heads if kv_heads is not None else cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    defs = {
        "wq": ParamDef((d, H, hd), ("d_model", "heads", "head_dim")),
        "wk": ParamDef((d, Kv, hd), ("d_model", "kv_heads", "head_dim")),
        "wv": ParamDef((d, Kv, hd), ("d_model", "kv_heads", "head_dim")),
        "wo": ParamDef((H, hd, d), ("heads", "head_dim", "d_model")),
    }
    if cfg.qkv_bias:
        defs["bq"] = ParamDef((H, hd), ("heads", "head_dim"), init="zeros")
        defs["bk"] = ParamDef((Kv, hd), ("kv_heads", "head_dim"), init="zeros")
        defs["bv"] = ParamDef((Kv, hd), ("kv_heads", "head_dim"), init="zeros")
    if cfg.qk_norm:
        defs["q_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
        defs["k_norm"] = ParamDef((hd,), ("head_dim",), init="ones")
    return defs


@jax.named_scope("qkv")
def _qkv(p: Dict, x: Array, cfg: ModelConfig, lora_ctx) -> Tuple[Array, Array, Array]:
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if lora_ctx is not None:
        with jax.named_scope("adapter_qkv"):
            q = lora_mod.apply(lora_ctx, "q", x, q)
            k = lora_mod.apply(lora_ctx, "k", x, k)
            v = lora_mod.apply(lora_ctx, "v", x, v)
    if cfg.qkv_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def _gqa_logits(q: Array, k: Array) -> Array:
    """q: (B,Sq,Kv,G,hd), k: (B,Skv,Kv,hd) -> (B,Kv,G,Sq,Skv) fp32."""
    return jnp.einsum("bqkgh,bskh->bkgqs", q.astype(jnp.float32),
                      k.astype(jnp.float32))


def naive_attention(q: Array, k: Array, v: Array, *, causal: bool,
                    q_offset: Array | int = 0,
                    kv_len: Optional[Array] = None,
                    sliding_window: int = 0) -> Array:
    """Reference attention. q: (B,Sq,H,hd); k,v: (B,Skv,Kv,hd)."""
    B, Sq, H, hd = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    qg = q.reshape(B, Sq, Kv, G, hd) * (hd ** -0.5)
    logits = _gqa_logits(qg, k)  # (B,Kv,G,Sq,Skv)
    q_off = jnp.asarray(q_offset)
    kpos = jnp.arange(Skv)
    if q_off.ndim == 0:
        qpos = jnp.arange(Sq) + q_off
        mask = jnp.ones((1, Sq, Skv), dtype=bool)
        qp = qpos[None]
    else:  # per-batch offsets (continuous batching, ragged slots)
        qp = q_off[:, None] + jnp.arange(Sq)[None]       # (B, Sq)
        mask = jnp.ones((B, Sq, Skv), dtype=bool)
    if causal:
        mask &= kpos[None, None, :] <= qp[:, :, None]
    if sliding_window:
        mask &= kpos[None, None, :] > qp[:, :, None] - sliding_window
    if kv_len is not None:
        kl = jnp.asarray(kv_len)
        kl = kl[:, None, None] if kl.ndim == 1 else kl
        mask &= kpos[None, None, :] < kl
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgqs,bskh->bqkgh", w, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def chunked_attention(q: Array, k: Array, v: Array, *, causal: bool,
                      chunk_q: int, chunk_kv: int,
                      q_offset: Array | int = 0,
                      kv_len: Optional[Array] = None,
                      sliding_window: int = 0) -> Array:
    """Flash-style online-softmax attention in pure jnp (scan over chunks).

    Memory is O(chunk_q * chunk_kv) per (batch, head) instead of O(Sq * Skv).
    """
    B, Sq, H, hd = q.shape
    Skv, Kv = k.shape[1], k.shape[2]
    G = H // Kv
    cq = min(chunk_q, Sq)
    ckv = min(chunk_kv, Skv)
    if Sq % cq or Skv % ckv:
        return naive_attention(q, k, v, causal=causal, q_offset=q_offset,
                               kv_len=kv_len, sliding_window=sliding_window)
    nq, nkv = Sq // cq, Skv // ckv
    qg = (q.reshape(B, nq, cq, Kv, G, hd) * (hd ** -0.5)).astype(jnp.float32)
    ks = k.reshape(B, nkv, ckv, Kv, hd).astype(jnp.float32)
    vs = v.reshape(B, nkv, ckv, Kv, hd).astype(jnp.float32)

    def q_block(iq, q_i):
        # q_i: (B, cq, Kv, G, hd)
        qpos = iq * cq + jnp.arange(cq) + q_offset

        def kv_block(carry, ikv):
            m, l, acc = carry
            k_j = jax.lax.dynamic_index_in_dim(ks, ikv, 1, keepdims=False)
            v_j = jax.lax.dynamic_index_in_dim(vs, ikv, 1, keepdims=False)
            logits = jnp.einsum("bqkgh,bskh->bkgqs", q_i, k_j)
            kpos = ikv * ckv + jnp.arange(ckv)
            mask = jnp.ones((cq, ckv), dtype=bool)
            if causal:
                mask &= kpos[None, :] <= qpos[:, None]
            if sliding_window:
                mask &= kpos[None, :] > qpos[:, None] - sliding_window
            if kv_len is not None:
                mask &= kpos[None, :] < kv_len
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            pv = jnp.einsum("bkgqs,bskh->bkgqh", p, v_j)
            acc_new = acc * alpha[..., None] + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, Kv, G, cq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, Kv, G, cq), jnp.float32)
        a0 = jnp.zeros((B, Kv, G, cq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_block, (m0, l0, a0), jnp.arange(nkv))
        out = acc / jnp.maximum(l, 1e-30)[..., None]     # (B,Kv,G,cq,hd)
        return jnp.transpose(out, (0, 3, 1, 2, 4))       # (B,cq,Kv,G,hd)

    outs = jax.vmap(q_block, in_axes=(0, 1), out_axes=1)(jnp.arange(nq), qg)
    return outs.reshape(B, Sq, H, hd).astype(q.dtype)


def _two_part_decode_attention(q, cache_k, cache_v, k_new, v_new, idx):
    """Decode attention over (old cache) + (current token) without writing
    the cache first.  q/k_new/v_new: (B,1,H|Kv,hd); cache: (B,S,Kv,hd)."""
    B, _, H, hd = q.shape
    S, Kv = cache_k.shape[1], cache_k.shape[2]
    G = H // Kv
    qg = (q[:, 0].reshape(B, Kv, G, hd) * (hd ** -0.5)).astype(jnp.float32)
    logits_c = jnp.einsum("bkgh,bskh->bkgs", qg,
                          cache_k.astype(jnp.float32))        # (B,Kv,G,S)
    kl = idx if jnp.ndim(idx) == 1 else jnp.full((B,), idx, jnp.int32)
    valid = jnp.arange(S)[None, :] < kl[:, None]
    logits_c = jnp.where(valid[:, None, None, :], logits_c, NEG_INF)
    logit_s = jnp.einsum("bkgh,bkh->bkg", qg,
                         k_new[:, 0].astype(jnp.float32))[..., None]
    m = jnp.maximum(logits_c.max(-1, keepdims=True), logit_s)
    w_c = jnp.exp(logits_c - m)
    w_c = jnp.where(valid[:, None, None, :], w_c, 0.0)
    w_s = jnp.exp(logit_s - m)
    denom = w_c.sum(-1, keepdims=True) + w_s
    out = jnp.einsum("bkgs,bskh->bkgh", w_c, cache_v.astype(jnp.float32))
    out = out + w_s * v_new[:, 0].astype(jnp.float32).reshape(B, Kv, 1, hd)
    out = out / jnp.maximum(denom, 1e-30)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


@dataclasses.dataclass
class KVCache:
    """Static-size KV cache. k/v: (B, S_max, Kv, hd); index: scalar int32."""
    k: Array
    v: Array
    index: Array

    @staticmethod
    def zeros(batch: int, s_max: int, kv_heads: int, head_dim: int,
              dtype=jnp.bfloat16) -> "KVCache":
        return KVCache(
            k=jnp.zeros((batch, s_max, kv_heads, head_dim), dtype),
            v=jnp.zeros((batch, s_max, kv_heads, head_dim), dtype),
            index=jnp.zeros((), jnp.int32))

    @staticmethod
    def abstract(batch: int, s_max: int, kv_heads: int, head_dim: int,
                 dtype=jnp.bfloat16) -> "KVCache":
        return KVCache(
            k=jax.ShapeDtypeStruct((batch, s_max, kv_heads, head_dim), dtype),
            v=jax.ShapeDtypeStruct((batch, s_max, kv_heads, head_dim), dtype),
            index=jax.ShapeDtypeStruct((), jnp.int32))


jax.tree_util.register_dataclass(KVCache, ["k", "v", "index"], [])


def attention_fwd(p: Dict, x: Array, cfg: ModelConfig, *,
                  positions: Array,
                  mode: str = "train",            # train | prefill | decode
                  cache: Optional[KVCache] = None,
                  lora_ctx=None,
                  causal: bool = True) -> Tuple[Array, Optional[KVCache]]:
    """Self-attention over x; updates cache in prefill/decode modes."""
    B, S, _ = x.shape
    hd = cfg.resolved_head_dim
    q, k, v = _qkv(p, x, cfg, lora_ctx)
    cos, sin = rope_tables(positions, hd, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    mesh = current_mesh()
    tp = mesh.shape.get("model", 1) if mesh is not None else 1
    # context-parallel fallback (§Perf hillclimb): when heads don't divide
    # the TP degree (granite 24H / whisper 12H at TP16), shard the attention
    # compute over SEQUENCE instead of replicating it on every model rank.
    use_cp = (cfg.attn_cp_fallback and tp > 1 and cfg.num_heads % tp != 0
              and mode != "decode" and S % tp == 0)
    if use_cp:
        q = constrain(q, "batch", "seq_sp", "heads", "head_dim")
        v = constrain(v, "batch", "seq", "kv_heads", "head_dim")
    else:
        q = constrain(q, "batch", "seq", "heads", "head_dim")
    k = constrain(k, "batch", "seq", "kv_heads", "head_dim")

    new_cache = None
    if mode == "train":
        keys, vals = k, v
        kv_len = None
        q_offset = 0
    elif mode == "prefill":
        assert cache is not None
        keys = jax.lax.dynamic_update_slice_in_dim(cache.k, k.astype(cache.k.dtype), 0, axis=1)
        vals = jax.lax.dynamic_update_slice_in_dim(cache.v, v.astype(cache.v.dtype), 0, axis=1)
        new_cache = KVCache(k=keys, v=vals, index=jnp.asarray(S, jnp.int32))
        keys, vals, kv_len, q_offset = k, v, None, 0   # attend within prompt only
    elif mode == "decode":
        assert cache is not None
        idx = cache.index
        S_max0 = cache.k.shape[1]
        use_seq_decode0 = (cfg.decode_attn == "seq_shard" and S == 1
                           and mesh is not None and tp > 1
                           and cfg.num_kv_heads % tp != 0
                           and S_max0 % tp == 0)
        if use_seq_decode0:
            # fused update+attention: the S-sharded cache never leaves its
            # shards (avoids per-layer full-cache reshard copies; §Perf)
            from repro.distributed.collectives import seq_sharded_decode_step
            out, keys, vals = seq_sharded_decode_step(
                q, cache.k, cache.v, k, v, idx, mesh)
            new_cache = KVCache(k=keys, v=vals, index=idx + S)
            out = constrain(out, "batch", "seq", "heads", "head_dim")
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            if lora_ctx is not None:
                y = lora_mod.apply(lora_ctx, "o", out.reshape(B, S, -1), y)
            return constrain(y, "batch", "seq", "d_model"), new_cache
        if cfg.decode_attn == "lazy" and S == 1:
            # lazy cache write (§Perf): attend to the OLD cache + the new
            # token as a two-part softmax; emit only the new (k, v) token.
            # The caller splices all layers' new tokens into the stacked
            # cache with ONE tiny dynamic-update-slice per step, instead of
            # rewriting every layer's full cache slice through scan ys.
            out = _two_part_decode_attention(q, cache.k, cache.v, k, v, idx)
            new_cache = KVCache(k=k.astype(cache.k.dtype),
                                v=v.astype(cache.v.dtype), index=idx + S)
            out = constrain(out, "batch", "seq", "heads", "head_dim")
            y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            if lora_ctx is not None:
                y = lora_mod.apply(lora_ctx, "o", out.reshape(B, S, -1), y)
            return constrain(y, "batch", "seq", "d_model"), new_cache
        if jnp.ndim(idx) == 0:
            keys = jax.lax.dynamic_update_slice_in_dim(
                cache.k, k.astype(cache.k.dtype), idx, axis=1)
            vals = jax.lax.dynamic_update_slice_in_dim(
                cache.v, v.astype(cache.v.dtype), idx, axis=1)
        else:  # per-row positions (S == 1)
            rows = jnp.arange(B)
            keys = cache.k.at[rows, idx].set(k[:, 0].astype(cache.k.dtype))
            vals = cache.v.at[rows, idx].set(v[:, 0].astype(cache.v.dtype))
        new_cache = KVCache(k=keys, v=vals, index=idx + S)
        kv_len = idx + S
        q_offset = idx
    else:
        raise ValueError(mode)

    keys = constrain(keys, "batch", "kv_seq", "kv_heads", "head_dim") \
        if mode == "decode" else keys
    vals = constrain(vals, "batch", "kv_seq", "kv_heads", "head_dim") \
        if mode == "decode" else vals

    use_chunks = cfg.attn_chunk_q > 0 and mode != "decode" and S > cfg.attn_chunk_q
    if use_chunks:
        out = chunked_attention(q, keys, vals, causal=causal,
                                chunk_q=cfg.attn_chunk_q,
                                chunk_kv=cfg.attn_chunk_kv,
                                q_offset=q_offset, kv_len=kv_len,
                                sliding_window=cfg.sliding_window)
    else:
        out = naive_attention(q, keys, vals, causal=causal, q_offset=q_offset,
                              kv_len=kv_len, sliding_window=cfg.sliding_window)
    out = constrain(out, "batch", "seq", "heads", "head_dim")
    y = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    if lora_ctx is not None:
        y = lora_mod.apply(lora_ctx, "o", out.reshape(B, S, -1), y)
    return constrain(y, "batch", "seq", "d_model"), new_cache


def cross_attention_fwd(p: Dict, x: Array, memory: Array, cfg: ModelConfig,
                        lora_ctx=None) -> Array:
    """Encoder-decoder cross attention (no rope, no causal mask)."""
    B, S, _ = x.shape
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", memory, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", memory, p["wv"])
    if lora_ctx is not None:
        q = lora_mod.apply(lora_ctx, "xq", x, q)
        k = lora_mod.apply(lora_ctx, "xk", memory, k)
        v = lora_mod.apply(lora_ctx, "xv", memory, v)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    out = naive_attention(q, k, v, causal=False)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"])


# ---------------------------------------------------------------------------
# mlp
# ---------------------------------------------------------------------------


def mlp_defs(d_model: int, d_ff: int) -> Dict:
    return {
        "w_gate": ParamDef((d_model, d_ff), ("d_model", "d_ff")),
        "w_up": ParamDef((d_model, d_ff), ("d_model", "d_ff")),
        "w_down": ParamDef((d_ff, d_model), ("d_ff", "d_model")),
    }


def mlp_fwd(p: Dict, x: Array) -> Array:
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    h = constrain(h, "batch", "seq", "d_ff")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


# ---------------------------------------------------------------------------
# embeddings & losses
# ---------------------------------------------------------------------------


def embedding_defs(cfg: ModelConfig) -> Dict:
    Vp, d = cfg.padded_vocab, cfg.d_model
    defs = {
        "embed": ParamDef((Vp, d), ("vocab", "d_model"), scale=0.02),
        "final_norm": ParamDef((d,), ("d_model",), init="ones"),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((d, Vp), ("d_model", "vocab"), scale=0.02)
    return defs


def embed_tokens(p: Dict, tokens: Array) -> Array:
    return constrain(p["embed"][tokens], "batch", "seq", "d_model")


def _unembed_matrix(p: Dict, cfg: ModelConfig) -> Array:
    return p["embed"].T if cfg.tie_embeddings else p["unembed"]


def logits_fwd(p: Dict, h: Array, cfg: ModelConfig) -> Array:
    h = rms_norm(h, p["final_norm"], cfg.norm_eps)
    logits = jnp.einsum("bsd,dv->bsv", h, _unembed_matrix(p, cfg))
    return constrain(logits, "batch", "seq", "vocab")


def cross_entropy(p: Dict, h: Array, targets: Array, cfg: ModelConfig,
                  mask: Optional[Array] = None) -> Array:
    """Token-mean CE.  With cfg.logits_chunk_vocab > 0, never materializes the
    full (B, S, V) logits: scans vocab chunks with an online logsumexp."""
    h = rms_norm(h, p["final_norm"], cfg.norm_eps)
    W = _unembed_matrix(p, cfg)                   # (d, Vp)
    Vp = W.shape[1]
    tgt = jnp.clip(targets, 0, Vp - 1)
    if mask is None:
        mask = (targets >= 0).astype(jnp.float32)
    chunk = cfg.logits_chunk_vocab
    if chunk and Vp > chunk:
        # pick the smallest chunk count >= Vp/target that divides Vp
        n = -(-Vp // chunk)
        while Vp % n and n < min(Vp, 4096):
            n += 1
        chunk = Vp // n if Vp % n == 0 else 0
    if chunk and Vp % chunk == 0 and Vp > chunk:
        n = Vp // chunk
        Wc = W.reshape(W.shape[0], n, chunk)

        def body(carry, i):
            m, l = carry
            lg = jnp.einsum("bsd,dv->bsv", h, jax.lax.dynamic_index_in_dim(
                Wc, i, 1, keepdims=False)).astype(jnp.float32)
            m_new = jnp.maximum(m, lg.max(axis=-1))
            l = l * jnp.exp(m - m_new) + jnp.exp(lg - m_new[..., None]).sum(-1)
            return (m_new, l), None

        m0 = jnp.full(h.shape[:2], NEG_INF, jnp.float32)
        l0 = jnp.zeros(h.shape[:2], jnp.float32)
        (m, l), _ = jax.lax.scan(body, (m0, l0), jnp.arange(n))
        lse = m + jnp.log(jnp.maximum(l, 1e-30))
        tgt_logit = jnp.einsum("bsd,bsd->bs", h.astype(jnp.float32),
                               W.T[tgt].astype(jnp.float32))
    else:
        logits = jnp.einsum("bsd,dv->bsv", h, W).astype(jnp.float32)
        logits = constrain(logits, "batch", "seq", "vocab")
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt_logit = jnp.take_along_axis(logits, tgt[..., None], axis=-1)[..., 0]
    nll = (lse - tgt_logit) * mask
    return jnp.sum(nll) / jnp.maximum(jnp.sum(mask), 1.0)
