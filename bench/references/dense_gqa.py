"""Plain float32 reference of a dense decoder with grouped-query attention
and per-request adapters on q/k/v/o, written from the published layer
equations of Mistral-7B and Qwen3 (Hugging Face ``modeling_mistral.py`` /
``modeling_qwen3.py``).  It imports nothing of the program under test.

    h = E[tokens]
    per layer:  a = RMSNorm(h)
                q, k, v = a Wq + dq(a), a Wk + dk(a), a Wv + dv(a)
                q, k = RMSNorm_per_head(q), RMSNorm_per_head(k)   (Qwen3 only)
                q, k = RoPE(q), RoPE(k)            (rotate-half, base theta)
                h = h + o Wo + do(o),  o = softmax(q k^T / sqrt(hd), causal) v
                h = h + (silu(m Wg) * m Wu) Wd,  m = RMSNorm(h)
    logits = RMSNorm(h) W_out                     (W_out = E^T when tied)

Adapter deltas: raw LoRA d(x) = (x A^T) B^T; jd d(x) = ((x V) Sigma) U^T
with the adapter's Sigma and its cluster's shared U, V.

Every matrix product runs in float32 at ``highest`` precision.  With
``quant="fp8"`` every linear layer instead takes float8 (e4m3) inputs:
weights scaled per output channel, activations per token, the products
accumulated in float32 -- the precision step below the configuration's
bfloat16, used as the control of the comparison.

The parameter trees are read by key: ``embed/{embed,unembed,final_norm}``
and ``layers/{ln1,ln2,attn/{wq,wk,wv,wo,q_norm,k_norm},mlp/{w_gate,w_up,
w_down}}``, each layer leaf stacked on a leading layer axis.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0       # largest finite float8_e4m3fn


def _q8(a, axes):
    """Round to float8 e4m3 with one scale per slice over ``axes``."""
    s = jnp.max(jnp.abs(a), axis=axes, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (a / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(spec, x, w, quant, x_axes, w_axes):
    """einsum in float32; under fp8, x is rounded per slice over its
    contracted ``x_axes`` and w per output channel (over ``w_axes``)."""
    x = x.astype(F32)
    w = w.astype(F32)
    if quant == "fp8":
        x, w = _q8(x, x_axes), _q8(w, w_axes)
    return jnp.einsum(spec, x, w, precision=HI)


def _rms(x, scale, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _rope(x, positions, theta):
    """x: (n, T, heads, hd); rotate-half RoPE (the Hugging Face layout)."""
    hd = x.shape[-1]
    half = hd // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=F32) / half))
    ang = positions.astype(F32)[:, None] * inv            # (T, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _plus_delta(y, lora, t, x, ids, mode, quant):
    """y plus the per-request adapter delta of target ``t`` on x
    (n, T, d_in); y itself where no adapter targets ``t``."""
    if lora is None or t not in lora:
        return y
    return y + _delta(lora[t], x, ids, mode, quant).reshape(y.shape)


def _delta(p, x, ids, mode, quant):
    if mode == "lora":
        A = p["A"][ids]                                   # (n, r, d_in)
        B = p["B"][ids]                                   # (n, d_out, r)
        h = _mm("ntd,nrd->ntr", x, A, quant, (-1,), (-1,))
        return _mm("ntr,nor->nto", h, B, quant, (-1,), (-1,))
    cid = p["cluster_of"][ids]
    V, U, S = p["V"][cid], p["U"][cid], p["sigma"][ids]
    h = _mm("ntd,ndr->ntr", x, V, quant, (-1,), (-2,))
    h = _mm("ntr,nrq->ntq", h, S, quant, (-1,), (-2,))
    return _mm("ntr,nor->nto", h, U, quant, (-1,), (-1,))


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _layer(layers, lora, li, x, ids, *, arch, quant):
    """One decoder layer on x (n, T, d) float32 for every request at once."""
    mode, eps, theta, qk_norm = arch
    p = jax.tree.map(lambda a: a[li], layers)
    lo = (jax.tree.map(lambda a: a[li], lora) if lora is not None else None)
    n, T, d = x.shape
    at = p["attn"]
    _, H, hd = at["wq"].shape
    Kv = at["wk"].shape[1]
    G = H // Kv
    pos = jnp.arange(T)

    a = _rms(x, p["ln1"], eps)
    q = _mm("ntd,dhk->nthk", a, at["wq"], quant, (-1,), (0,))
    k = _mm("ntd,dhk->nthk", a, at["wk"], quant, (-1,), (0,))
    v = _mm("ntd,dhk->nthk", a, at["wv"], quant, (-1,), (0,))
    q = _plus_delta(q, lo, "q", a, ids, mode, quant)
    k = _plus_delta(k, lo, "k", a, ids, mode, quant)
    v = _plus_delta(v, lo, "v", a, ids, mode, quant)
    if qk_norm:
        q = _rms(q, at["q_norm"], eps)
        k = _rms(k, at["k_norm"], eps)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)

    qg = q.reshape(n, T, Kv, G, hd) * hd ** -0.5
    s = jnp.einsum("ntkgh,nskh->nkgts", qg, k, precision=HI)
    causal = pos[None, :] <= pos[:, None]
    s = jnp.where(causal, s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("nkgts,nskh->ntkgh", w, v, precision=HI).reshape(n, T, H * hd)

    y = _mm("ntf,fd->ntd", o, at["wo"].reshape(H * hd, d), quant, (-1,), (0,))
    x = x + _plus_delta(y, lo, "o", o, ids, mode, quant)

    m = _rms(x, p["ln2"], eps)
    mlp = p["mlp"]
    g = _mm("ntd,df->ntf", m, mlp["w_gate"], quant, (-1,), (0,))
    u = _mm("ntd,df->ntf", m, mlp["w_up"], quant, (-1,), (0,))
    return x + _mm("ntf,fd->ntd", jax.nn.silu(g) * u, mlp["w_down"], quant,
                   (-1,), (0,))


@functools.partial(jax.jit, static_argnames=("vocab", "eps", "tied", "quant"))
def _head(embed, h, *, vocab, eps, tied, quant):
    """Logits over the real vocabulary for h (n, P, d)."""
    hn = _rms(h, embed["final_norm"], eps)
    if tied:
        return _mm("npd,vd->npv", hn, embed["embed"][:vocab], quant,
                   (-1,), (-1,))
    return _mm("npd,dv->npv", hn, embed["unembed"][:, :vocab], quant,
               (-1,), (0,))


def logits(params: Dict, adapters: Dict | None, conf: Dict, mode: str,
           tokens, ids, first: int, quant: str | None = None) -> jax.Array:
    """Reference logits (n, T - first, vocab) float32 at positions
    ``first .. T-1`` of ``tokens`` (n, T), request i served by adapter
    ``ids[i]``."""
    arch = (mode, float(conf["rms_norm_eps"]), float(conf["rope_theta"]),
            bool(conf["program"]["qk_norm"]))
    tokens = jnp.asarray(tokens)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"]["embed"][tokens].astype(F32)
    lora = adapters["layers"] if adapters is not None else None
    for li in range(conf["num_hidden_layers"]):
        x = _layer(params["layers"], lora, jnp.int32(li), x, ids,
                   arch=arch, quant=quant)
    return _head(params["embed"], x[:, first:], vocab=int(conf["vocab_size"]),
                 eps=arch[1], tied=bool(conf["tie_word_embeddings"]),
                 quant=quant)
