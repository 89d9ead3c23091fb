"""Plain float32 reference of a mixture-of-experts decoder (DeepSeekMoE,
Hugging Face ``modeling_deepseek.py``): the dense reference's layers for
the leading ``first_k_dense_replace`` layers, then layers whose MLP is

    p = softmax(m W_router);  w, e = top_k(p, num_experts_per_tok)
    w = w / sum(w)                                   (if norm_topk_prob)
    y = sum_{j: e_j held} w_j FFN_{e_j}(m) + FFN_shared(m)

with every FFN ``(silu(m Wg) * m Wu) Wd`` and the shared experts as one FFN
of their summed width.  The router scores all ``program.router_experts``
experts; the sum runs over the choices that fall on the experts held here,
``n_routed_experts`` of them from ``program.expert_offset`` (one chip's
share of an expert-parallel layer; the other chips' part is left out, as
the program leaves it out).  Every held expert is computed for every token
and weighted by its routed weight, zero where not routed.  It imports
nothing of the program under test; the dense reference's helpers compute
attention, norms and adapters, every product at ``highest`` precision.

The parameter trees are read by key: ``dense_layers`` as the dense
reference's ``layers``, and ``layers/{ln1,ln2,attn/{wq,wk,wv,wo},
moe/{router,w_gate,w_up,w_down,shared/{w_gate,w_up,w_down}}}``; the
adapters by the same two stacks.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp

from bench import spec

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
dense = spec.reference("dense_gqa")


def _ffn(p, m, quant):
    g = dense._mm("ntd,df->ntf", m, p["w_gate"], quant, (-1,), (0,))
    u = dense._mm("ntd,df->ntf", m, p["w_up"], quant, (-1,), (0,))
    return dense._mm("ntf,fd->ntd", jax.nn.silu(g) * u, p["w_down"], quant,
                     (-1,), (0,))


def _moe(p, m, top_k, norm_topk, offset, quant):
    probs = jax.nn.softmax(
        dense._mm("ntd,de->nte", m, p["router"], quant, (-1,), (0,)), -1)
    w, e = jax.lax.top_k(probs, top_k)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    held = p["w_gate"].shape[0]
    gate = jnp.sum(jax.nn.one_hot(e - offset, held, dtype=F32)
                   * w[..., None], -2)                       # (n, T, held)
    g = dense._mm("ntd,edf->ntef", m, p["w_gate"], quant, (-1,), (1,))
    u = dense._mm("ntd,edf->ntef", m, p["w_up"], quant, (-1,), (1,))
    y = dense._mm("ntef,efd->nted", jax.nn.silu(g) * u, p["w_down"], quant,
                  (-1,), (1,))
    return jnp.einsum("nted,nte->ntd", y, gate, precision=HI) \
        + _ffn(p["shared"], m, quant)


@functools.partial(jax.jit, static_argnames=("arch", "quant"))
def _moe_layer(layers, lora, li, x, ids, *, arch, quant):
    """One expert layer on x (n, T, d) float32."""
    mode, eps, theta, top_k, norm_topk, offset = arch
    p = jax.tree.map(lambda a: a[li], layers)
    lo = jax.tree.map(lambda a: a[li], lora) if lora is not None else None
    n, T, d = x.shape
    at = p["attn"]
    _, H, hd = at["wq"].shape
    Kv = at["wk"].shape[1]
    pos = jnp.arange(T)

    a = dense._rms(x, p["ln1"], eps)
    qkv = [dense._plus_delta(
        dense._mm("ntd,dhk->nthk", a, at[w], quant, (-1,), (0,)), lo, t, a,
        ids, mode, quant) for w, t in (("wq", "q"), ("wk", "k"), ("wv", "v"))]
    q, k = dense._rope(qkv[0], pos, theta), dense._rope(qkv[1], pos, theta)
    qg = q.reshape(n, T, Kv, H // Kv, hd) * hd ** -0.5
    s = jnp.einsum("ntkgh,nskh->nkgts", qg, k, precision=HI)
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("nkgts,nskh->ntkgh", jax.nn.softmax(s, -1), qkv[2],
                   precision=HI).reshape(n, T, H * hd)
    y = dense._mm("ntf,fd->ntd", o, at["wo"].reshape(H * hd, d), quant,
                  (-1,), (0,))
    x = x + dense._plus_delta(y, lo, "o", o, ids, mode, quant)
    return x + _moe(p["moe"], dense._rms(x, p["ln2"], eps), top_k,
                    norm_topk, offset, quant)


def logits(params: Dict, adapters: Dict | None, conf: Dict, mode: str,
           tokens, ids, first: int, quant: str | None = None) -> jax.Array:
    """Reference logits (n, T - first, vocab) float32 at positions
    ``first .. T-1`` of ``tokens`` (n, T), request i served by adapter
    ``ids[i]``."""
    eps, theta = float(conf["rms_norm_eps"]), float(conf["rope_theta"])
    tokens = jnp.asarray(tokens)
    ids = jnp.asarray(ids, jnp.int32)
    x = params["embed"]["embed"][tokens].astype(F32)
    ad = adapters or {}
    for li in range(conf["first_k_dense_replace"]):
        x = dense._layer(params["dense_layers"], ad.get("dense_layers"),
                         jnp.int32(li), x, ids,
                         arch=(mode, eps, theta, False), quant=quant)
    arch = (mode, eps, theta, int(conf["num_experts_per_tok"]),
            bool(conf["norm_topk_prob"]),
            int(conf["program"]["expert_offset"]))
    for li in range(conf["num_hidden_layers"] - conf["first_k_dense_replace"]):
        x = _moe_layer(params["layers"], ad.get("layers"), jnp.int32(li), x,
                       ids, arch=arch, quant=quant)
    return dense._head(params["embed"], x[:, first:],
                       vocab=int(conf["vocab_size"]), eps=eps,
                       tied=bool(conf["tie_word_embeddings"]), quant=quant)
