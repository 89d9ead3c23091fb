"""Model assembly for all assigned architecture families.

``model_defs(cfg)`` builds the ParamDef tree; ``forward(...)`` runs it in
train / prefill / decode mode with optional LoRA context.  Layers are scanned
(``jax.lax.scan``) with optional remat so the HLO stays compact for 80–90
layer models; hybrid (zamba2) scans groups of SSM layers with a weight-shared
attention block between groups; audio (whisper) runs an encoder stack and a
decoder stack with cross-attention.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import lora as lora_mod
from repro.models.layers import (KVCache, ParamDef, attention_defs,
                                 attention_fwd, cross_attention_fwd,
                                 cross_entropy, embed_tokens, embedding_defs,
                                 logits_fwd, mlp_defs, mlp_fwd, rms_norm)
from repro.models.moe import moe_defs, moe_fwd
from repro.models.param import stacked
from repro.models.ssm import SSMCache, ssm_block_fwd, ssm_defs

Array = jax.Array


# ---------------------------------------------------------------------------
# parameter trees
# ---------------------------------------------------------------------------


def _norm_def(d: int) -> ParamDef:
    return ParamDef((d,), ("d_model",), init="ones")


def _attn_block_defs(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_def(cfg.d_model), "attn": attention_defs(cfg),
            "ln2": _norm_def(cfg.d_model), "mlp": mlp_defs(cfg.d_model, cfg.d_ff)}


def _moe_block_defs(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_def(cfg.d_model), "attn": attention_defs(cfg),
            "ln2": _norm_def(cfg.d_model), "moe": moe_defs(cfg)}


def _ssm_block_defs(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_def(cfg.d_model), "ssm": ssm_defs(cfg)}


def _decoder_block_defs(cfg: ModelConfig) -> Dict:
    return {"ln1": _norm_def(cfg.d_model), "attn": attention_defs(cfg),
            "lnx": _norm_def(cfg.d_model), "xattn": attention_defs(cfg),
            "ln2": _norm_def(cfg.d_model), "mlp": mlp_defs(cfg.d_model, cfg.d_ff)}


def model_defs(cfg: ModelConfig) -> Dict:
    defs: Dict[str, Any] = {"embed": embedding_defs(cfg)}
    L = cfg.num_layers
    if cfg.family == "moe":
        fk = cfg.moe.first_k_dense
        if fk:
            import dataclasses as _dc
            dense_cfg = _dc.replace(cfg, d_ff=cfg.moe.d_ff_dense)
            defs["dense_layers"] = stacked(_attn_block_defs(dense_cfg), fk)
        defs["layers"] = stacked(_moe_block_defs(cfg), L - fk)
    elif cfg.family == "ssm":
        defs["layers"] = stacked(_ssm_block_defs(cfg), L)
    elif cfg.family == "hybrid":
        period = cfg.hybrid.period
        groups = L // period
        defs["layers"] = stacked(stacked(_ssm_block_defs(cfg), period, None),
                                 groups)
        defs["shared"] = _attn_block_defs(cfg)
    elif cfg.family == "audio":
        enc_l = cfg.encdec.encoder_layers
        defs["enc_layers"] = stacked(_attn_block_defs(cfg), enc_l)
        defs["enc_norm"] = _norm_def(cfg.d_model)
        defs["layers"] = stacked(_decoder_block_defs(cfg), L)
    else:  # dense / vlm
        defs["layers"] = stacked(_attn_block_defs(cfg), L)
    return defs


def lora_defs_tree(cfg: ModelConfig) -> Dict:
    """LoRA adapter ParamDefs mirroring the layer structure."""
    targets = cfg.lora.targets
    if cfg.family == "ssm":
        per = lora_mod.lora_layer_defs(cfg, targets)
        return {"layers": stacked(per, cfg.num_layers)}
    if cfg.family == "hybrid":
        ssm_targets = tuple(t for t in targets if t.startswith("ssm"))
        attn_targets = tuple(t for t in targets if not t.startswith("ssm"))
        out = {}
        if ssm_targets:
            period = cfg.hybrid.period
            groups = cfg.num_layers // period
            out["layers"] = stacked(
                stacked(lora_mod.lora_layer_defs(cfg, ssm_targets), period, None),
                groups)
        if attn_targets:
            out["shared"] = lora_mod.lora_layer_defs(cfg, attn_targets)
        return out
    if cfg.family == "audio":
        per = lora_mod.lora_layer_defs(cfg, targets)
        return {"enc_layers": stacked(per, cfg.encdec.encoder_layers),
                "layers": stacked(per, cfg.num_layers)}
    if cfg.family == "moe":
        fk = cfg.moe.first_k_dense
        per = lora_mod.lora_layer_defs(cfg, targets)
        out = {"layers": stacked(per, cfg.num_layers - fk)}
        if fk:
            out["dense_layers"] = stacked(per, fk)
        return out
    per = lora_mod.lora_layer_defs(cfg, targets)
    return {"layers": stacked(per, cfg.num_layers)}


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, s_max: int, *,
               enc_len: int = 0, abstract: bool = False,
               dtype=jnp.bfloat16) -> Dict:
    """Family-appropriate decode cache (stacked over layers for scanning)."""
    mk = jax.ShapeDtypeStruct if abstract else jnp.zeros

    def zeros(shape, dt=dtype):
        return mk(shape, dt)

    hd = cfg.resolved_head_dim
    Kv = cfg.num_kv_heads
    L = cfg.num_layers
    cache: Dict[str, Any] = {"index": zeros((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "moe"):
        cache["k"] = zeros((L, batch, s_max, Kv, hd))
        cache["v"] = zeros((L, batch, s_max, Kv, hd))
    elif cfg.family == "ssm":
        s = cfg.ssm
        W = s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state
        H = s.n_heads(cfg.d_model)
        cache["conv"] = zeros((L, batch, s.d_conv - 1, W))
        cache["state"] = zeros((L, batch, H, s.d_state, s.head_dim), jnp.float32)
    elif cfg.family == "hybrid":
        s = cfg.ssm
        period = cfg.hybrid.period
        groups = L // period
        W = s.d_inner(cfg.d_model) + 2 * s.n_groups * s.d_state
        H = s.n_heads(cfg.d_model)
        cache["conv"] = zeros((groups, period, batch, s.d_conv - 1, W))
        cache["state"] = zeros((groups, period, batch, H, s.d_state, s.head_dim),
                               jnp.float32)
        cache["k"] = zeros((groups, batch, s_max, Kv, hd))
        cache["v"] = zeros((groups, batch, s_max, Kv, hd))
    elif cfg.family == "audio":
        cache["k"] = zeros((L, batch, s_max, Kv, hd))
        cache["v"] = zeros((L, batch, s_max, Kv, hd))
        cache["cross_k"] = zeros((L, batch, enc_len, Kv, hd))
        cache["cross_v"] = zeros((L, batch, enc_len, Kv, hd))
    else:
        raise ValueError(cfg.family)
    return cache


# ---------------------------------------------------------------------------
# blocks (single layer, used inside scans)
# ---------------------------------------------------------------------------


def _dense_block(p, x, cfg, *, positions, mode, kv, lora_ctx, causal=True):
    with jax.named_scope("attention"):
        h, new_kv = attention_fwd(p["attn"],
                                  rms_norm(x, p["ln1"], cfg.norm_eps), cfg,
                                  positions=positions, mode=mode, cache=kv,
                                  lora_ctx=lora_ctx, causal=causal)
        x = x + h
    with jax.named_scope("mlp"):
        x = x + mlp_fwd(p["mlp"], rms_norm(x, p["ln2"], cfg.norm_eps))
    return x, new_kv


def _moe_block(p, x, cfg, *, positions, mode, kv, lora_ctx):
    h, new_kv = attention_fwd(p["attn"], rms_norm(x, p["ln1"], cfg.norm_eps),
                              cfg, positions=positions, mode=mode, cache=kv,
                              lora_ctx=lora_ctx)
    x = x + h
    y, aux = moe_fwd(p["moe"], rms_norm(x, p["ln2"], cfg.norm_eps), cfg)
    return x + y, new_kv, aux


def _ssm_block(p, x, cfg, *, mode, cache, lora_ctx):
    h, new_cache = ssm_block_fwd(p["ssm"], rms_norm(x, p["ln1"], cfg.norm_eps),
                                 cfg, mode=mode, cache=cache, lora_ctx=lora_ctx)
    return x + h, new_cache


# ---------------------------------------------------------------------------
# layer-stack scanners
# ---------------------------------------------------------------------------


def _maybe_remat(fn, cfg: ModelConfig, mode: str):
    if cfg.remat and mode == "train":
        return jax.checkpoint(fn, prevent_cse=False)
    return fn


@jax.custom_vjp
def _bf16_grad_boundary(x):
    return x


def _bf16_fwd(x):
    return x, None


def _bf16_bwd(_, g):
    # cast the cotangent to bf16 (halves backward activation collective
    # traffic through the FSDP/SP gathers; §Perf hillclimb)
    return (g.astype(jnp.bfloat16).astype(g.dtype)
            if g.dtype == jnp.float32 else g,)


_bf16_grad_boundary.defvjp(_bf16_fwd, _bf16_bwd)


def _constrain_carry(c, cfg=None):
    """Layer-boundary activation sharding (Megatron-SP), applied OUTSIDE the
    remat boundary so the saved residuals are the sequence-sharded copies."""
    def one(a):
        if hasattr(a, "ndim") and a.ndim == 3:
            a = constrain(a, "batch", "seq_sp", "d_model")
            if cfg is not None and cfg.grad_cast_bf16:
                a = _bf16_grad_boundary(a)
        return a
    return jax.tree.map(one, c)


def _scan_stack(fn, x, xs, cfg: ModelConfig, mode: str):
    """scan fn over stacked layer inputs; fn(x, xs_l) -> (x, ys_l)."""
    inner = _maybe_remat(fn, cfg, mode)

    def wrapped(c, xs_l):
        return inner(_constrain_carry(c, cfg), xs_l)

    if cfg.scan_layers:
        return jax.lax.scan(wrapped, x, xs)
    n = jax.tree.leaves(xs)[0].shape[0]
    ys = []
    for i in range(n):
        x, y = wrapped(x, jax.tree.map(lambda a: a[i], xs))
        ys.append(y)
    ys = jax.tree.map(lambda *a: jnp.stack(a), *ys) if ys and ys[0] is not None \
        else None
    return x, ys


def _kv_of(cache, mode, layer_kv=None, index=None):
    if mode == "train":
        return None
    k, v = layer_kv
    return KVCache(k=k, v=v, index=index)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def forward(params: Dict, cfg: ModelConfig, *,
            tokens: Optional[Array] = None,
            patches: Optional[Array] = None,
            frames: Optional[Array] = None,
            mode: str = "train",
            cache: Optional[Dict] = None,
            lora_params: Optional[Dict] = None,
            lora_ctx_proto: Optional[lora_mod.LoRAContext] = None,
            ) -> Tuple[Array, Optional[Dict], Array]:
    """Run the model.  Returns (hidden (B,S,d), new_cache, aux_loss).

    ``lora_params`` mirrors the layer structure (see lora_defs_tree);
    ``lora_ctx_proto`` carries mode/ids/scaling (its .params is ignored).
    """
    assert mode in ("train", "prefill", "decode")
    aux = jnp.zeros((), jnp.float32)

    def ctx(layer_lora):
        if layer_lora is None or lora_ctx_proto is None:
            return None
        return lora_mod.layer_slice(lora_ctx_proto, layer_lora)

    lp = lora_params or {}
    if cfg.family == "audio":
        return _forward_audio(params, cfg, tokens=tokens, frames=frames,
                              mode=mode, cache=cache, lp=lp, ctx=ctx, aux=aux)

    # ---- embed ----------------------------------------------------------
    x = embed_tokens(params["embed"], tokens)
    if cfg.family == "vlm" and patches is not None:
        x = jnp.concatenate([patches.astype(x.dtype), x], axis=1)
        x = constrain(x, "batch", "seq", "d_model")
    B, S, _ = x.shape

    index = cache["index"] if cache is not None else jnp.zeros((), jnp.int32)
    if jnp.ndim(index) == 1:   # per-slot positions (continuous batching)
        positions = index[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    else:
        positions = index + jnp.arange(S, dtype=jnp.int32)
    new_cache = dict(cache) if cache is not None else None

    # ---- layer stacks ---------------------------------------------------
    if cfg.family in ("dense", "vlm"):
        def fn(x, xs):
            p_l, kv_l, lora_l = xs
            kv = _kv_of(cache, mode, kv_l, index)
            x, new_kv = _dense_block(p_l, x, cfg, positions=positions,
                                     mode=mode, kv=kv, lora_ctx=ctx(lora_l))
            ys = (new_kv.k, new_kv.v) if new_kv is not None else None
            return x, ys

        kv_stack = (cache["k"], cache["v"]) if cache is not None else None
        xs = (params["layers"], kv_stack, lp.get("layers"))
        x, ys = _scan_stack(fn, x, xs, cfg, mode)
        if ys is not None and cache is not None:
            if cfg.decode_attn == "lazy" and mode == "decode":
                # ys hold only each layer's new (k, v) token: one tiny
                # dynamic-update-slice on the stacked cache per step
                new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["k"], ys[0], index, axis=2)
                new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
                    cache["v"], ys[1], index, axis=2)
            else:
                new_cache["k"], new_cache["v"] = ys

    elif cfg.family == "moe":
        fk = cfg.moe.first_k_dense
        if fk:
            import dataclasses as _dc
            dense_cfg = _dc.replace(cfg, d_ff=cfg.moe.d_ff_dense)

            def fn_d(x, xs):
                p_l, kv_l, lora_l = xs
                kv = _kv_of(cache, mode, kv_l, index)
                x, new_kv = _dense_block(p_l, x, dense_cfg,
                                         positions=positions, mode=mode,
                                         kv=kv, lora_ctx=ctx(lora_l))
                ys = (new_kv.k, new_kv.v) if new_kv is not None else None
                return x, ys

            kv_stack = ((cache["k"][:fk], cache["v"][:fk])
                        if cache is not None else None)
            xs = (params["dense_layers"], kv_stack, lp.get("dense_layers"))
            x, ys_d = _scan_stack(fn_d, x, xs, cfg, mode)
        aux_acc = jnp.zeros((), jnp.float32)

        def fn_m(carry, xs):
            x, aux_acc = carry
            p_l, kv_l, lora_l = xs
            kv = _kv_of(cache, mode, kv_l, index)
            x, new_kv, aux_l = _moe_block(p_l, x, cfg, positions=positions,
                                          mode=mode, kv=kv, lora_ctx=ctx(lora_l))
            ys = (new_kv.k, new_kv.v) if new_kv is not None else None
            return (x, aux_acc + aux_l), ys

        kv_stack = ((cache["k"][fk:], cache["v"][fk:])
                    if cache is not None else None)
        xs = (params["layers"], kv_stack, lp.get("layers"))
        (x, aux_acc), ys_m = _scan_stack(fn_m, (x, aux_acc), xs, cfg, mode)
        aux = aux + aux_acc / max(cfg.num_layers - fk, 1)
        if cache is not None:
            ks, vs = [], []
            if fk:
                ks.append(ys_d[0])
                vs.append(ys_d[1])
            if ys_m is not None:
                ks.append(ys_m[0])
                vs.append(ys_m[1])
            new_cache["k"] = jnp.concatenate(ks, axis=0)
            new_cache["v"] = jnp.concatenate(vs, axis=0)

    elif cfg.family == "ssm":
        def fn(x, xs):
            p_l, c_l, lora_l = xs
            c = _ssm_cache_of(c_l, index) if cache is not None else None
            x, new_c = _ssm_block(p_l, x, cfg, mode=mode, cache=c,
                                  lora_ctx=ctx(lora_l))
            ys = (new_c.conv, new_c.state) if new_c is not None else None
            return x, ys

        c_stack = ((cache["conv"], cache["state"]) if cache is not None else None)
        xs = (params["layers"], c_stack, lp.get("layers"))
        x, ys = _scan_stack(fn, x, xs, cfg, mode)
        if ys is not None and cache is not None:
            new_cache["conv"], new_cache["state"] = ys

    elif cfg.family == "hybrid":
        period = cfg.hybrid.period
        shared_p = params["shared"]
        shared_lora = lp.get("shared")

        def group_fn(x, xs):
            p_g, ssm_c_g, kv_g, lora_g = xs

            def inner_fn(x, xs_i):
                p_l, c_l, lora_l = xs_i
                c = _ssm_cache_of(c_l, index) if cache is not None else None
                x, new_c = _ssm_block(p_l, x, cfg, mode=mode, cache=c,
                                      lora_ctx=ctx(lora_l))
                ys = (new_c.conv, new_c.state) if new_c is not None else None
                return x, ys

            x, ssm_ys = jax.lax.scan(inner_fn, x, (p_g, ssm_c_g, lora_g))
            kv = _kv_of(cache, mode, kv_g, index)
            x, new_kv = _dense_block(shared_p, x, cfg, positions=positions,
                                     mode=mode, kv=kv, lora_ctx=ctx(shared_lora))
            kv_ys = (new_kv.k, new_kv.v) if new_kv is not None else None
            return x, (ssm_ys, kv_ys)

        ssm_stack = ((cache["conv"], cache["state"]) if cache is not None
                     else None)
        kv_stack = ((cache["k"], cache["v"]) if cache is not None else None)
        lora_stack = lp.get("layers")
        xs = (params["layers"], ssm_stack, kv_stack, lora_stack)
        x, ys = _scan_stack(group_fn, x, xs, cfg, mode)
        if cache is not None and ys is not None:
            (conv_s, state_s), kv_ys = ys
            new_cache["conv"], new_cache["state"] = conv_s, state_s
            new_cache["k"], new_cache["v"] = kv_ys
    else:
        raise ValueError(cfg.family)

    if new_cache is not None:
        new_cache["index"] = index + S
    return x, new_cache, aux


def _forward_audio(params, cfg, *, tokens, frames, mode, cache, lp, ctx, aux):
    """whisper-style: encoder over frames, decoder over tokens w/ cross-attn."""
    index = cache["index"] if cache is not None else jnp.zeros((), jnp.int32)
    new_cache = dict(cache) if cache is not None else None
    enc_lp = lp.get("enc_layers")

    memory = None
    if frames is not None:
        h = frames
        pos_e = jnp.arange(h.shape[1], dtype=jnp.int32)

        def enc_fn(x, xs):
            p_l, lora_l = xs
            x, _ = _dense_block(p_l, x, cfg, positions=pos_e, mode="train",
                                kv=None, lora_ctx=ctx(lora_l), causal=False)
            return x, None

        xs = (params["enc_layers"], enc_lp)
        h, _ = _scan_stack(enc_fn, h, xs, cfg, mode)
        memory = rms_norm(h, params["enc_norm"], cfg.norm_eps)

    x = embed_tokens(params["embed"], tokens)
    B, S, _ = x.shape
    positions = index + jnp.arange(S, dtype=jnp.int32)

    def dec_fn(x, xs):
        p_l, kv_l, xkv_l, lora_l = xs
        kv = _kv_of(cache, mode, kv_l, index)
        h, new_kv = attention_fwd(p_l["attn"],
                                  rms_norm(x, p_l["ln1"], cfg.norm_eps), cfg,
                                  positions=positions, mode=mode, cache=kv,
                                  lora_ctx=ctx(lora_l))
        x = x + h
        xin = rms_norm(x, p_l["lnx"], cfg.norm_eps)
        if mode == "decode":
            xk, xv = xkv_l
            q = jnp.einsum("bsd,dhk->bshk", xin, p_l["xattn"]["wq"])
            from repro.models.layers import naive_attention
            o = naive_attention(q, xk, xv, causal=False)
            h2 = jnp.einsum("bshk,hkd->bsd", o, p_l["xattn"]["wo"])
            new_xkv = None
        else:
            h2 = cross_attention_fwd(p_l["xattn"], xin, memory, cfg,
                                     lora_ctx=ctx(lora_l))
            new_xkv = (jnp.einsum("bsd,dhk->bshk", memory, p_l["xattn"]["wk"]),
                       jnp.einsum("bsd,dhk->bshk", memory, p_l["xattn"]["wv"])) \
                if mode == "prefill" else None
        x = x + h2
        x = x + mlp_fwd(p_l["mlp"], rms_norm(x, p_l["ln2"], cfg.norm_eps))
        ys = ((new_kv.k, new_kv.v) if new_kv is not None else None, new_xkv)
        return x, ys

    kv_stack = (cache["k"], cache["v"]) if cache is not None else None
    xkv_stack = ((cache["cross_k"], cache["cross_v"])
                 if (cache is not None and mode == "decode") else None)
    xs = (params["layers"], kv_stack, xkv_stack, lp.get("layers"))
    x, ys = _scan_stack(dec_fn, x, xs, cfg, mode)
    if cache is not None and ys is not None:
        kv_ys, xkv_ys = ys
        if kv_ys is not None:
            new_cache["k"], new_cache["v"] = kv_ys
        if xkv_ys is not None and mode == "prefill":
            new_cache["cross_k"], new_cache["cross_v"] = xkv_ys
        new_cache["index"] = index + S
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# scan plumbing helpers
# ---------------------------------------------------------------------------


def _ssm_cache_of(c_l, index):
    conv, state = c_l
    return SSMCache(conv=conv, state=state, index=index)


# ---------------------------------------------------------------------------
# public steps
# ---------------------------------------------------------------------------


def lm_loss(params: Dict, batch: Dict, cfg: ModelConfig,
            lora_params: Optional[Dict] = None,
            lora_ctx_proto=None,
            aux_weight: float = 0.01) -> Array:
    h, _, aux = forward(params, cfg, tokens=batch.get("tokens"),
                        patches=batch.get("patches"),
                        frames=batch.get("frames"), mode="train",
                        lora_params=lora_params, lora_ctx_proto=lora_ctx_proto)
    targets = batch["targets"]
    if cfg.family == "vlm" and batch.get("patches") is not None:
        h = h[:, batch["patches"].shape[1]:]
    loss = cross_entropy(params["embed"], h, targets, cfg,
                         mask=batch.get("loss_mask"))
    return loss + aux_weight * aux


def prefill(params: Dict, batch: Dict, cfg: ModelConfig, cache: Dict,
            lora_params=None, lora_ctx_proto=None) -> Tuple[Array, Dict]:
    h, new_cache, _ = forward(params, cfg, tokens=batch.get("tokens"),
                              patches=batch.get("patches"),
                              frames=batch.get("frames"), mode="prefill",
                              cache=cache, lora_params=lora_params,
                              lora_ctx_proto=lora_ctx_proto)
    with jax.named_scope("logits"):
        logits = logits_fwd(params["embed"], h[:, -1:], cfg)
    return logits, new_cache


def decode_step(params: Dict, tokens: Array, cfg: ModelConfig, cache: Dict,
                lora_params=None, lora_ctx_proto=None) -> Tuple[Array, Dict]:
    h, new_cache, _ = forward(params, cfg, tokens=tokens, mode="decode",
                              cache=cache, lora_params=lora_params,
                              lora_ctx_proto=lora_ctx_proto)
    with jax.named_scope("logits"):
        logits = logits_fwd(params["embed"], h, cfg)
    return logits, new_cache
