"""Real-model executor: actually runs prefill/decode with batched LoRA
application on JAX's default device (the TPU on a chip host, the CPU in
tests).  Wall-clock timed, real logits.

Slot model: a fixed decode batch of ``max_batch`` KV-cache slots; admitted
requests prefill into a free slot (batch-1 prefill, cache splice); each
engine decode step advances every occupied slot by one token with per-slot
adapter ids (mode "lora": stacked A/B banks; mode "jd": U/V/Sigma bundles).

Decode paths (``decode_path``, surfaced as `EngineConfig.decode_path`):

* ``"unfused"`` (default) — the generic `transformer.decode_step`
  (functional cache, separate attention + adapter passes).  Bit-exact
  with every committed baseline.
* ``"fused"`` — a purpose-built decode step: the per-layer loop is
  unrolled, rope tables are built once, the KV cache is DONATED to the
  jit so the single-token write is in-place instead of a full functional
  cache copy per layer, and attention + the o-projection adapter delta
  run as ONE fused pass (`kernels/fused_decode.py` via
  `kernels/ops.py::fused_lora_decode` / `fused_jd_decode`).
* ``"fused_q8"`` — ``"fused"`` plus int8 per-output-channel adapter
  residency (`kernels/adapter_quant.py`): banks are packed at
  construction, `adapter_bytes` shrinks ~4x (threading straight through
  `PagedPool` page accounting), and the o-target bank is dequantized
  inside the fused kernel epilogue; q/k/v banks are dequantized in-jit.

`benchmarks/real_decode.py` measures all three and re-derives the
simulator's cost-model constants from the fused measurements
(:func:`derive_cost_constants`)."""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.kernels import ops as kops
from repro.kernels import ref as kref
from repro.kernels.adapter_quant import adapter_quantize
from repro.models import layers, moe
from repro.models import transformer as tf
from repro.models.lora import LoRAContext
from repro.serving import telemetry
from repro.serving.request import Request

Array = jax.Array

DECODE_PATHS = ("unfused", "fused", "fused_q8")


class RealModelExecutor:
    def __init__(self, cfg: ModelConfig, params, bundles: Dict[str, Dict],
                 mode: str, max_batch: int, s_max: int,
                 cluster_of: Optional[np.ndarray] = None,
                 adapter_bytes_override: Optional[int] = None,
                 decode_path: str = "unfused", seed: int = 0):
        """bundles: layer-structured arrays for the adapters:
        mode 'lora': {"layers": {target: {"A": (L,n,r,d), "B": (L,n,d,r)}}}
        mode 'jd':   {"layers": {target: {"U","V","sigma","cluster_of"}}}
        (an MoE model's leading dense layers in a "dense_layers" stack
        beside them, as `transformer.lora_defs_tree` has it; the fused
        paths join the two, :func:`_one_stack`)
        ``seed`` keys the synthetic prompts of :meth:`prompt_for`."""
        if decode_path not in DECODE_PATHS:
            raise ValueError(f"decode_path must be one of {DECODE_PATHS}, "
                             f"got {decode_path!r}")
        self.cfg, self.mode = cfg, mode
        self.decode_path = decode_path
        self.params = params
        self.max_batch = max_batch
        self.s_max = s_max
        self.cluster_of = cluster_of
        self.seed = seed
        self.cache = tf.init_cache(cfg, max_batch, s_max)
        self.slot_req: List[Optional[int]] = [None] * max_batch
        self.slot_adapter = np.zeros(max_batch, np.int32)
        self.slot_tokens = np.zeros(max_batch, np.int32)
        self.slot_len = np.zeros(max_batch, np.int32)
        # tokens each request's decode steps emitted: one per engine step,
        # so a finished request holds exactly max_new_tokens
        self.outputs: Dict[int, List[int]] = {}
        # host mirror of the cache's scalar index: lets the fused paths pick
        # a static KV bucket without a device sync
        self._host_len = 0
        if decode_path == "unfused":
            self.bundles = bundles
            self._decode = jax.jit(self._decode_fn)
        else:
            self._check_fusable()
            # one adapter stack by cache layer, as a dense model's: the
            # fused kernel reads the o-bank in place at the cache's layer
            bundles = _one_stack(bundles)
            if decode_path == "fused_q8":
                bundles = _quantize_bundles(bundles, mode)
            self.bundles = bundles
            # donate the cache: the per-step single-token KV write happens
            # in place instead of copying every layer's full cache slice
            self._decode = jax.jit(self._fused_decode_fn, donate_argnums=(3,),
                                   static_argnames=("bucket",))
        self._prefill = jax.jit(self._prefill_fn)
        nbytes = sum(x.size * x.dtype.itemsize
                     for x in jax.tree.leaves(self.bundles)) or 1
        n_adapters = self._n_adapters()
        self._adapter_bytes = adapter_bytes_override or max(
            nbytes // max(n_adapters, 1), 1)

    def _check_fusable(self) -> None:
        if self.cfg.family not in ("dense", "vlm", "moe"):
            raise ValueError("fused decode paths support dense-attention "
                             f"families only, not {self.cfg.family!r}")
        if self.cfg.sliding_window:
            raise ValueError("fused decode paths assume full attention "
                             "(sliding_window=0)")
        if self.mode not in ("lora", "jd"):
            raise ValueError(f"unknown adapter mode {self.mode!r}")

    def _n_adapters(self) -> int:
        for leaf in jax.tree.leaves(self.bundles):
            return leaf.shape[1] if leaf.ndim > 1 else 1
        return 1

    def _ctx(self, ids: Array) -> LoRAContext:
        return LoRAContext(mode="batched" if self.mode == "lora" else "jd",
                           params=None, ids=ids, scaling=1.0)

    def _decode_fn(self, params, bundles, tokens, cache, ids):
        proto = self._ctx(ids)
        return tf.decode_step(params, tokens, self.cfg, cache,
                              lora_params=bundles, lora_ctx_proto=proto)

    def _prefill_fn(self, params, bundles, tokens, cache, ids):
        if self.decode_path == "fused_q8":
            bundles = _dequantize_bundles(bundles)
        if self.decode_path != "unfused" and self.cfg.family == "moe":
            bundles = _split_stack(bundles, self.cfg.moe.first_k_dense)
        proto = self._ctx(ids)
        return tf.prefill(params, {"tokens": tokens}, self.cfg, cache,
                          lora_params=bundles, lora_ctx_proto=proto)

    # -- fused decode step --------------------------------------------------
    def _bucket(self) -> int:
        """Static KV window for the fused step: the occupied prefix of the
        cache rounded up to 128 tokens (the page/quant-block granule).

        The generic unfused step attends over all ``s_max`` slots every
        step (masked, but computed); the executor knows the occupied
        length on the host, so the fused step only ever touches
        ``ceil(len/128)`` blocks — one retrace per 128 tokens of growth,
        O(active) attention instead of O(s_max)."""
        need = self._host_len + 1
        return min(self.s_max, 128 * -(-need // 128))

    def _stacks(self):
        """The layer stacks in order, ``(name, first cache layer, count)``:
        a dense model's one, an MoE model's leading dense layers and then
        its expert layers (`transformer.model_defs`)."""
        cfg = self.cfg
        if cfg.family != "moe":
            return [("layers", 0, cfg.num_layers)]
        fk = cfg.moe.first_k_dense
        return ([("dense_layers", 0, fk)] if fk else []) + [
            ("layers", fk, cfg.num_layers - fk)]

    def _fused_decode_fn(self, params, bundles, tokens, cache, ids, *,
                         bucket):
        """Unrolled single-token decode with the o-projection adapter delta
        fused into the attention kernel.  Matches `transformer.decode_step`
        semantics (scalar cache index, decode at max occupied length);
        ``bucket`` (static) truncates attention to the occupied KV prefix
        — masked tail blocks contribute exactly zero, so logits are
        unchanged.  An MoE model's expert layers run every held expert on
        every token (`moe.moe_decode`), and the step also returns the
        tokens routed to each held expert, (expert layers, held) int32."""
        cfg = self.cfg
        quant = self.decode_path == "fused_q8"
        banks = bundles["layers"]
        if quant:
            qkv_banks = {t: _dequantize_target(tp)
                         for t, tp in banks.items() if t != "o"}
        else:
            qkv_banks = {t: tp for t, tp in banks.items() if t != "o"}
        o_bank = banks.get("o")
        if o_bank is not None:
            o_args, o_kw = self._o_bank_args(o_bank)
            fused = (kops.fused_lora_decode if self.mode == "lora"
                     else kops.fused_jd_decode)
        proto = self._ctx(ids)

        x = layers.embed_tokens(params["embed"], tokens)
        Bt, S, _ = x.shape                       # S == 1
        idx = cache["index"]
        positions = idx + jnp.arange(S, dtype=jnp.int32)
        cos, sin = layers.rope_tables(positions, cfg.resolved_head_dim,
                                      cfg.rope_theta)
        ck, cv = cache["k"], cache["v"]
        kv_len = jnp.broadcast_to(idx + S, (Bt,)).astype(jnp.int32)
        routed = []
        for name, first, count in self._stacks():
            for j in range(count):
                li = first + j
                p_l = jax.tree.map(lambda a: a[j], params[name])
                lora_l = {t: jax.tree.map(lambda a: a[li], tp)
                          for t, tp in qkv_banks.items()} or None
                ctx = (LoRAContext(mode=proto.mode, params=lora_l, ids=ids,
                                   scaling=proto.scaling)
                       if lora_l is not None else None)
                xin = layers.rms_norm(x, p_l["ln1"], cfg.norm_eps)
                qh, kh, vh = layers._qkv(p_l["attn"], xin, cfg, ctx)
                with jax.named_scope("attention"):
                    qh = layers.apply_rope(qh, cos, sin)
                    kh = layers.apply_rope(kh, cos, sin)
                    ck = jax.lax.dynamic_update_slice(
                        ck, kh.astype(ck.dtype)[None], (li, 0, idx, 0, 0))
                    cv = jax.lax.dynamic_update_slice(
                        cv, vh.astype(cv.dtype)[None], (li, 0, idx, 0, 0))
                    # the kernel reads layer li's first `bucket` tokens (and
                    # its o-bank rows) in place: the donated cache is never
                    # sliced or copied
                    at = dict(layer=li, window=bucket)
                    if o_bank is None:
                        attn = kops.decode_attention(qh[:, 0], ck, cv, kv_len,
                                                     **at)
                        delta = None
                    else:
                        attn, delta = fused(qh[:, 0], ck, cv, kv_len, ids,
                                            *o_args, **o_kw, **at)
                    y = jnp.einsum("bhk,hkd->bd", attn, p_l["attn"]["wo"])
                    if delta is not None:
                        y = y + (proto.scaling * delta).astype(y.dtype)
                    x = x + y[:, None]
                if "moe" in p_l:
                    with jax.named_scope("moe"):
                        y, n_routed = moe.moe_decode(
                            p_l["moe"],
                            layers.rms_norm(x, p_l["ln2"], cfg.norm_eps), cfg)
                        x = x + y
                    routed.append(n_routed)
                else:
                    with jax.named_scope("mlp"):
                        x = x + layers.mlp_fwd(
                            p_l["mlp"],
                            layers.rms_norm(x, p_l["ln2"], cfg.norm_eps))
        with jax.named_scope("logits"):
            logits = layers.logits_fwd(params["embed"], x, cfg)
        new_cache = dict(cache)
        new_cache.update(k=ck, v=cv, index=idx + S)
        if routed:
            return logits, new_cache, jnp.stack(routed)
        return logits, new_cache

    def _o_bank_args(self, o_bank):
        """The o-target's layer-stacked banks as the fused kernel takes
        them: positional banks and scale keywords.  The kernel reads each
        layer's rows in place; only a packed full Sigma is unpacked here,
        once per step."""
        if self.decode_path != "fused_q8":
            if self.mode == "lora":
                return (o_bank["A"], o_bank["B"]), {}
            return (o_bank["U"], o_bank["V"], o_bank["sigma"],
                    o_bank["cluster_of"]), {}
        if self.mode == "lora":
            return ((o_bank["A_q"], o_bank["B_q"]),
                    dict(a_scale=o_bank["A_s"], b_scale=o_bank["B_s"]))
        sigma = (o_bank["sigma"] if "sigma" in o_bank else
                 kref.adapter_dequant_ref(o_bank["sigma_q"],
                                          o_bank["sigma_s"]))
        return ((o_bank["U_q"], o_bank["V_q"], sigma, o_bank["cluster_of"]),
                dict(u_scale=o_bank["U_s"], v_scale=o_bank["V_s"]))

    # -- engine interface ---------------------------------------------------
    def adapter_bytes(self, aid: int) -> int:
        return self._adapter_bytes

    def shared_bytes(self) -> int:
        return 0

    def prefill_request(self, req: Request, prompt: np.ndarray) -> None:
        slot = self.slot_req.index(None)
        with telemetry.span("executor.prefill", rid=req.rid,
                            prompt_len=int(req.prompt_len), slot=slot):
            self._prefill_into(slot, req, prompt)

    def _prefill_into(self, slot: int, req: Request,
                      prompt: np.ndarray) -> None:
        span = telemetry.span
        with span("executor.prefill.cache"):
            c1 = tf.init_cache(self.cfg, 1, self.s_max)
        with span("executor.prefill.run"):
            logits, c1 = self._prefill(
                self.params, self.bundles, jnp.asarray(prompt[None]), c1,
                jnp.asarray([req.adapter_id], jnp.int32))
        # splice the single-request cache into the slot batch
        def splice(dst, src):
            if dst.ndim == 0:
                return dst
            bdim = _batch_dim(dst)
            idx = [slice(None)] * dst.ndim
            idx[bdim] = slice(slot, slot + 1)
            return dst.at[tuple(idx)].set(src)
        with span("executor.prefill.splice"):
            self.cache = jax.tree.map(splice, self.cache, c1)
            # advance the shared scalar index to the deepest prefilled slot
            # so decode continues AFTER the prompt instead of overwriting it
            # (the splice alone keeps dst's scalar leaves, a stale index)
            self.cache["index"] = jnp.maximum(
                self.cache["index"], jnp.asarray(req.prompt_len, jnp.int32))
        self._host_len = max(self._host_len, int(req.prompt_len))
        self.slot_req[slot] = req.rid
        self.slot_adapter[slot] = req.adapter_id
        with span("executor.prefill.sample"):
            first = jnp.argmax(logits[0, -1, :self.cfg.vocab_size])
        # the host read in two calls: waiting for the device, then the copy
        with span("executor.prefill.wait"):
            first.block_until_ready()
        with span("executor.prefill.fetch"):
            self.slot_tokens[slot] = int(first)
        self.slot_len[slot] = req.prompt_len
        self.outputs[req.rid] = []

    def decode_step_real(self) -> Dict[int, int]:
        """One decode step for all occupied slots; returns {rid: token}."""
        unfused = self.decode_path == "unfused"
        # the attended KV window: each new one is a new fused-step program
        bucket = self.s_max if unfused else self._bucket()
        with telemetry.span("executor.decode", slots=self.max_batch,
                            batch=self.max_batch - self.slot_req.count(None),
                            bucket=bucket):
            return self._decode_step(unfused, bucket)

    def _decode_step(self, unfused: bool, bucket: int) -> Dict[int, int]:
        span = telemetry.span
        with span("executor.decode.inputs"):
            tokens = jnp.asarray(self.slot_tokens[:, None])
            ids = jnp.asarray(self.slot_adapter)
        # index must be per-slot; our cache uses a scalar index — decode at
        # max occupied length (padding slots attend junk but are ignored)
        with span("executor.decode.launch"):
            if unfused:
                res = self._decode(self.params, self.bundles, tokens,
                                   self.cache, ids)
            else:
                res = self._decode(self.params, self.bundles, tokens,
                                   self.cache, ids, bucket=bucket)
            # an MoE model's fused step also counts its routed tokens
            logits, self.cache, *routed = res
            routed = routed[0] if routed else None
        self._host_len += 1
        out = {}
        # the unembedding is padded past the vocabulary; those columns are
        # not tokens
        with span("executor.decode.sample"):
            nxt = jnp.argmax(logits[:, -1, :self.cfg.vocab_size], axis=-1)
        # the host read in two calls: waiting for the device, then the copy
        with span("executor.decode.wait"):
            nxt.block_until_ready()
        with span("executor.decode.fetch"):
            if routed is None:
                nxt = np.asarray(nxt)
            else:
                nxt, routed = jax.device_get((nxt, routed))
        if routed is not None:
            # summed over the expert layers: pairs routed to the experts
            # held here, and held experts that had at least one
            telemetry.event("executor.decode.experts",
                            routed=int(routed.sum()),
                            used=int((routed > 0).sum()))
        with span("executor.decode.emit"):
            for slot, rid in enumerate(self.slot_req):
                if rid is not None:
                    self.slot_tokens[slot] = nxt[slot]
                    self.slot_len[slot] += 1
                    out[rid] = int(nxt[slot])
                    self.outputs.setdefault(rid, []).append(int(nxt[slot]))
        return out

    def release(self, rid: int) -> None:
        slot = self.slot_req.index(rid)
        self.slot_req[slot] = None
        if all(r is None for r in self.slot_req):
            # drained: the next wave prefills from position 0 again rather
            # than decoding after the previous wave's scalar index
            self.cache["index"] = jnp.zeros((), jnp.int32)
            self._host_len = 0

    # -- live migration (PR 9) ----------------------------------------------
    def export_slot(self, rid: int) -> Dict:
        """Checkpoint a request's decode state for live migration: its KV
        slice (every batched cache leaf at the request's slot), the last
        sampled token, and the filled depth.  The slot is NOT released —
        the engine frees it via :meth:`release` once the checkpoint is on
        the wire (invariant M3)."""
        slot = self.slot_req.index(rid)

        def take(x):
            if x.ndim == 0:
                return x
            bdim = _batch_dim(x)
            idx = [slice(None)] * x.ndim
            idx[bdim] = slice(slot, slot + 1)
            return x[tuple(idx)]

        return {"kv": jax.tree.map(take, self.cache),
                "adapter": int(self.slot_adapter[slot]),
                "token": int(self.slot_tokens[slot]),
                "len": int(self.slot_len[slot]),
                "index": int(self._host_len)}

    def import_slot(self, req: Request, state: Dict) -> None:
        """Re-admit a migrated request from :meth:`export_slot` state.

        Splices the shipped KV slice into a free slot and resumes decode
        from the checkpointed token — token-exact with the source
        (invariant M1).  The cache's scalar index is shared across slots,
        so exactness requires the target's filled depth not to exceed the
        source's (e.g. a fresh replica); deeper targets decode correctly
        but attend padding for the shallower slot, like any mixed-depth
        batch under the scalar-index cache model."""
        slot = self.slot_req.index(None)

        def splice(dst, src):
            if dst.ndim == 0:
                return dst
            bdim = _batch_dim(dst)
            idx = [slice(None)] * dst.ndim
            idx[bdim] = slice(slot, slot + 1)
            return dst.at[tuple(idx)].set(src)

        self.cache = jax.tree.map(splice, self.cache, state["kv"])
        self.cache["index"] = jnp.maximum(
            self.cache["index"], jnp.asarray(state["index"], jnp.int32))
        self._host_len = max(self._host_len, int(state["index"]))
        self.slot_req[slot] = req.rid
        self.slot_adapter[slot] = state["adapter"]
        self.slot_tokens[slot] = state["token"]
        self.slot_len[slot] = state["len"]

    # cost hooks (engine uses wall-clock when run_real is used instead)
    def decode_step_time(self, batch) -> float:
        t0 = time.perf_counter()
        self.decode_step_real()
        return time.perf_counter() - t0

    def prompt_for(self, req: Request) -> np.ndarray:
        """The request's synthetic prompt: a function of (seed, rid) only,
        so a served run and a reference see the same tokens whatever the
        admission order."""
        rng = np.random.default_rng((self.seed, req.rid))
        return rng.integers(0, self.cfg.vocab_size, size=req.prompt_len,
                            dtype=np.int32)

    def prefill_time(self, req: Request) -> float:
        t0 = time.perf_counter()
        self.prefill_request(req, self.prompt_for(req))
        return time.perf_counter() - t0


def _quantize_bundles(bundles: Dict, mode: str) -> Dict:
    """Pack fp adapter banks into int8 values + per-output-channel f32
    scales (`kernels/adapter_quant.py`).  Diag Sigma (already tiny) stays
    fp; `cluster_of` passes through."""
    def one_target(tp):
        if "A" in tp:                              # raw LoRA
            aq, a_s = adapter_quantize(tp["A"])
            bq, b_s = adapter_quantize(tp["B"])
            return {"A_q": aq, "A_s": a_s, "B_q": bq, "B_s": b_s}
        uq, u_s = adapter_quantize(tp["U"])
        vq, v_s = adapter_quantize(tp["V"], axis=-2)
        out = {"U_q": uq, "U_s": u_s, "V_q": vq, "V_s": v_s,
               "cluster_of": tp["cluster_of"]}
        sigma = tp["sigma"]
        if sigma.ndim >= 4:                        # (L, n, r, r) full
            sq, s_s = adapter_quantize(sigma)
            out["sigma_q"], out["sigma_s"] = sq, s_s
        else:                                      # (L, n, r) diag
            out["sigma"] = sigma
        return out
    return {"layers": {t: one_target(tp)
                       for t, tp in bundles["layers"].items()}}


def _dequantize_target(tp: Dict) -> Dict:
    """fp32 view of one (possibly packed) target bank, traceable in-jit."""
    if "A_q" in tp:
        return {"A": kref.adapter_dequant_ref(tp["A_q"], tp["A_s"]),
                "B": kref.adapter_dequant_ref(tp["B_q"], tp["B_s"])}
    if "U_q" in tp:
        out = {"U": kref.adapter_dequant_ref(tp["U_q"], tp["U_s"]),
               "V": kref.adapter_dequant_ref(tp["V_q"], tp["V_s"]),
               "cluster_of": tp["cluster_of"]}
        out["sigma"] = (tp["sigma"] if "sigma" in tp else
                        kref.adapter_dequant_ref(tp["sigma_q"],
                                                 tp["sigma_s"]))
        return out
    return tp


def _dequantize_bundles(bundles: Dict) -> Dict:
    return {"layers": {t: _dequantize_target(tp)
                       for t, tp in bundles["layers"].items()}}


def _one_stack(bundles: Dict) -> Dict:
    """An MoE model's adapter stacks (`transformer.lora_defs_tree`: the
    leading dense layers', then the expert layers') joined into one
    ``layers`` stack by cache layer; one stack is returned as it is."""
    if "dense_layers" not in bundles:
        return bundles
    return {"layers": jax.tree.map(lambda a, b: jnp.concatenate([a, b]),
                                   bundles["dense_layers"],
                                   bundles["layers"])}


def _split_stack(bundles: Dict, first_k: int) -> Dict:
    """:func:`_one_stack` undone for `transformer.prefill`: the first
    ``first_k`` layers' adapters in ``dense_layers``, the rest in
    ``layers``."""
    if not first_k:
        return bundles
    return {"dense_layers": jax.tree.map(lambda a: a[:first_k],
                                         bundles["layers"]),
            "layers": jax.tree.map(lambda a: a[first_k:], bundles["layers"])}


def derive_cost_constants(samples) -> Dict[str, float]:
    """Fit the simulator's decode cost model t(B) ~= c0 + c1 * B to real
    measured (batch, seconds) pairs from `benchmarks/real_decode.py`.

    The fit keeps `CostModelExecutor`'s constants (`ServingHardware`'s
    ``step_overhead`` and the per-token roofline term) auditable against
    the fused executor's wall clock: the benchmark embeds this dict in its
    ``--json`` output, so when the kernels speed up, the drift between the
    simulated and real cost model is a number in the report instead of a
    silent divergence."""
    b = np.asarray([s[0] for s in samples], np.float64)
    t = np.asarray([s[1] for s in samples], np.float64)
    if b.size < 2 or np.all(b == b[0]):
        raise ValueError("need samples at >= 2 distinct batch sizes")
    M = np.stack([np.ones_like(b), b], axis=1)
    coef, *_ = np.linalg.lstsq(M, t, rcond=None)
    pred = M @ coef
    denom = float(np.sum((t - t.mean()) ** 2)) or 1.0
    return {"step_overhead_s": float(max(coef[0], 0.0)),
            "per_slot_s": float(max(coef[1], 0.0)),
            "r2": 1.0 - float(np.sum((t - pred) ** 2)) / denom,
            "n_samples": int(b.size)}


def _batch_dim(x) -> int:
    # caches: kv (L,B,S,Kv,hd) -> 1; hybrid (G,P,B,...) -> 2 for conv/state,
    # (G,B,S,..) -> 1 for kv; audio cross (L,B,S,..) -> 1
    return {5: 1, 6: 2, 4: 1, 3: 1, 2: 0}.get(x.ndim, 1)
