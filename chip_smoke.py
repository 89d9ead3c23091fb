"""Smoke run of the served multi-LoRA decode path on one TPU chip.

    python chip_smoke.py [--seed N]

Builds qwen3-1.7b at its published widths and depth (28 layers, d_model
2048, vocab 151936) with random weights and 64 random rank-16 adapters on
q/k/v/o, all made from ``--seed``.  Two phases run in this one process:
``lora`` (a raw A/B bank per adapter) and ``jd`` (one shared basis with a
full Sigma per adapter).  Each phase serves 16 requests of 128 prompt
tokens and 16 new tokens through `ServingEngine` over `RealModelExecutor`
on the fused decode path (max_batch 8, KV window 256), and checks:

* every request finishes with exactly 16 tokens, each below the vocabulary;
* the compiled fused decode step holds a Pallas TPU kernel
  (``tpu_custom_call``), so the kernels ran and not a fallback;
* on one prefilled batch, the fused step's logits match those of the
  generic unfused `transformer.decode_step` over the same adapters: the
  RMS of their difference is within ``LOGIT_RTOL`` of the RMS logit.

Any failed check exits non-zero; so does a platform other than ``tpu``.
Earlier lines are smoke readings (compile seconds, the engine's per-token
decode time, peak device bytes), not benchmark results.  The last line of standard
output is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ARCH = "qwen3-1.7b"
N_ADAPTERS = 64
MAX_BATCH = 8
S_MAX = 256
N_REQUESTS = 16
PROMPT_LEN = 128      # equal lengths: the cache's scalar index is exact
NEW_TOKENS = 16       # 128 + 16 tokens stay inside one 256-token KV bucket
# bf16 tolerance of fused vs unfused logits, as RMS(difference) over
# RMS(unfused logits).  The two paths round the attention output and the
# adapter delta to bf16 at different points; the one-ulp differences of
# one layer grow over 28 (0.038 lora / 0.032 jd, measured on the CPU at
# 28 layers of width 128).  A kernel that reads the wrong head, block or
# length is off by O(1).
LOGIT_RTOL = 2.0 ** -3


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr)
    sys.exit(1)


class CompileClock:
    """Seconds JAX spends in backend compiles (a persistent-cache hit
    counts only its read) and the number of persistent-cache hits."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1


def fused_vs_unfused_error(ex, reqs) -> dict:
    """Prefill one batch, then compare the next decode step's logits on the
    fused path against the unfused reference from the same state, over the
    real vocabulary.  ``rms`` is the gated error; ``max`` is max |diff|
    over max |unfused|; ``adapters_shifted`` is the unfused step's own RMS
    change when every slot takes its neighbour's adapter, for scale.  The
    executor is drained again afterwards."""
    import jax
    import jax.numpy as jnp

    for req in reqs:
        ex.prefill_request(req, ex.prompt_for(req))
    tokens = jnp.asarray(ex.slot_tokens[:, None])
    ids = jnp.asarray(ex.slot_adapter)
    unfused = jax.jit(ex._decode_fn)
    V = ex.cfg.vocab_size

    def last(logits):
        return np.asarray(logits[:, -1, :V], np.float32)

    ref = last(unfused(ex.params, ex.bundles, tokens, ex.cache, ids)[0])
    shifted = last(unfused(ex.params, ex.bundles, tokens, ex.cache,
                           jnp.roll(ids, 1))[0])
    got, ex.cache, _ = ex._decode(ex.params, ex.bundles, tokens, ex.cache,
                                  ids, bucket=ex._bucket())
    got = last(got)
    if not (np.isfinite(ref).all() and np.isfinite(got).all()):
        fail("non-finite logits")
    for req in reqs:
        ex.release(req.rid)

    def rms(x):
        return float(np.sqrt(np.mean(np.square(x))))

    return {"rms": rms(got - ref) / rms(ref),
            "max": float(np.max(np.abs(got - ref)) / np.max(np.abs(ref))),
            "adapters_shifted": rms(shifted - ref) / rms(ref)}


def fused_step_hlo(ex) -> str:
    """Compiled HLO text of the fused decode step at the serving bucket."""
    import jax.numpy as jnp

    tokens = jnp.zeros((ex.max_batch, 1), jnp.int32)
    ids = jnp.zeros((ex.max_batch,), jnp.int32)
    return ex._decode.lower(ex.params, ex.bundles, tokens, ex.cache, ids,
                            bucket=S_MAX).compile().as_text()


def run_phase(cfg, mode: str, seed: int, clock: CompileClock) -> dict:
    """Serve one adapter mode end to end and check what came out."""
    from repro.launch.serve import build_real_executor, serve_real
    from repro.serving.simulator import WorkloadConfig, make_workload

    t0 = time.perf_counter()
    c0, h0 = clock.seconds, clock.cache_hits
    ex = build_real_executor(cfg, N_ADAPTERS, mode, MAX_BATCH, S_MAX, seed,
                             decode_path="fused")
    wl = WorkloadConfig(n_requests=N_REQUESTS, n_adapters=N_ADAPTERS,
                        prompt_len_mean=PROMPT_LEN, prompt_len_std=0,
                        new_tokens=NEW_TOKENS, seed=seed)
    reqs = make_workload(wl)
    if any(r.prompt_len != PROMPT_LEN for r in reqs):
        fail("workload drew unequal prompt lengths")

    err = fused_vs_unfused_error(ex, reqs[:MAX_BATCH])
    if not err["rms"] <= LOGIT_RTOL:
        fail(f"{mode}: fused logits differ from unfused by {err['rms']:.3g} "
             f"of the RMS logit (limit {LOGIT_RTOL:.3g})")
    if "tpu_custom_call" not in fused_step_hlo(ex):
        fail(f"{mode}: the fused decode step holds no Pallas TPU kernel")

    stats = serve_real(ex, wl)
    if stats["n_requests"] != N_REQUESTS:
        fail(f"{mode}: {stats['n_requests']} of {N_REQUESTS} finished")
    for req in reqs:
        toks = ex.outputs.get(req.rid, [])
        if len(toks) != NEW_TOKENS:
            fail(f"{mode}: request {req.rid} got {len(toks)} tokens")
        if not all(0 <= t < cfg.vocab_size for t in toks):
            fail(f"{mode}: request {req.rid} emitted a token id outside "
                 f"[0, {cfg.vocab_size}): {toks}")
    # the batch decodes in two full waves with no admission in between,
    # so time per output token is the wall time of one decode step
    return {"phase": mode, "logit_rel_err": err,
            "compile_s": clock.seconds - c0,
            "cache_hits": clock.cache_hits - h0,
            "phase_wall_s": time.perf_counter() - t0,
            "decode_step_ms_p50": 1e3 * stats["tpot_p50_s"],
            "decode_step_ms_p99": 1e3 * stats["tpot_p99_s"]}


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.configs import get_config
    from repro.launch.serve import use_compile_cache

    cache_dir = use_compile_cache()
    clock = CompileClock()
    cfg = get_config(ARCH)
    print(f"# smoke readings, not benchmark results; compile cache "
          f"{cache_dir}", flush=True)
    for mode in ("lora", "jd"):
        reading = run_phase(cfg, mode, args.seed, clock)
        print(json.dumps(reading), flush=True)
        gc.collect()
    peak = dev.memory_stats().get("peak_bytes_in_use")
    print(json.dumps({"peak_bytes_in_use": peak}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
