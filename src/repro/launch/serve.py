"""Serving launcher: multi-LoRA continuous-batching server (real model or
cost-model simulation).

  # paper-style throughput study (simulated clock, v5e cost model)
  PYTHONPATH=src python -m repro.launch.serve --arch mistral-7b \\
      --study 4,64,256,1024 --requests 500

  # real reduced-model serving on CPU
  PYTHONPATH=src python -m repro.launch.serve --arch mistral-7b --smoke \\
      --real --adapters 8 --requests 24
"""
from __future__ import annotations

import argparse
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config, smoke_config
from repro.serving.engine import EngineConfig, ServingEngine
from repro.serving.scheduler import SchedulerConfig
from repro.serving.simulator import WorkloadConfig, make_workload, \
    run_throughput_study

# fixed, so every run from this checkout finds what earlier runs compiled
# (the directory is part of the cache key; it is listed in .gitignore)
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"
ADAPTER_TARGETS = ("q", "k", "v", "o")


def use_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives at :data:`COMPILE_CACHE_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)


def make_bundles(cfg, n_adapters: int, mode: str, seed: int = 0) -> dict:
    """Random adapters on q/k/v/o (paper §6.4 simulates random LoRAs for
    throughput), layer-stacked in the layout `RealModelExecutor` takes.
    ``mode="jd"`` is one shared basis (U, V) with a full per-adapter
    Sigma.  Every array is a function of ``seed`` and the target's index."""
    r = cfg.lora.rank
    d = cfg.d_model
    hd = cfg.resolved_head_dim
    L = cfg.num_layers
    key = jax.random.PRNGKey(seed + 1)
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (cfg.num_heads * hd, d)}
    bundles = {"layers": {}}
    for i, tname in enumerate(ADAPTER_TARGETS):
        di, do = dims[tname]
        ka, kb, ks = jax.random.split(jax.random.fold_in(key, i), 3)
        if mode == "lora":
            bundles["layers"][tname] = {
                "A": jax.random.normal(ka, (L, n_adapters, r, di),
                                       jnp.bfloat16) * 0.02,
                "B": jax.random.normal(kb, (L, n_adapters, do, r),
                                       jnp.bfloat16) * 0.02}
        else:
            bundles["layers"][tname] = {
                "U": jax.random.normal(ka, (L, 1, do, r), jnp.bfloat16) * 0.02,
                "V": jax.random.normal(kb, (L, 1, di, r), jnp.bfloat16) * 0.02,
                "sigma": jax.random.normal(ks, (L, n_adapters, r, r),
                                           jnp.bfloat16) * 0.1,
                "cluster_of": jnp.zeros((L, n_adapters), jnp.int32)}
    return bundles


def build_real_executor(cfg, n_adapters: int, mode: str = "jd",
                        max_batch: int = 8, s_max: int = 160, seed: int = 0,
                        decode_path: str = "unfused"):
    """A `RealModelExecutor` over random weights and adapters made from
    ``seed``.  ``decode_path`` selects the decode step: "unfused" is the
    generic `transformer.decode_step`; "fused"/"fused_q8" run the one-pass
    kernel of `kernels/fused_decode.py`."""
    from repro.models import transformer as tf
    from repro.models.param import init_params
    from repro.serving.real_executor import RealModelExecutor

    params = init_params(tf.model_defs(cfg), jax.random.PRNGKey(seed))
    bundles = make_bundles(cfg, n_adapters, mode, seed)
    return RealModelExecutor(cfg, params, bundles, mode, max_batch, s_max,
                             decode_path=decode_path, seed=seed)


def serve_real(ex, wl: WorkloadConfig) -> dict:
    """Serve the workload `wl` through a `ServingEngine` over executor
    `ex`; the tokens each request got are in ``ex.outputs``."""
    eng = ServingEngine(EngineConfig(
        scheduler=SchedulerConfig(max_batch=ex.max_batch),
        adapter_budget_bytes=1e12, mode="lora",
        decode_path=ex.decode_path), ex)
    eng.on_finish = lambda req: ex.release(req.rid)
    eng.submit(make_workload(wl))
    return eng.run().to_dict()


def run_real(cfg, n_adapters: int, n_requests: int, mode: str = "jd",
             max_batch: int = 8, seed: int = 0,
             decode_path: str = "unfused") -> dict:
    """Real execution path: real prefill/decode with batched adapter math
    on random weights, prompts of 24±4 tokens and 8 new tokens each."""
    ex = build_real_executor(cfg, n_adapters, mode, max_batch, seed=seed,
                             decode_path=decode_path)
    return serve_real(ex, WorkloadConfig(
        n_requests=n_requests, n_adapters=n_adapters, prompt_len_mean=24,
        prompt_len_std=4, new_tokens=8, seed=seed))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--study", default=None,
                    help="comma list of adapter counts for the Fig-1 study")
    ap.add_argument("--requests", type=int, default=500)
    ap.add_argument("--real", action="store_true")
    ap.add_argument("--adapters", type=int, default=8)
    ap.add_argument("--mode", default="jd", choices=["jd", "lora"])
    ap.add_argument("--decode-path", default="unfused",
                    choices=["unfused", "fused", "fused_q8"])
    args = ap.parse_args()

    use_compile_cache()
    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.study:
        ns = [int(x) for x in args.study.split(",")]
        rows = run_throughput_study(
            cfg, ns, WorkloadConfig(n_requests=args.requests))
        for r in rows:
            print(json.dumps(r, indent=None, default=str))
    elif args.real:
        out = run_real(cfg, args.adapters, args.requests, args.mode,
                       decode_path=args.decode_path)
        print(json.dumps(out, indent=2))


if __name__ == "__main__":
    main()
