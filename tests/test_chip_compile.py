"""The serving path's Pallas kernels compile for a TPU v5e at qwen3-1.7b
decode widths (B=8, H=16, Kv=8, hd=128, KV window 256, rank 16,
d_model 2048, 64 adapters).

Nothing runs: the TPU compiler installed with JAX compiles for a chip that
is described, not attached, and refuses what Mosaic cannot tile (block
shapes whose two minor dims are not multiples of (8, 128) or whole) or fit.
Interpret-mode tests cannot see either.  Each compile must keep the kernel
as a ``tpu_custom_call``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.adapter_quant import adapter_quantize
from repro.kernels.flash_decode import flash_decode, flash_decode_paged
from repro.kernels.fused_decode import (fused_decode_jd, fused_decode_jd_paged,
                                        fused_decode_lora,
                                        fused_decode_lora_paged)
from repro.kernels.jd_apply import jd_apply
from repro.kernels.kv_quant import kv_dequantize, kv_quantize
from repro.kernels.sgmv import sgmv_expand, sgmv_shrink

B, H, KV, HD, S, R, D, N, L = 8, 16, 8, 128, 256, 16, 2048, 64, 28
PAGES, PAGE_T, T = 32, 128, 256
I32, BF16, F32, I8 = jnp.int32, jnp.bfloat16, jnp.float32, jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one; keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _attn(s):
    return (s((B, H, HD), BF16), s((B, S, KV, HD), BF16),
            s((B, S, KV, HD), BF16), s((B,), I32))


def _paged(s):
    return (s((B, H, HD), BF16), s((PAGES, PAGE_T, KV, HD), BF16),
            s((PAGES, PAGE_T, KV, HD), BF16), s((B, S // PAGE_T), I32),
            s((B,), I32))


def _jd_bank(s, sigma_shape):
    return (s((1, D, R), BF16), s((1, H * HD, R), BF16), s(sigma_shape, BF16),
            s((N,), I32))


# name -> (kernel called with interpret=False, args given the ShapeDtype
# maker).  The wrappers resolve interpret from the backend, which is the
# CPU here, so the compiled kernel is asked for explicitly.
CASES = {
    "flash_decode": (
        lambda *a: flash_decode(*a, interpret=False), _attn),
    "flash_decode_paged": (
        lambda *a: flash_decode_paged(*a, interpret=False), _paged),
    "fused_decode_lora": (
        lambda *a: fused_decode_lora(*a, interpret=False),
        lambda s: _attn(s) + (s((B,), I32), s((N, R, H * HD), BF16),
                              s((N, D, R), BF16))),
    "fused_decode_lora_int8": (
        lambda *a: fused_decode_lora(*a, interpret=False),
        lambda s: _attn(s) + (s((B,), I32), s((N, R, H * HD), I8),
                              s((N, D, R), I8), s((N, R, 1), F32),
                              s((N, D, 1), F32))),
    "fused_decode_lora_paged": (
        lambda *a: fused_decode_lora_paged(*a, interpret=False),
        lambda s: _paged(s) + (s((B,), I32), s((N, R, H * HD), BF16),
                               s((N, D, R), BF16))),
    "fused_decode_jd_full": (
        lambda *a: fused_decode_jd(*a, interpret=False),
        lambda s: _attn(s) + (s((B,), I32),) + _jd_bank(s, (N, R, R))),
    "fused_decode_jd_diag": (
        lambda *a: fused_decode_jd(*a, interpret=False),
        lambda s: _attn(s) + (s((B,), I32),) + _jd_bank(s, (N, R))),
    "fused_decode_jd_paged": (
        lambda *a: fused_decode_jd_paged(*a, interpret=False),
        lambda s: _paged(s) + (s((B,), I32),) + _jd_bank(s, (N, R, R))),
    "sgmv_shrink": (
        lambda *a: sgmv_shrink(*a, interpret=False),
        lambda s: (s((T, D), BF16), s((N, R, D), BF16), s((T // 128,), I32))),
    "sgmv_expand": (
        lambda *a: sgmv_expand(*a, interpret=False),
        lambda s: (s((T, R), BF16), s((N, D, R), BF16), s((T // 128,), I32))),
    "jd_apply_diag": (
        lambda x, U, V, sg, co, ids, tc, ti: jd_apply(
            x, U, V, sg, co, ids, tc, ti, interpret=False),
        lambda s: (s((T, D), BF16), s((1, D, R), BF16), s((1, D, R), BF16),
                   s((N, R), BF16), s((N,), I32), s((T,), I32),
                   s((T // 128,), I32), s((T // 128,), I32))),
    "jd_apply_full": (
        lambda x, U, V, sg, co, ids, tc, ti: jd_apply(
            x, U, V, sg, co, ids, tc, ti, interpret=False),
        lambda s: (s((T, D), BF16), s((1, D, R), BF16), s((1, D, R), BF16),
                   s((N, R, R), BF16), s((N,), I32), s((T,), I32),
                   s((T // 128,), I32), s((T // 128,), I32))),
    "adapter_quantize_rows": (
        lambda w: adapter_quantize(w, interpret=False),
        lambda s: (s((L, N, R, D), BF16),)),
    "adapter_quantize_cols": (
        lambda w: adapter_quantize(w, axis=-2, interpret=False),
        lambda s: (s((L, 1, D, R), BF16),)),
    "kv_quantize_int8": (
        lambda x: kv_quantize(x, bits=8, interpret=False),
        lambda s: (s((128, KV * HD), F32),)),
    "kv_quantize_int4": (
        lambda x: kv_quantize(x, bits=4, interpret=False),
        lambda s: (s((128, KV * HD), F32),)),
    "kv_dequantize_int4": (
        lambda p, sc: kv_dequantize(p, sc, bits=4, interpret=False),
        lambda s: (s((64, KV * HD), jnp.uint8), s((1, KV * HD), F32))),
}


# the served call: layer 2 of a layer-stacked (4, 32, 768, 8, 128) cache,
# attended over a 640-token window, with the o-bank's layer-stacked banks
# read in place, at qwen3-1.7b (H 16, d_model 2048) and mistral-7b (H 32,
# d_model 4096) widths
SB, SL, SS, SW, SN = 32, 4, 768, 640, 1000


def _stacked_attn(s, h):
    return (s((SB, h, HD), BF16), s((SL, SB, SS, KV, HD), BF16),
            s((SL, SB, SS, KV, HD), BF16), s((SB,), I32), s((SB,), I32))


def _d(h):
    return {16: 2048, 32: 4096}[h]


def _stacked_jd(s, h, sigma_shape):
    return _stacked_attn(s, h) + (
        s((SL, 1, _d(h), R), BF16), s((SL, 1, h * HD, R), BF16),
        s((SL, SN) + sigma_shape, BF16), s((SL, SN), I32))


def _stacked(kernel):
    return lambda *a: kernel(*a, layer=2, window=SW, interpret=False)


for _h in (16, 32):
    CASES[f"fused_decode_jd_full_stacked_h{_h}"] = (
        _stacked(fused_decode_jd),
        lambda s, h=_h: _stacked_jd(s, h, (R, R)))
    CASES[f"fused_decode_jd_diag_stacked_h{_h}"] = (
        _stacked(fused_decode_jd),
        lambda s, h=_h: _stacked_jd(s, h, (R,)))
    CASES[f"fused_decode_lora_stacked_h{_h}"] = (
        _stacked(fused_decode_lora),
        lambda s, h=_h: _stacked_attn(s, h) + (
            s((SL, SN, R, h * HD), BF16), s((SL, SN, _d(h), R), BF16)))


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
    kernel, args = CASES[name]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    compiled = jax.jit(kernel).lower(*args(shape)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_fused_step_reads_the_cache_in_place(one_chip, monkeypatch):
    """The served fused jd decode step (the executor's jitted step, cache
    donated) at qwen3-1.7b widths and three layers, compiled for a v5e:
    the kernels read K/V and the o-bank from the layer-stacked arrays, so
    the program holds no slice, reshape or copy with a bf16 output of a
    K/V window or more (B x 128 x Kv x hd elements) — not of a window, not
    of the whole cache — and one kernel per layer."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.param import init_params
    from repro.serving.real_executor import RealModelExecutor

    cfg = dataclasses.replace(get_config("qwen3-1.7b"), num_layers=3)
    nl, b, s_max, bucket = cfg.num_layers, 8, 768, 640

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(x):
        return shape(x.shape, x.dtype)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(tf.model_defs(cfg), jax.random.PRNGKey(0))))
    d, hq, hk = cfg.d_model, cfg.num_heads * HD, cfg.num_kv_heads * HD
    bundles = {"layers": {
        t: {"U": shape((nl, 1, do, R), BF16), "V": shape((nl, 1, di, R), BF16),
            "sigma": shape((nl, SN, R, R), BF16),
            "cluster_of": shape((nl, SN), I32)}
        for t, (di, do) in {"q": (d, hq), "k": (d, hk), "v": (d, hk),
                            "o": (hq, d)}.items()}}
    ex = RealModelExecutor(cfg, params, bundles, "jd", b, s_max,
                           decode_path="fused")
    cache = jax.tree.map(on_chip, ex.cache)
    # steer the kernels' dispatch to the chip's path: the CPU backend here
    # would pick the jnp oracles and the Pallas interpreter
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    txt = ex._decode.lower(params, bundles, shape((b, 1), I32), cache,
                           shape((b,), I32), bucket=bucket).compile().as_text()
    jax.clear_caches()

    # what the program materializes: the entry computation's instructions
    # (a slice inside a fusion is read in place)
    entry = txt.split("\nENTRY", 1)[1].split("\n}\n", 1)[0]
    limit = b * 128 * cfg.num_kv_heads * HD
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if not m or m.group(2) not in ("slice", "reshape", "copy",
                                       "copy-start"):
            continue
        for dims in re.findall(r"bf16\[([\d,]*)\]", m.group(1)):
            n = 1
            for x in filter(None, dims.split(",")):
                n *= int(x)
            assert n < limit, line[:240]
    kernels = re.findall(r"%fused_decode_jd(?:\.\d+)? = .*custom-call", txt)
    assert len(kernels) == nl


def test_moe_step_and_prefill_compile_for_v5e(one_chip, monkeypatch):
    """deepseek-moe-16b's share of an 8-way expert split (8 of 64 experts
    held, 2 shared, MHA 16/16) at published widths and three layers, the
    leading dense one and two expert layers, compiled for a v5e: the fused
    jd step has one kernel per layer and holds no copy of an expert's
    weights (the layers read the stacked experts in place); the batch-1
    prefill groups its routed pairs into the chip's ragged matmul."""
    import dataclasses
    import re

    from repro.configs import get_config
    from repro.models import transformer as tf
    from repro.models.param import init_params
    from repro.serving import real_executor

    full = get_config("deepseek-moe-16b")
    cfg = dataclasses.replace(full, num_layers=3, moe=dataclasses.replace(
        full.moe, held_experts=8))
    b, s_max, bucket = 32, 384, 256

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def on_chip(x):
        return shape(x.shape, x.dtype)

    params = jax.tree.map(on_chip, jax.eval_shape(
        lambda: init_params(tf.model_defs(cfg), jax.random.PRNGKey(0),
                            dtype_override=BF16)))
    d, hq = cfg.d_model, cfg.num_heads * HD
    bundles = {stack: {
        t: {"U": shape((nl, 1, do, R), BF16), "V": shape((nl, 1, di, R), BF16),
            "sigma": shape((nl, SN, R, R), BF16),
            "cluster_of": shape((nl, SN), I32)}
        for t, (di, do) in {"q": (d, hq), "k": (d, hq), "v": (d, hq),
                            "o": (hq, d)}.items()}
        for stack, nl in (("layers", 2), ("dense_layers", 1))}
    # the one (L, …) stack the fused path serves from
    bundles = jax.tree.map(on_chip, jax.eval_shape(real_executor._one_stack,
                                                   bundles))
    ex = real_executor.RealModelExecutor(cfg, params, bundles, "jd", b, s_max,
                                         decode_path="fused")
    cache = jax.tree.map(on_chip, ex.cache)
    c1 = jax.tree.map(on_chip, tf.init_cache(cfg, 1, s_max, abstract=True))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    step = ex._decode.lower(params, bundles, shape((b, 1), I32), cache,
                            shape((b,), I32), bucket=bucket).compile()
    prefill = ex._prefill.lower(params, bundles, shape((1, 128), I32), c1,
                                shape((1,), I32)).compile()
    jax.clear_caches()

    txt = step.as_text()
    entry = txt.split("\nENTRY", 1)[1].split("\n}\n", 1)[0]
    f = cfg.moe.d_ff_expert
    for line in entry.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = (.+?) ([\w-]+)\(", line)
        if m and m.group(2) in ("slice", "copy", "copy-start", "dynamic-slice"):
            for dims in re.findall(r"bf16\[([\d,]*)\]", m.group(1)):
                assert str(f) not in dims.split(","), line[:240]
    kernels = re.findall(r"%fused_decode_jd(?:\.\d+)? = .*custom-call", txt)
    assert len(kernels) == cfg.num_layers
    assert "ragged-dot" in prefill.as_text()
