"""RealModelExecutor decode-path parity: the fused and fused_q8 paths must
reproduce the unfused (baseline-bit-exact) path on a reduced model, and the
engine must refuse a decode-path mismatch between config and executor."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import transformer as tf
from repro.models.param import init_params
from repro.serving.engine import EngineConfig, ModelFootprint, ServingEngine
from repro.serving.real_executor import (DECODE_PATHS, RealModelExecutor,
                                         derive_cost_constants)
from repro.serving.request import Request
from repro.serving.scheduler import SchedulerConfig


@pytest.fixture(scope="module")
def setup():
    cfg = dc.replace(smoke_config("mistral-7b"), num_layers=2, d_model=64,
                     num_heads=2, num_kv_heads=1, d_ff=128, vocab_size=64)
    params = init_params(tf.model_defs(cfg), jax.random.PRNGKey(0))
    L, n, r = cfg.num_layers, 4, 8
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (cfg.num_heads * hd, d)}
    ks = jax.random.split(jax.random.PRNGKey(7), 2 * len(dims))
    bundles = {"layers": {}}
    for i, (t, (di, do)) in enumerate(dims.items()):
        bundles["layers"][t] = {
            "A": 0.05 * jax.random.normal(ks[2 * i], (L, n, r, di),
                                          jnp.float32),
            "B": 0.05 * jax.random.normal(ks[2 * i + 1], (L, n, do, r),
                                          jnp.float32)}
    return cfg, params, bundles, n


def _executor(setup, path):
    cfg, params, bundles, n = setup
    return RealModelExecutor(cfg, params, bundles, "lora", max_batch=8,
                             s_max=64, decode_path=path)


def _prefill_all(ex, n, prompts):
    for rid, prompt in prompts.items():
        ex.prefill_request(Request(rid=rid, adapter_id=rid % n,
                                   prompt_len=len(prompt),
                                   max_new_tokens=8), prompt)


def _prompts(count=4):
    rng = np.random.default_rng(0)
    return {rid: rng.integers(0, 36, size=6 + rid).astype(np.int32)
            for rid in range(count)}


def test_fused_path_matches_unfused_tokens_and_logits(setup):
    cfg, params, bundles, n = setup
    prompts = _prompts()
    e_u, e_f = _executor(setup, "unfused"), _executor(setup, "fused")
    _prefill_all(e_u, n, prompts)
    _prefill_all(e_f, n, prompts)
    tokens = jnp.asarray(e_u.slot_tokens[:, None])
    ids = jnp.asarray(e_u.slot_adapter)
    l_u, _ = e_u._decode(e_u.params, e_u.bundles, tokens, e_u.cache, ids)
    l_f, _ = e_f._decode(e_f.params, e_f.bundles, tokens, e_f.cache, ids,
                         bucket=e_f._bucket())
    # one bf16 ulp at logit magnitude, over the real vocabulary (the
    # padding columns of the unembedding are not tokens); the argmax
    # stream is identical below
    V = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(l_u[..., :V], np.float32),
                               np.asarray(l_f[..., :V], np.float32),
                               rtol=0, atol=8e-3)
    e_u2, e_f2 = _executor(setup, "unfused"), _executor(setup, "fused")
    _prefill_all(e_u2, n, prompts)
    _prefill_all(e_f2, n, prompts)
    for _ in range(4):
        assert e_u2.decode_step_real() == e_f2.decode_step_real()


@pytest.mark.parametrize("path", DECODE_PATHS)
def test_emitted_tokens_stay_inside_the_vocabulary(setup, path):
    """The unembedding is padded to a multiple of 256 columns (vocab 64 ->
    256 here); no prefill or decode step may emit a padding column."""
    cfg, params, bundles, n = setup
    assert cfg.padded_vocab > cfg.vocab_size
    ex = _executor(setup, path)
    _prefill_all(ex, n, _prompts())
    assert (ex.slot_tokens < cfg.vocab_size).all()
    for _ in range(4):
        assert all(0 <= t < cfg.vocab_size
                   for t in ex.decode_step_real().values())
    assert all(len(toks) == 4 for toks in ex.outputs.values())


def test_prompts_are_a_function_of_seed_and_rid(setup):
    cfg, params, bundles, n = setup
    req = Request(rid=3, adapter_id=0, prompt_len=12, max_new_tokens=1)
    a, b = _executor(setup, "unfused"), _executor(setup, "unfused")
    np.testing.assert_array_equal(a.prompt_for(req), b.prompt_for(req))
    other = dc.replace(req, rid=4)
    assert not np.array_equal(a.prompt_for(req), a.prompt_for(other))
    assert (a.prompt_for(req) < cfg.vocab_size).all()


def test_drained_executor_restarts_at_position_zero(setup):
    """Once every slot is released the scalar cache index goes back to 0,
    so the next wave decodes right after its own prompts."""
    cfg, params, bundles, n = setup
    prompts = _prompts(2)
    ex = _executor(setup, "fused")
    _prefill_all(ex, n, prompts)
    ex.decode_step_real()
    for rid in prompts:
        ex.release(rid)
    assert ex._host_len == 0 and int(ex.cache["index"]) == 0
    fresh = _executor(setup, "fused")
    _prefill_all(ex, n, prompts)
    _prefill_all(fresh, n, prompts)
    assert ex.decode_step_real() == fresh.decode_step_real()


def test_fused_q8_shrinks_residency_and_stays_close(setup):
    cfg, params, bundles, n = setup
    e_f, e_q = _executor(setup, "fused"), _executor(setup, "fused_q8")
    ratio = e_f.adapter_bytes(0) / e_q.adapter_bytes(0)
    assert ratio >= 3.0, ratio                 # int8 + per-channel scales
    prompts = _prompts()
    _prefill_all(e_f, n, prompts)
    _prefill_all(e_q, n, prompts)
    tokens = jnp.asarray(e_f.slot_tokens[:, None])
    ids = jnp.asarray(e_f.slot_adapter)
    l_f, _ = e_f._decode(e_f.params, e_f.bundles, tokens, e_f.cache, ids,
                         bucket=e_f._bucket())
    l_q, _ = e_q._decode(e_q.params, e_q.bundles, tokens, e_q.cache, ids,
                         bucket=e_q._bucket())
    err = float(np.max(np.abs(np.asarray(l_f, np.float32)
                              - np.asarray(l_q, np.float32))))
    assert err < 0.5, err                      # rel-err gate territory


def test_engine_rejects_decode_path_mismatch(setup):
    ex = _executor(setup, "fused")
    with pytest.raises(ValueError, match="decode_path"):
        ServingEngine(EngineConfig(scheduler=SchedulerConfig(max_batch=8),
                                   mode="lora", decode_path="unfused"), ex)
    with pytest.raises(ValueError):
        _executor(setup, "nope")
    assert set(DECODE_PATHS) == {"unfused", "fused", "fused_q8"}


def test_footprint_adapter_bits_pricing():
    cfg = smoke_config("mistral-7b")
    fp16 = ModelFootprint.from_config(cfg, rank=16)
    fp8 = ModelFootprint.from_config(cfg, rank=16, adapter_bits=8)
    # vs bf16 the value bytes halve; per-channel f32 scales claw a bit back
    assert fp8.lora_bytes_per_adapter < fp16.lora_bytes_per_adapter / 1.6
    assert fp8.jd_shared_bytes_per_cluster < fp16.jd_shared_bytes_per_cluster
    with pytest.raises(ValueError):
        ModelFootprint.from_config(cfg, adapter_bits=4)


def test_derive_cost_constants_fits_affine_model():
    samples = [(b, 1e-3 + 2e-4 * b) for b in (1, 2, 4, 8)]
    got = derive_cost_constants(samples)
    assert abs(got["step_overhead_s"] - 1e-3) < 1e-7
    assert abs(got["per_slot_s"] - 2e-4) < 1e-8
    assert got["r2"] > 0.999 and got["n_samples"] == 4
    with pytest.raises(ValueError):
        derive_cost_constants([(4, 1.0), (4, 1.1)])
