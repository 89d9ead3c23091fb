"""RealModelExecutor decode-path parity: the fused and fused_q8 paths must
reproduce the unfused (baseline-bit-exact) path on a reduced model, and the
engine must refuse a decode-path mismatch between config and executor.
Decode runs a step ahead of the host: the emitted tokens are a plain greedy
loop's, and a step launched ahead is dropped when occupancy changes."""
import dataclasses as dc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.models import transformer as tf
from repro.models.lora import LoRAContext
from repro.models.param import init_params
from repro.serving import telemetry
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


def _jd_bundles(cfg, n, r=8):
    """A jd collection on q/k/v/o: one shared basis, a Sigma per adapter."""
    L, d, hd = cfg.num_layers, cfg.d_model, cfg.resolved_head_dim
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (cfg.num_heads * hd, d)}
    ks = jax.random.split(jax.random.PRNGKey(11), 3 * len(dims))
    return {"layers": {t: {
        "U": 0.1 * jax.random.normal(ks[3 * i], (L, 1, do, r)),
        "V": 0.1 * jax.random.normal(ks[3 * i + 1], (L, 1, di, r)),
        "sigma": 0.5 * jax.random.normal(ks[3 * i + 2], (L, n, r, r)),
        "cluster_of": jnp.zeros((L, n), jnp.int32)}
        for i, (t, (di, do)) in enumerate(dims.items())}}


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
    l_u, _, s_u = e_u._decode(e_u.params, e_u.bundles, tokens, e_u.cache,
                              ids)
    l_f, _, s_f = e_f._decode(e_f.params, e_f.bundles, tokens, e_f.cache,
                              ids, bucket=e_f._bucket())
    # one bf16 ulp at logit magnitude, over the real vocabulary (the
    # padding columns of the unembedding are not tokens); the argmax
    # stream is identical below
    V = cfg.vocab_size
    np.testing.assert_allclose(np.asarray(l_u[..., :V], np.float32),
                               np.asarray(l_f[..., :V], np.float32),
                               rtol=0, atol=8e-3)
    # each step samples its greedy ids itself, over the real vocabulary
    want = jnp.argmax(l_u[:, -1, :V], -1)[:, None]
    assert s_u["ids"].shape == s_f["ids"].shape == (8, 1)
    assert s_u["ids"].dtype == s_f["ids"].dtype == jnp.int32
    np.testing.assert_array_equal(s_u["ids"], want)
    np.testing.assert_array_equal(s_f["ids"], want)
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


def _req(rid, n, prompt, new=8):
    return Request(rid=rid, adapter_id=rid % n, prompt_len=len(prompt),
                   max_new_tokens=new)


def _greedy_loop(cfg, params, bundles, mode, path, prompts, ids, steps,
                 s_max):
    """Each step's greedy ids of a batch served without the executor:
    `transformer.prefill` of each prompt alone, the caches joined on the
    batch axis, then one step at a time on the argmax of the last, read on
    the host.  The step is `transformer.decode_step`, or on the fused path
    the fused step program on its own: its bf16 logits may tie where
    decode_step's differ by an ulp, and the parity test above holds the two
    programs together."""
    kind = "batched" if mode == "lora" else "jd"

    def ctx(i):
        return LoRAContext(mode=kind, params=None, ids=i, scaling=1.0)

    prefill = jax.jit(lambda p, b, t, c, i: tf.prefill(
        p, {"tokens": t}, cfg, c, lora_params=b, lora_ctx_proto=ctx(i)))
    if path == "unfused":
        step = jax.jit(lambda p, b, t, c, i: tf.decode_step(
            p, t, cfg, c, lora_params=b, lora_ctx_proto=ctx(i)))
    else:
        fused = jax.jit(RealModelExecutor(cfg, params, bundles, mode, len(ids),
                                          s_max, decode_path=path)
                        ._fused_decode_fn, static_argnames=("bucket",))

        def step(*a):
            return fused(*a, bucket=s_max)[:2]
    tokens, caches = [], []
    for prompt, aid in zip(prompts, ids):
        logits, c = prefill(params, bundles, jnp.asarray(prompt[None]),
                            tf.init_cache(cfg, 1, s_max),
                            jnp.asarray([aid], jnp.int32))
        tokens.append(int(jnp.argmax(logits[0, -1, :cfg.vocab_size])))
        caches.append(c)
    # the K/V leaves are (layers, batch, ...); the index is one scalar
    cache = {k: jnp.concatenate([c[k] for c in caches], axis=1)
             for k in ("k", "v")}
    cache["index"] = caches[0]["index"]
    nxt, out = jnp.asarray(tokens, jnp.int32)[:, None], []
    for _ in range(steps):
        logits, cache = step(params, bundles, nxt, cache,
                             jnp.asarray(ids, jnp.int32))
        nxt = jnp.argmax(logits[:, -1, :cfg.vocab_size], -1)[:, None]
        out.append(np.asarray(nxt[:, 0]))
    return np.stack(out, 1)                                   # (B, steps)


def _decode_attrs():
    return [r.attrs for r in telemetry.records()
            if r.name == "executor.decode"]


@pytest.mark.parametrize("path", ["unfused", "fused"])
@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_served_tokens_are_a_greedy_loop_and_run_a_step_ahead(setup, mode,
                                                              path):
    """A full batch served for a whole request length: every token is the
    plain greedy loop's; every step but the first was on the device when
    its call began; no step is launched past the requests' last token; the
    device's cache index is the host mirror after every call."""
    cfg, params, bundles, n = setup
    if mode == "jd":
        bundles = _jd_bundles(cfg, n)
    B, P, N = 4, 6, 8
    rng = np.random.default_rng(1)
    prompts = rng.integers(0, cfg.vocab_size, (B, P)).astype(np.int32)
    ex = RealModelExecutor(cfg, params, bundles, mode, max_batch=B, s_max=64,
                           decode_path=path)
    launched = []
    inner = ex._decode

    def counted(*a, **kw):
        launched.append(ex._host_len)
        return inner(*a, **kw)
    ex._decode = counted
    for rid in range(B):
        ex.prefill_request(_req(rid, n, prompts[rid], N), prompts[rid])
    telemetry.clear()
    for k in range(N):
        ex.decode_step_real()
        assert int(ex.cache["index"]) == ex._host_len
        assert ex._host_len == P + min(k + 2, N)
    want = _greedy_loop(cfg, params, bundles, mode, path, prompts,
                        [rid % n for rid in range(B)], N, 64)
    got = np.array([ex.outputs[rid] for rid in range(B)])
    np.testing.assert_array_equal(got, want)
    assert launched == list(range(P, P + N))   # one launch per token
    assert ex._ahead is None
    attrs = _decode_attrs()
    assert [a["ahead"] for a in attrs] == [0] + [1] * (N - 1)
    assert [a["discarded"] for a in attrs] == [0] * N


@pytest.mark.parametrize("path", ["unfused", "fused"])
def test_release_and_admission_mid_decode_drop_the_step_ahead(setup, path):
    """A request released before its end, with a step in flight, and a new
    one admitted to its slot: the step launched ahead is dropped once, the
    index goes back to the host mirror, and the requests that stayed get
    the tokens of a run that was never interrupted."""
    cfg, params, bundles, n = setup
    prompts = _prompts(3)
    prompts = {rid: p[:6] for rid, p in prompts.items()}

    def served():
        ex = RealModelExecutor(cfg, params, bundles, "lora", max_batch=3,
                               s_max=64, decode_path=path)
        for rid, p in prompts.items():
            ex.prefill_request(_req(rid, n, p, 10), p)
        return ex
    control = served()
    for _ in range(7):
        control.decode_step_real()
    ex = served()
    for _ in range(3):
        ex.decode_step_real()
    assert ex._ahead is not None
    ex.release(2)
    assert int(ex.cache["index"]) == ex._host_len == 6 + 3
    late = _prompts(4)[3][:6]
    ex.prefill_request(_req(3, n, late, 10), late)
    telemetry.clear()
    for _ in range(4):
        ex.decode_step_real()
        assert int(ex.cache["index"]) == ex._host_len
    attrs = _decode_attrs()
    assert attrs[0]["discarded"] == 1 and attrs[0]["ahead"] == 0
    assert [a["discarded"] for a in attrs[1:]] == [0, 0, 0]
    for rid in (0, 1):
        assert ex.outputs[rid] == control.outputs[rid]
    assert len(ex.outputs[2]) == 3 and len(ex.outputs[3]) == 4


@pytest.mark.parametrize("path", ["unfused", "fused"])
def test_export_and_import_mid_decode_drop_the_step_ahead(setup, path):
    """A slot exported with a step in flight and imported into another
    executor: the source drops the step once and continues its other
    request, and both requests' tokens are an uninterrupted run's."""
    cfg, params, bundles, n = setup
    prompts = {rid: p[:6] for rid, p in _prompts(2).items()}

    def executor():
        return RealModelExecutor(cfg, params, bundles, "lora", max_batch=2,
                                 s_max=64, decode_path=path)

    def served():
        ex = executor()
        for rid, p in prompts.items():
            ex.prefill_request(_req(rid, n, p, 10), p)
        return ex
    control = served()
    for _ in range(10):
        control.decode_step_real()
    src = served()
    for _ in range(3):
        src.decode_step_real()
    assert src._ahead is not None
    state = src.export_slot(0)
    assert int(src.cache["index"]) == src._host_len == state["index"] == 9
    src.release(0)
    dst = executor()
    moved = _req(0, n, prompts[0], 10)
    moved.generated = 3
    dst.import_slot(moved, state)
    telemetry.clear()
    for _ in range(7):
        src.decode_step_real()
        dst.decode_step_real()
        for ex in (src, dst):
            assert int(ex.cache["index"]) == ex._host_len
    attrs = _decode_attrs()
    assert [a["discarded"] for a in attrs[::2]] == [1] + [0] * 6
    assert src.outputs[1] == control.outputs[1]
    assert src.outputs[0] + dst.outputs[0] == control.outputs[0]
    # both requests' last tokens: nothing was launched past them
    assert src._ahead is None and dst._ahead is None


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
    l_f, _, _ = e_f._decode(e_f.params, e_f.bundles, tokens, e_f.cache, ids,
                            bucket=e_f._bucket())
    l_q, _, _ = e_q._decode(e_q.params, e_q.bundles, tokens, e_q.cache, ids,
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
