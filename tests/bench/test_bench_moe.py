"""The benchmark's MoE configuration, ``deepseek-moe-16b-ep8``, at smoke
widths on the CPU: read through `bench.spec` as the program's config, served
by the fused path (prefill, then decoding through the cache) against the
plain float32 reference's full forward pass, the reference's held shares
against its uncut layer, and the cell's two per-layer metrics on a
hand-made record with the program's expert counters."""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, trace, weights
from bench.costs import BF16

CELL = "deepseek-moe-16b-ep8.jd1000.short"
SMOKE = dict(hidden_size=128, intermediate_size=256, moe_intermediate_size=64,
             num_attention_heads=4, num_key_value_heads=4,
             num_hidden_layers=4, vocab_size=512)
PEAK = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _smoke(**program):
    cell = spec.cell(CELL)
    # each matrix's gain at the published width, 0.02 x sqrt(2048) = 0.9,
    # at width 128: the experts' share of the residual stream as at full size
    conf = dict(cell.config, initializer_range=0.08, **SMOKE)
    conf["program"] = dict(conf["program"], **program)
    tr = dict(cell.traffic, clients=4, max_batch=4, prompt_len=16,
              output_len=12, s_max=64)
    tr["adapters"] = dict(tr["adapters"], count=16)
    return conf, tr


def test_the_configuration_is_the_program():
    cell = spec.cell(CELL)
    conf = cell.config
    cfg = spec.model_config(conf, cell.traffic)
    m = cfg.moe
    assert (m.num_experts, m.held, m.expert_offset) == (64, 8, 0)
    assert (m.top_k, m.num_shared, m.d_ff_expert) == (6, 2, 1408)
    assert (m.first_k_dense, m.d_ff_dense) == (1, 10944)
    assert m.norm_topk_prob is False
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads,
            cfg.resolved_head_dim, cfg.vocab_size) == (28, 2048, 16, 16, 128,
                                                       102400)
    assert cfg.norm_eps == 1e-6 and cfg.rope_theta == 1e4
    assert not cfg.tie_embeddings
    assert conf["reduced"] == ["n_routed_experts"]
    assert conf["published"] == {"n_routed_experts": 64}
    # the chip's share: 3,296 M parameters, 6.59 GB in bf16
    a = spec.arch(conf["reference"]).arch(conf)
    held = a._matmul_params(a.E) + a.d * a.vocab * 2
    assert held == pytest.approx(3.296e9, rel=1e-3)


def test_the_smoke_program_holds_its_share():
    conf, tr = _smoke(expert_offset=8)
    cfg = spec.model_config(conf, tr)
    params = jax.eval_shape(lambda: weights.make_params(cfg, 1, 0.02))
    moe = params["layers"]["moe"]
    assert moe["router"].shape == (3, 128, 64)
    assert moe["w_gate"].shape == (3, 8, 128, 64)
    assert moe["shared"]["w_gate"].shape == (3, 128, 128)
    assert params["dense_layers"]["mlp"]["w_gate"].shape == (1, 128, 256)


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "normalised"])
def test_the_reference_shares_add_up_to_its_uncut_layer(norm):
    ref = spec.reference("moe_gqa_share")
    d, f, E = 32, 16, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    p = {"router": jax.random.normal(ks[0], (d, E)),
         "w_gate": jax.random.normal(ks[1], (E, d, f)) / 6,
         "w_up": jax.random.normal(ks[2], (E, d, f)) / 6,
         "w_down": jax.random.normal(ks[3], (E, f, d)) / 4,
         "shared": {"w_gate": jax.random.normal(ks[4], (d, f)) / 6,
                    "w_up": jax.random.normal(ks[4], (d, f)) / 6,
                    "w_down": jax.random.normal(ks[5], (f, d)) / 4}}
    m = jax.random.normal(jax.random.PRNGKey(1), (2, 9, d))
    whole = ref._moe(p, m, 6, norm, 0, None)
    shared = ref._ffn(p["shared"], m, None)
    got = shared
    for o in range(0, E, 8):
        share = dict(p, **{k: p[k][o:o + 8]
                           for k in ("w_gate", "w_up", "w_down")})
        got = got + ref._moe(share, m, 6, norm, o, None) - shared
    np.testing.assert_allclose(np.asarray(got), np.asarray(whole),
                               rtol=1e-5, atol=1e-5)


def _serve_logits(conf, tr, seed, mode="jd"):
    """The fused path's logits: each request's prefill (its last prompt
    position), then each decode step through the cache, greedy; its
    weights; and the reference's logits at the same positions of the same
    tokens, ``reference(params, conf)``."""
    from repro.serving.real_executor import RealModelExecutor
    from repro.serving.request import Request

    cfg = spec.model_config(conf, tr)
    ad = dict(tr["adapters"], mode=mode)
    if mode == "lora":
        for k in ("clusters", "sigma"):
            ad.pop(k)
    init = conf["initializer_range"]
    mod = spec.arch(conf["reference"])
    params = weights.make_params(cfg, seed, init)
    bundles = weights.make_adapters(cfg, mod.adapter_dims(conf), ad, seed,
                                    init)
    B, P, N = tr["max_batch"], tr["prompt_len"], tr["output_len"]
    ex = RealModelExecutor(cfg, params, bundles, mode, B, tr["s_max"],
                           decode_path="fused")
    first = []
    inner = ex._prefill

    def prefill(*a):
        out = inner(*a)
        first.append(out[0][0, -1])
        return out
    ex._prefill = prefill
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, conf["vocab_size"], (B, P)).astype(np.int32)
    ids = rng.integers(0, ad["count"], B).astype(np.int32)
    for b in range(B):
        ex.prefill_request(Request(rid=b, adapter_id=int(ids[b]),
                                   prompt_len=P, max_new_tokens=N), prompts[b])
    steps = [jnp.stack(first)]
    fed = [ex.slot_tokens.copy()]
    for _ in range(N):
        tokens = jnp.asarray(ex.slot_tokens[:, None])
        lg, ex.cache, _ = ex._decode(ex.params, ex.bundles, tokens, ex.cache,
                                     jnp.asarray(ex.slot_adapter),
                                     bucket=ex._bucket())
        ex._host_len += 1
        steps.append(lg[:, 0])
        ex.slot_tokens[:] = np.asarray(jnp.argmax(
            lg[:, 0, :cfg.vocab_size], -1))
        fed.append(ex.slot_tokens.copy())
    got = np.stack([np.asarray(s[:, :cfg.vocab_size], np.float32)
                    for s in steps], 1)                   # (B, N + 1, V)
    inputs = np.concatenate([prompts, np.stack(fed[:-1], 1)], 1)
    ref = spec.reference(conf["reference"])

    def reference(params, conf):
        return np.asarray(ref.logits(params, bundles, conf, mode, inputs, ids,
                                     P - 1), np.float32)
    return got, params, reference


# widest gap, over every served position's logits, as a share of the
# largest reference logit: bf16 weights on both sides, and the program
# rounds its activations, its K/V and each layer's output to bf16 (8 bits
# of mantissa) where the reference keeps float32.  Seeds 1-6 read 0.011-
# 0.0165 (jd and lora); the faults below read 0.030 and more.
LIMIT = 0.025


def _gap(got, want):
    return float(np.abs(got - want).max() / np.abs(want).max())


@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_prefill_then_decode_matches_the_reference(mode):
    conf, tr = _smoke(expert_offset=24)
    got, params, reference = _serve_logits(conf, tr, 3, mode)
    want = reference(params, conf)
    assert got.shape == want.shape == (4, 13, 512)
    assert _gap(got, want) < LIMIT
    # the rounding moves no served token off the reference's best by more
    best = np.take_along_axis(want, got.argmax(-1)[..., None], -1)[..., 0]
    assert (want.max(-1) - best).max() < LIMIT * np.abs(want).max()


def _no_held_experts(params, conf):
    moe = params["layers"]["moe"]
    return dict(params, layers=dict(params["layers"], moe=dict(
        moe, w_down=0 * moe["w_down"]))), conf


def _other_share(params, conf):
    return params, dict(conf, program=dict(conf["program"], expert_offset=0))


def _normalised(params, conf):
    return params, dict(conf, norm_topk_prob=True)


@pytest.mark.parametrize("fault", [_no_held_experts, _other_share,
                                   _normalised],
                         ids=["no_held_experts", "other_share", "normalised"])
def test_a_wrong_expert_layer_fails_the_comparison(fault):
    """The limit sees the held experts left out, another chip's share of
    them, or the top-6 weights renormalised (as the program did before)."""
    conf, tr = _smoke(expert_offset=24)
    got, params, reference = _serve_logits(conf, tr, 3)
    assert _gap(got, reference(*fault(params, conf))) > LIMIT


def _record(steps, scope_s, events):
    """A traced window of ``steps`` decode steps with the program's
    ``executor.decode.experts`` events, as `bench.run` builds it."""
    from repro.serving import telemetry

    telemetry.clear()
    conf, tr = _smoke()
    t0 = time.perf_counter()
    for routed, used in events:
        telemetry.event("executor.decode.experts", routed=routed, used=used)
    t1 = time.perf_counter() + 1e-6
    spans = [("decode", t0 + (t1 - t0) * i / steps,
              t0 + (t1 - t0) * (i + 1) / steps, {}) for i in range(steps)]
    a = spec.arch(conf["reference"]).arch(spec.cell(CELL).config)
    return trace.Record(reduced={"scope_s": {"jit__fused_decode_fn": scope_s}},
                        spans=spans, arch=a, adapters=tr["adapters"],
                        peak=PEAK)


def test_the_moe_metrics_read_a_hand_made_record():
    by = {"attention": 0.040, "moe": 0.002, "moe/router": 0.001,
          "moe/experts": 0.050, "moe/shared": 0.012, "mlp": 0.001,
          trace.UNSCOPED: 0.003}
    layers, held, d, f = 27, 8, 2048, 1408
    events = [(layers * 24, layers * held - 3), (layers * 20, layers * 7)]
    rec = _record(2, by, events)
    assert spec.metric_reader("step.decode_moe_ms").read(rec) == \
        pytest.approx(1e3 * 0.065 / 2)
    least = sum(max(r * 6 * d * f / PEAK["flops_per_s"],
                    (u * 3 * d * f + r * 2 * d) * BF16
                    / PEAK["hbm_bytes_per_s"]) for r, u in events)
    assert spec.metric_reader("held_experts_roofline").read(rec) == \
        pytest.approx(100 * least / 0.050)
    assert any("moe/experts" in n for n in rec.notes)


def test_the_experts_roofline_stays_within_the_chip():
    """Every held expert of every layer used, at the most pairs a step of
    32 tokens can route here: a device time that reads each expert once
    and moves each pair's activations at the peak bandwidth reads 100%."""
    layers, held, d, f, B, k = 27, 8, 2048, 1408, 32, 6
    routed, used = layers * B * k, layers * held
    dev = max(routed * 6 * d * f / PEAK["flops_per_s"],
              (layers * held * 3 * d * f + routed * 2 * d) * BF16
              / PEAK["hbm_bytes_per_s"])
    rec = _record(1, {"moe/experts": dev}, [(routed, used)])
    share = spec.metric_reader("held_experts_roofline").read(rec)
    assert share == pytest.approx(100.0) and share <= 100.0 + 1e-9


def test_the_moe_metrics_are_silent_without_the_program_s_counters():
    """A program that keeps no expert counters or scopes (a dense model,
    or the program before the MoE family joined the fused path) gives
    neither metric."""
    rec = _record(2, {"attention": 0.04, "mlp": 0.03}, [])
    for name in ("step.decode_moe_ms", "held_experts_roofline"):
        assert spec.metric_reader(name).read(rec) is None
    rec = _record(2, {"moe/experts": 0.04}, [])
    assert spec.metric_reader("held_experts_roofline").read(rec) is None


def test_the_shared_readers_read_the_moe_cell():
    """Every per-layer metric that lists the MoE cell, other than its own
    two, reads the cell's cost object as it reads a dense cell's: on the
    same recorded v5e trace, a value wherever the dense cell has one."""
    from pathlib import Path

    ev = json.loads((Path(__file__).parent / "data" /
                     "trace_v5e_decode.json").read_text())
    kinds = {"bench.decode": "decode", "bench.prefill": "prefill",
             "bench.engine": "engine"}
    info = {"decode": {"kv_lens": [129 + i for i in range(32)],
                       "ids": [i % 7 for i in range(32)]},
            "prefill": {"prompt_len": 128, "adapter": 3}, "engine": {}}
    spans = [(kinds[n], t / 1e9, (t + d) / 1e9, info[kinds[n]])
             for n, t, d in ev["host"] if n in kinds]

    def record(cell):
        c = spec.cell(cell)
        return trace.Record(reduced=trace.reduce(ev), spans=spans,
                            arch=spec.arch(c.config["reference"]).arch(
                                c.config),
                            adapters=c.traffic["adapters"], peak=PEAK)
    moe, dense = record(CELL), record("qwen3-1.7b.jd1000.decode")
    shared = [m["name"] for m in spec.cell(CELL).per_layer
              if m["name"] not in ("step.decode_moe_ms",
                                   "held_experts_roofline")]
    assert len(shared) == 12
    for name in shared:
        want = spec.metric_reader(name).read(dense)
        got = spec.metric_reader(name).read(moe)
        assert (got is None) == (want is None), name
        if name in ("step.mfu", "step.decode_hbm_share",
                    "fused_decode_jd_roofline"):
            assert got > 0 and got != want, name


def test_benchmark_entries():
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    w, = [w for w in b["workloads"] if w["name"] == CELL]
    assert (w["config"], w["traffic"], w["chips"]) == (
        "deepseek-moe-16b-ep8", "jd1000.short", 1)
    new = {m["name"]: m for m in b["per_layer"]
           if m.get("workloads") == [CELL]}
    assert set(new) == {"step.decode_moe_ms", "held_experts_roofline"}
    assert spec.cell(CELL).limits["compared_tokens"]["limit"] == 4 * 257
