"""The held-expert MoE layer and the MoE family on the fused decode path.

A layer holds experts ``[offset, offset + held)`` of the ``num_experts`` its
router scores (one chip's share of an expert-parallel deployment); the
shares of all chips, with the shared expert counted once, make the whole
layer.  The fused decode step of an MoE model follows
`transformer.decode_step` and counts the tokens routed to each held expert;
a dense model's fused step is the program it was before MoE joined it."""
import dataclasses as dc
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import smoke_config
from repro.kernels import ops
from repro.models import moe
from repro.models import transformer as tf
from repro.models.layers import mlp_fwd
from repro.models.param import init_params, is_def
from repro.serving import telemetry
from repro.serving.real_executor import RealModelExecutor
from repro.serving.request import Request

ROUTER, HELD, TOP_K = 64, 8, 6


def _cfg(held=0, offset=0, norm=False, num_experts=ROUTER, top_k=TOP_K):
    cfg = smoke_config("deepseek-moe-16b")
    return dc.replace(cfg, moe=dc.replace(
        cfg.moe, num_experts=num_experts, top_k=top_k, held_experts=held,
        expert_offset=offset, norm_topk_prob=norm))


def _params(defs, seed):
    """float32, N(0, 1/fan_in) matrices (the fan-in is the next-to-last
    axis), norms one."""
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_def)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    out = []
    for k, d in zip(keys, leaves):
        if d.init in ("ones", "zeros"):
            out.append((jnp.ones if d.init == "ones" else jnp.zeros)(
                d.shape, jnp.float32))
        else:
            fan_in = d.shape[-2] if len(d.shape) >= 2 else 1
            out.append(jax.random.normal(k, d.shape) * fan_in ** -0.5)
    return jax.tree.unflatten(treedef, out)


def _share(p, offset, held):
    """The held share of a whole layer's parameters: every router output,
    the shared expert, and experts ``[offset, offset + held)``."""
    return {k: (v[offset:offset + held] if k.startswith("w_") else v)
            for k, v in p.items()}


@pytest.mark.parametrize("every_token", [False, True],
                         ids=["grouped", "every_token"])
@pytest.mark.parametrize("norm", [False, True], ids=["raw", "normalised"])
def test_held_shares_add_up_to_the_uncut_layer(norm, every_token):
    whole = _cfg(norm=norm)
    p = _params(moe.moe_defs(whole), 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, whole.d_model))
    want, _ = moe.moe_fwd(p, x, whole, every_token=every_token)
    shared = mlp_fwd(p["shared"], x)
    got = shared
    for offset in range(0, ROUTER, HELD):
        cfg = _cfg(HELD, offset, norm)
        y, _ = moe.moe_fwd(_share(p, offset, HELD), x, cfg,
                           every_token=every_token)
        got = got + (y - shared)
    # float32 throughout; the shares sum the same products in another order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_the_two_held_paths_agree():
    """Prefill's grouped path and decode's every-token path compute the
    same share."""
    cfg = _cfg(HELD, 16)
    p = _params(moe.moe_defs(cfg), 2)
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, cfg.d_model))
    a, _ = moe.moe_fwd(p, x, cfg)
    b, _ = moe.moe_fwd(p, x, cfg, every_token=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@pytest.mark.parametrize("norm", [False, True], ids=["raw", "normalised"])
def test_route_normalises_only_when_told(norm):
    cfg = _cfg(norm=norm)
    p = _params(moe.moe_defs(cfg), 0)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    topw, topi, _ = moe._route(p, x, cfg)
    probs = jax.nn.softmax(x @ p["router"], -1)
    picked = np.take_along_axis(np.asarray(probs), np.asarray(topi), -1)
    if norm:
        picked = picked / picked.sum(-1, keepdims=True)
        np.testing.assert_allclose(np.asarray(topw).sum(-1), 1.0, rtol=1e-6)
    else:
        assert (np.asarray(topw).sum(-1) < 0.99).all()
    np.testing.assert_allclose(np.asarray(topw), picked, rtol=1e-5)


# -- the fused decode step of an MoE model -----------------------------------

N_ADAPTERS, RANK = 6, 4
SERVED = dict(held=4, offset=4, num_experts=16, top_k=3)


def _bundles(cfg, mode, seed=5):
    """An adapter collection on q/k/v/o in each of the model's stacks."""
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (cfg.num_heads * hd, d)}
    fk = cfg.moe.first_k_dense
    key = jax.random.PRNGKey(seed)
    out = {}
    for s, (stack, L) in enumerate((("layers", cfg.num_layers - fk),
                                    ("dense_layers", fk))):
        out[stack] = {}
        for i, (t, (di, do)) in enumerate(dims.items()):
            k1, k2, k3 = jax.random.split(jax.random.fold_in(key, 8 * s + i),
                                          3)
            if mode == "lora":
                out[stack][t] = {
                    "A": 0.1 * jax.random.normal(k1, (L, N_ADAPTERS, RANK,
                                                      di)),
                    "B": 0.1 * jax.random.normal(k2, (L, N_ADAPTERS, do,
                                                      RANK))}
            else:
                out[stack][t] = {
                    "U": 0.1 * jax.random.normal(k1, (L, 1, do, RANK)),
                    "V": 0.1 * jax.random.normal(k2, (L, 1, di, RANK)),
                    "sigma": jax.random.normal(k3, (L, N_ADAPTERS, RANK,
                                                    RANK)),
                    "cluster_of": jnp.zeros((L, N_ADAPTERS), jnp.int32)}
    return out


def _served(mode, path, cfg=None):
    cfg = cfg or _cfg(**SERVED)
    ex = RealModelExecutor(cfg, _params(tf.model_defs(cfg), 0),
                           _bundles(cfg, mode), mode, max_batch=4, s_max=64,
                           decode_path=path)
    rng = np.random.default_rng(0)
    for rid in range(4):
        ex.prefill_request(
            Request(rid=rid, adapter_id=rid % N_ADAPTERS, prompt_len=8,
                    max_new_tokens=8),
            rng.integers(0, cfg.vocab_size, size=8).astype(np.int32))
    return ex


@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_fused_moe_step_matches_decode_step(mode):
    """Fewer experts held (4, from expert 4) than the router scores (16):
    the fused step's logits are `transformer.decode_step`'s."""
    unfused, fused = _served(mode, "unfused"), _served(mode, "fused")
    tokens = jnp.asarray(unfused.slot_tokens[:, None])
    ids = jnp.asarray(unfused.slot_adapter)
    want, _, _ = unfused._decode(unfused.params, unfused.bundles, tokens,
                                 unfused.cache, ids)
    got, _, out = fused._decode(fused.params, fused.bundles, tokens,
                                fused.cache, ids, bucket=fused._bucket())
    routed = out["routed"]
    V = fused.cfg.vocab_size
    # float32 weights; the K/V cache is bf16 on both paths, so what is left
    # is the order of float32 sums (attention kernel, expert combine)
    np.testing.assert_allclose(np.asarray(got[..., :V]),
                               np.asarray(want[..., :V]), rtol=0, atol=1e-3)
    assert routed.shape == (fused.cfg.num_layers - 1, 4)
    # the calls above donated the fused executor's cache: serve anew
    unfused, fused = _served(mode, "unfused"), _served(mode, "fused")
    for _ in range(4):
        assert unfused.decode_step_real() == fused.decode_step_real()


def test_expert_counters_equal_a_recount_of_the_routing(monkeypatch):
    """Each decode step's ``executor.decode.experts`` event holds the
    (token, expert) pairs routed to the held experts and the held experts
    used, summed over the expert layers: recounted here in numpy from the
    routing of `transformer.decode_step` on the same cache (the test's
    own: the executors' caches hold the step launched ahead)."""
    cfg = dc.replace(_cfg(**SERVED), scan_layers=False)
    fused, unfused = _served("jd", "fused", cfg), _served("jd", "unfused", cfg)
    cache = unfused.cache
    choices = []
    route = moe._route

    def recorded(p, x, c):
        out = route(p, x, c)
        if not isinstance(out[1], jax.core.Tracer):
            choices.append(np.asarray(out[1]))
        return out
    monkeypatch.setattr(moe, "_route", recorded)
    telemetry.clear()
    for _ in range(3):
        choices.clear()
        tokens = jnp.asarray(unfused.slot_tokens[:, None])
        ids = jnp.asarray(unfused.slot_adapter)
        # eager (no jit, no scan): each expert layer's choices are arrays
        _, cache = tf.decode_step(unfused.params, tokens, cfg, cache,
                                  lora_params=unfused.bundles,
                                  lora_ctx_proto=unfused._ctx(ids))
        local = np.stack(choices) - SERVED["offset"]        # (layers, B, k)
        per = np.stack([(local == e).sum((1, 2))
                        for e in range(SERVED["held"])], -1)
        assert fused.decode_step_real() == unfused.decode_step_real()
        ev = [r for r in telemetry.records()
              if r.name == "executor.decode.experts"][-1]
        assert ev.attrs == {"routed": int(per.sum()),
                            "used": int((per > 0).sum())}
        assert 0 < ev.attrs["routed"] < cfg.num_layers * 4 * SERVED["top_k"]


def test_dense_models_step_reports_no_experts():
    telemetry.clear()
    cfg = dc.replace(smoke_config("qwen3-1.7b"), num_layers=2)
    ex = RealModelExecutor(cfg, init_params(tf.model_defs(cfg),
                                            jax.random.PRNGKey(0)),
                           {"layers": {}}, "jd", max_batch=2, s_max=32,
                           decode_path="fused")
    ex.prefill_request(Request(rid=0, adapter_id=0, prompt_len=4,
                               max_new_tokens=2),
                       np.arange(4, dtype=np.int32))
    ex.decode_step_real()
    assert not [r for r in telemetry.records()
                if r.name == "executor.decode.experts"]


# the dense fused step's jaxpr, with each equation's named scopes, as the
# program had it before the MoE family joined the fused path, and since with
# its greedy sampling (argmax over the vocabulary, in scope `logits`, the ids
# a fourth output): a qwen3 smoke model of 2 layers, 4 slots, a 128-token
# window, the kernels in Pallas' interpreter (so the kernels' bodies and
# index maps are in it too).  A change of JAX's version changes the text;
# regenerate them then.
DENSE_STEP = {
    "jd": "08b40784a05e9c80830dcb9404384452bea110ddc7dfc997eb4cc5ea5a906fb6",
    "lora": "84ff129ffaaf1a0a5da7918ff3fb397ee0f6e51d491a16f3a01f454b5abe2e84",
}


def _dense_step_jaxpr(mode):
    S = jax.ShapeDtypeStruct
    cfg = dc.replace(smoke_config("qwen3-1.7b"), num_layers=2)
    params = jax.eval_shape(lambda: init_params(tf.model_defs(cfg),
                                                jax.random.PRNGKey(0)))
    L, n, r = 2, 4, 8
    d, hd = cfg.d_model, cfg.resolved_head_dim
    dims = {"q": (d, cfg.num_heads * hd), "k": (d, cfg.num_kv_heads * hd),
            "v": (d, cfg.num_kv_heads * hd), "o": (cfg.num_heads * hd, d)}
    bf = jnp.bfloat16
    if mode == "lora":
        banks = {t: {"A": S((L, n, r, di), bf), "B": S((L, n, do, r), bf)}
                 for t, (di, do) in dims.items()}
    else:
        banks = {t: {"U": S((L, 1, do, r), bf), "V": S((L, 1, di, r), bf),
                     "sigma": S((L, n, r, r), bf),
                     "cluster_of": S((L, n), jnp.int32)}
                 for t, (di, do) in dims.items()}
    ex = RealModelExecutor.__new__(RealModelExecutor)
    ex.cfg, ex.mode, ex.decode_path = cfg, mode, "fused"
    cache = tf.init_cache(cfg, 4, 256, abstract=True)
    return jax.make_jaxpr(lambda *a: ex._fused_decode_fn(*a, bucket=128))(
        params, {"layers": banks}, S((4, 1), jnp.int32), cache,
        S((4,), jnp.int32)).pretty_print(name_stack=True)


@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_dense_fused_step_is_the_program_it_was(mode, monkeypatch):
    monkeypatch.setattr(ops, "resolve_impl", lambda use_pallas: "interpret")
    text = _dense_step_jaxpr(mode)
    assert hashlib.sha256(text.encode()).hexdigest() == DENSE_STEP[mode]
