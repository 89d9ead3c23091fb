"""Weights and adapters come from --seed alone, in bf16, in the layouts the
served path takes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import spec, weights


@pytest.fixture
def cfg(tiny_cell):
    cell = tiny_cell("jd")
    return spec.model_config(cell.config, cell.traffic), cell


def leaves(tree):
    return [np.asarray(x, np.float32) for x in jax.tree.leaves(tree)]


def test_params_are_bf16_and_repeat_from_the_seed(cfg):
    cfg, _ = cfg
    a = weights.make_params(cfg, 2 ** 33 + 5, 0.02)
    b = weights.make_params(cfg, 2 ** 33 + 5, 0.02)
    c = weights.make_params(cfg, 5, 0.02)
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(a))
    for x, y in zip(leaves(a), leaves(b)):
        np.testing.assert_array_equal(x, y)
    wq = lambda t: np.asarray(t["layers"]["attn"]["wq"], np.float32)
    assert not np.array_equal(wq(a), wq(c))
    assert wq(a).std() == pytest.approx(0.02, rel=0.05)
    np.testing.assert_array_equal(np.asarray(a["layers"]["ln1"], np.float32),
                                  1.0)


def test_large_leaves_are_drawn_in_blocks(cfg, monkeypatch):
    """Blocked and whole draws are both N(0, std) over the same shape."""
    cfg, _ = cfg
    monkeypatch.setattr(weights, "_BLOCK_ELEMS", 4096)
    x = np.asarray(weights._normal(jax.random.PRNGKey(0), (6, 64, 64), 0.5),
                   np.float32)
    assert x.shape == (6, 64, 64)
    assert x.std() == pytest.approx(0.5, rel=0.05)
    assert not np.array_equal(x[0], x[1])


@pytest.mark.parametrize("mode", ["jd", "lora"])
def test_adapter_layout(mode, tiny_cell):
    cell = tiny_cell(mode)
    cfg = spec.model_config(cell.config, cell.traffic)
    ad = cell.traffic["adapters"]
    dims = spec.arch("dense_gqa").adapter_dims(cell.config)
    b = weights.make_adapters(cfg, dims, ad, 3, 0.02)["layers"]
    L, n, r = cfg.num_layers, ad["count"], ad["rank"]
    d, q, kv = cfg.d_model, cfg.num_heads * cfg.resolved_head_dim, \
        cfg.num_kv_heads * cfg.resolved_head_dim
    assert set(b) == {"q", "k", "v", "o"}
    if mode == "lora":
        assert b["k"]["A"].shape == (L, n, r, d)
        assert b["k"]["B"].shape == (L, n, kv, r)
        assert b["o"]["A"].shape == (L, n, r, q)
    else:
        assert b["o"]["U"].shape == (L, 1, d, r)
        assert b["o"]["V"].shape == (L, 1, q, r)
        assert b["q"]["sigma"].shape == (L, n, r, r)
        assert b["q"]["cluster_of"].shape == (L, n)
