"""Per-kernel shape/dtype sweeps: Pallas (interpret mode) vs jnp oracles."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref as R
from repro.kernels.flash_decode import flash_decode
from repro.kernels.jd_apply import jd_apply
from repro.kernels.sgmv import sgmv_expand, sgmv_shrink, sigma_bmm

TOL = dict(rtol=2e-2, atol=3e-2)


def grouped_inputs(seed, T, d_in, n, tile, dtype):
    key = jax.random.PRNGKey(seed)
    ks = jax.random.split(key, 3)
    ids = jax.random.randint(ks[0], (T,), 0, n)
    x = (jax.random.normal(ks[1], (T, d_in), jnp.float32)).astype(dtype)
    perm, tile_ids, valid = R.group_tokens_by_adapter(ids, n, tile)
    return x[perm], ids[perm], tile_ids, valid


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("T,d_in,d_out,n,r,tile", [
    (32, 128, 64, 3, 8, 8),
    (64, 256, 192, 5, 16, 8),
    (128, 512, 256, 2, 32, 16),
    (16, 64, 128, 7, 4, 8),
])
def test_sgmv_sweep(T, d_in, d_out, n, r, tile, dtype):
    xg, idg, tile_ids, _ = grouped_inputs(0, T, d_in, n, tile, dtype)
    key = jax.random.PRNGKey(1)
    A = (jax.random.normal(key, (n, r, d_in)) / 8).astype(dtype)
    B = (jax.random.normal(key, (n, d_out, r)) / 4).astype(dtype)
    t = sgmv_shrink(xg, A, tile_ids, block_t=tile, block_d=64)
    t_ref = R.sgmv_shrink_ref(xg, A, idg).astype(jnp.float32)
    np.testing.assert_allclose(np.asarray(t), np.asarray(t_ref), **TOL)
    y = sgmv_expand(t.astype(dtype), B, tile_ids, block_t=tile, block_d=64)
    y_ref = R.sgmv_expand_ref(t.astype(dtype), B, idg)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(y_ref, np.float32), **TOL)


@pytest.mark.parametrize("r", [4, 16])
def test_sigma_bmm(r):
    T, n, tile = 48, 4, 8
    xg, idg, tile_ids, _ = grouped_inputs(2, T, r, n, tile, jnp.float32)
    sig = jax.random.normal(jax.random.PRNGKey(3), (n, r, r)) / 4
    out = sigma_bmm(xg, sig, tile_ids, block_t=tile)
    ref = R.sigma_bmm_ref(xg, sig, idg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), **TOL)


@pytest.mark.parametrize("diag", [True, False])
@pytest.mark.parametrize("k_clusters", [1, 3])
def test_jd_apply_sweep(diag, k_clusters):
    T, d_in, d_out, n, r, tile = 64, 192, 128, 6, 8, 8
    xg, idg, tile_ids, _ = grouped_inputs(4, T, d_in, n, tile, jnp.bfloat16)
    key = jax.random.PRNGKey(5)
    ks = jax.random.split(key, 3)
    U = (jax.random.normal(ks[0], (k_clusters, d_out, r)) / 4).astype(jnp.bfloat16)
    V = (jax.random.normal(ks[1], (k_clusters, d_in, r)) / 8).astype(jnp.bfloat16)
    cluster_of = jnp.arange(n, dtype=jnp.int32) % k_clusters
    sig = (jnp.abs(jax.random.normal(ks[2], (n, r))) if diag
           else jax.random.normal(ks[2], (n, r, r)) / 4)
    tile_cids = cluster_of[tile_ids]
    out = jd_apply(xg, U, V, sig, cluster_of, idg, tile_cids, tile_ids)
    ref = R.jd_apply_ref(xg, U, V, sig, cluster_of, idg)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=5e-2, atol=5e-2)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("B,H,Kv,hd,S,bs", [
    (2, 4, 2, 32, 128, 32),
    (3, 8, 4, 64, 256, 64),
    (1, 2, 1, 16, 64, 64),     # single block
])
def test_flash_decode_sweep(B, H, Kv, hd, S, bs, dtype):
    key = jax.random.PRNGKey(6)
    ks = jax.random.split(key, 4)
    q = jax.random.normal(ks[0], (B, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, Kv, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, Kv, hd)).astype(dtype)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    out, l, m = flash_decode(q, k, v, kv_len, block_s=bs)
    ref = R.flash_decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=2e-2, atol=2e-2)


def test_flash_decode_merge_stats():
    """(m, l) stats support sequence-sharded softmax merging: two half-KV
    kernel calls merged == full-KV call (the long-context decode path)."""
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 3)
    B, H, Kv, hd, S = 2, 4, 2, 32, 128
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kv, hd), jnp.float32)
    kv_len = jnp.full((B,), S, jnp.int32)
    full, _, _ = flash_decode(q, k, v, kv_len, block_s=32)
    h = S // 2
    o1, l1, m1 = flash_decode(q, k[:, :h], v[:, :h],
                              jnp.full((B,), h, jnp.int32), block_s=32)
    o2, l2, m2 = flash_decode(q, k[:, h:], v[:, h:],
                              jnp.full((B,), h, jnp.int32), block_s=32)
    G = H // Kv
    m = jnp.maximum(m1, m2)
    w1 = jnp.exp(m1 - m) * l1
    w2 = jnp.exp(m2 - m) * l2
    o1g = o1.reshape(B, Kv, G, hd)
    o2g = o2.reshape(B, Kv, G, hd)
    merged = (o1g * w1 + o2g * w2) / (w1 + w2)
    np.testing.assert_allclose(np.asarray(merged.reshape(B, H, hd)),
                               np.asarray(full), rtol=2e-4, atol=2e-4)


def test_ops_dispatch_matches_ref():
    from repro.kernels import ops
    T, d_in, d_out, n, r = 40, 96, 64, 4, 8
    key = jax.random.PRNGKey(8)
    ks = jax.random.split(key, 4)
    x = jax.random.normal(ks[0], (T, d_in), jnp.float32)
    A = jax.random.normal(ks[1], (n, r, d_in)) / 8
    B = jax.random.normal(ks[2], (n, d_out, r)) / 4
    ids = jax.random.randint(ks[3], (T,), 0, n)
    y_k = ops.lora_apply(x, A, B, ids, tile=8, use_pallas="interpret")
    y_r = ops.lora_apply(x, A, B, ids, use_pallas="ref")
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-3, atol=1e-3)


# ---------------------------------------------------------------------------
# fused decode + adapter delta (PR 8): one pass == composed unfused passes
# ---------------------------------------------------------------------------

from repro.kernels.adapter_quant import (adapter_dequantize, adapter_quantize,
                                         int8_error_bound, quantized_nbytes)
from repro.kernels.flash_decode import flash_decode_paged
from repro.kernels.fused_decode import (fused_decode_jd,
                                        fused_decode_jd_paged,
                                        fused_decode_lora,
                                        fused_decode_lora_paged)

FUSED_TOL = dict(rtol=2e-5, atol=2e-5)


def _attn_inputs(seed, B, H, Kv, hd, S, n):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Kv, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, S + 1)
    ids = jax.random.randint(ks[4], (B,), 0, n)
    return q, k, v, kv_len, ids, ks[5]


def _paged(k, v, page_t, seed=0):
    """Scatter contiguous (B,S,Kv,hd) KV into a permuted physical pool."""
    B, S, Kv, hd = k.shape
    nb = S // page_t
    perm = np.random.default_rng(seed).permutation(B * nb).astype(np.int32)
    page_table = jnp.asarray(perm.reshape(B, nb))
    kp = jnp.zeros((B * nb, page_t, Kv, hd), k.dtype)
    vp = jnp.zeros_like(kp)
    for b in range(B):
        for s in range(nb):
            kp = kp.at[perm[b * nb + s]].set(k[b, s * page_t:(s + 1) * page_t])
            vp = vp.at[perm[b * nb + s]].set(v[b, s * page_t:(s + 1) * page_t])
    return kp, vp, page_table


def _scatter_tiles(vals, perm, valid, B):
    """Undo group_tokens_by_adapter: grouped rows back to batch order."""
    out = np.zeros((B,) + vals.shape[1:], np.float32)
    p, m = np.asarray(perm), np.asarray(valid).astype(bool)
    out[p[m]] = np.asarray(vals, np.float32)[m]
    return out


@pytest.mark.parametrize("B,r,n", [(4, 8, 3), (8, 16, 5), (16, 4, 2)])
def test_fused_lora_matches_composed_and_oracle(B, r, n):
    """Fused kernel == flash_decode (bit-exact attention) + sgmv shrink/
    expand (delta to f32 tolerance) == ref oracle, across batch x rank x
    adapter-count."""
    H, Kv, hd, S, d_out = 4, 2, 32, 128, 64
    q, k, v, kv_len, ids, kw = _attn_inputs(10 + B + r, B, H, Kv, hd, S, n)
    ka, kb = jax.random.split(kw)
    A = jax.random.normal(ka, (n, r, H * hd), jnp.float32) / 8
    Bm = jax.random.normal(kb, (n, d_out, r), jnp.float32) / 4
    out, delta = fused_decode_lora(q, k, v, kv_len, ids, A, Bm, block_s=32)
    # attention half: bit-exact with the standalone kernel
    f_out, _, _ = flash_decode(q, k, v, kv_len, block_s=32)
    assert np.array_equal(np.asarray(out), np.asarray(f_out))
    # delta half: composed unfused path (grouped SGMV over the attn out)
    of = f_out.reshape(B, -1)
    perm, tile_ids, valid = R.group_tokens_by_adapter(ids, n, tile=4)
    t = sgmv_shrink(of[perm], A, tile_ids, block_t=4)
    d = sgmv_expand(t, Bm, tile_ids, block_t=4)
    composed = _scatter_tiles(d, perm, valid, B)
    np.testing.assert_allclose(np.asarray(delta), composed, **FUSED_TOL)
    # and the oracle
    o_ref, d_ref = R.fused_decode_lora_ref(q, k, v, kv_len, ids, A, Bm)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(d_ref),
                               **FUSED_TOL)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("diag", [True, False])
@pytest.mark.parametrize("k_clusters", [1, 3])
def test_fused_jd_matches_composed_and_oracle(diag, k_clusters):
    """Fused compressed-basis variant == flash_decode + jd_apply on the
    grouped attention output, for diag and full Sigma and >1 cluster."""
    B, H, Kv, hd, S, n, r, d_out = 8, 4, 2, 32, 128, 6, 8, 64
    q, k, v, kv_len, ids, kw = _attn_inputs(3 if diag else 4,
                                            B, H, Kv, hd, S, n)
    ku, kv_, ksig = jax.random.split(kw, 3)
    U = jax.random.normal(ku, (k_clusters, d_out, r), jnp.float32) / 4
    V = jax.random.normal(kv_, (k_clusters, H * hd, r), jnp.float32) / 8
    cluster_of = jnp.arange(n, dtype=jnp.int32) % k_clusters
    sig = (jnp.abs(jax.random.normal(ksig, (n, r))) if diag
           else jax.random.normal(ksig, (n, r, r)) / 4)
    out, delta = fused_decode_jd(q, k, v, kv_len, ids, U, V, sig,
                                 cluster_of, block_s=32)
    f_out, _, _ = flash_decode(q, k, v, kv_len, block_s=32)
    assert np.array_equal(np.asarray(out), np.asarray(f_out))
    of = f_out.reshape(B, -1)
    perm, tile_ids, valid = R.group_tokens_by_adapter(ids, n, tile=4)
    tile_cids = cluster_of[tile_ids]
    d = jd_apply(of[perm], U, V, sig, cluster_of, ids[perm], tile_cids,
                 tile_ids, block_t=4)
    composed = _scatter_tiles(d, perm, valid, B)
    np.testing.assert_allclose(np.asarray(delta), composed, **FUSED_TOL)
    _, d_ref = R.fused_decode_jd_ref(q, k, v, kv_len, ids, U, V, sig,
                                     cluster_of)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(d_ref),
                               **FUSED_TOL)


@pytest.mark.parametrize("mode", ["lora", "jd"])
def test_fused_paged_bit_exact_with_contiguous(mode):
    """Paged fused variant over a permuted page table == contiguous fused
    (out AND delta), and == flash_decode_paged on the attention half."""
    B, H, Kv, hd, S, n, r, d_out, page_t = 4, 4, 2, 32, 128, 3, 8, 64, 16
    q, k, v, kv_len, ids, kw = _attn_inputs(20, B, H, Kv, hd, S, n)
    kp, vp, page_table = _paged(k, v, page_t, seed=1)
    if mode == "lora":
        ka, kb = jax.random.split(kw)
        A = jax.random.normal(ka, (n, r, H * hd), jnp.float32) / 8
        Bm = jax.random.normal(kb, (n, d_out, r), jnp.float32) / 4
        out_c, d_c = fused_decode_lora(q, k, v, kv_len, ids, A, Bm,
                                       block_s=page_t)
        out_p, d_p = fused_decode_lora_paged(q, kp, vp, page_table, kv_len,
                                             ids, A, Bm)
    else:
        ku, kv_, ksig = jax.random.split(kw, 3)
        U = jax.random.normal(ku, (2, d_out, r), jnp.float32) / 4
        V = jax.random.normal(kv_, (2, H * hd, r), jnp.float32) / 8
        cluster_of = jnp.arange(n, dtype=jnp.int32) % 2
        sig = jax.random.normal(ksig, (n, r, r), jnp.float32) / 4
        out_c, d_c = fused_decode_jd(q, k, v, kv_len, ids, U, V, sig,
                                     cluster_of, block_s=page_t)
        out_p, d_p = fused_decode_jd_paged(q, kp, vp, page_table, kv_len,
                                           ids, U, V, sig, cluster_of)
    assert np.array_equal(np.asarray(out_p), np.asarray(out_c))
    assert np.array_equal(np.asarray(d_p), np.asarray(d_c))
    f_out, _, _ = flash_decode_paged(q, kp, vp, page_table, kv_len)
    assert np.array_equal(np.asarray(out_p), np.asarray(f_out))


def test_fused_lora_q8_matches_q8_oracle_and_fp_within_bound():
    """int8 banks: fused dequant epilogue == quantized oracle exactly (to
    f32 tolerance), and the fp gap stays within the analytic bound."""
    B, H, Kv, hd, S, n, r, d_out = 8, 4, 2, 32, 128, 4, 8, 64
    q, k, v, kv_len, ids, kw = _attn_inputs(30, B, H, Kv, hd, S, n)
    ka, kb = jax.random.split(kw)
    A = jax.random.normal(ka, (n, r, H * hd), jnp.float32) / 8
    Bm = jax.random.normal(kb, (n, d_out, r), jnp.float32) / 4
    aq, a_s = adapter_quantize(A)
    bq, b_s = adapter_quantize(Bm)
    out, delta = fused_decode_lora(q, k, v, kv_len, ids, aq, bq,
                                   a_scale=a_s, b_scale=b_s, block_s=32)
    _, d_ref = R.fused_decode_lora_ref(q, k, v, kv_len, ids, aq, bq,
                                       a_scale=a_s, b_scale=b_s)
    np.testing.assert_allclose(np.asarray(delta), np.asarray(d_ref),
                               **FUSED_TOL)
    _, d_fp = R.fused_decode_lora_ref(q, k, v, kv_len, ids, A, Bm)
    err = float(np.max(np.abs(np.asarray(delta) - np.asarray(d_fp))))
    assert err < 0.05, err                     # quant noise, not a bug


def test_adapter_quant_kernel_matches_oracle_and_bound():
    """Pallas quantizer == ref oracle bit-exact; roundtrip error bounded by
    `int8_error_bound`; packed bytes ~4x smaller than f32."""
    key = jax.random.PRNGKey(9)
    for shape, axis in (((3, 16, 64), -1), ((2, 5, 64, 8), -2)):
        key, k1 = jax.random.split(key)
        w = jax.random.normal(k1, shape, jnp.float32)
        q, s = adapter_quantize(w, axis=axis)
        q_ref, s_ref = R.adapter_quant_ref(w, axis=axis)
        assert np.array_equal(np.asarray(q), np.asarray(q_ref))
        np.testing.assert_allclose(np.asarray(s), np.asarray(s_ref),
                                   rtol=1e-6, atol=0)
        back = adapter_dequantize(q, s)
        bound = np.asarray(int8_error_bound(w, axis=axis))
        assert np.all(np.abs(np.asarray(back) - np.asarray(w))
                      <= bound + 1e-7)
        fp32 = int(np.prod(shape)) * 4
        assert fp32 / quantized_nbytes(shape, axis=axis) > 3.0


def test_ops_fused_dispatch_matches_ref():
    from repro.kernels import ops
    B, H, Kv, hd, S, n, r, d_out = 4, 4, 2, 32, 64, 3, 8, 64
    q, k, v, kv_len, ids, kw = _attn_inputs(40, B, H, Kv, hd, S, n)
    ka, kb = jax.random.split(kw)
    A = jax.random.normal(ka, (n, r, H * hd), jnp.float32) / 8
    Bm = jax.random.normal(kb, (n, d_out, r), jnp.float32) / 4
    o_k, d_k = ops.fused_lora_decode(q, k, v, kv_len, ids, A, Bm,
                                     use_pallas="interpret")
    o_r, d_r = ops.fused_lora_decode(q, k, v, kv_len, ids, A, Bm,
                                     use_pallas="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), **FUSED_TOL)
    # the served form: the layer-stacked cache and banks, read at a layer
    # over the first `window` tokens
    L, li, window = 2, 1, 32
    kk, kv_, ku, kvb, ksig = jax.random.split(jax.random.PRNGKey(41), 5)
    ks = jax.random.normal(kk, (L, B, S, Kv, hd), jnp.float32)
    vs = jax.random.normal(kv_, (L, B, S, Kv, hd), jnp.float32)
    kv_w = jnp.minimum(kv_len, window)
    at = dict(layer=li, window=window)
    As, Bs = jnp.stack([A / 2, A]), jnp.stack([Bm, Bm / 2])
    o_k, d_k = ops.fused_lora_decode(q, ks, vs, kv_w, ids, As, Bs, **at,
                                     use_pallas="interpret")
    o_r, d_r = ops.fused_lora_decode(q, ks, vs, kv_w, ids, As, Bs, **at,
                                     use_pallas="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), **FUSED_TOL)
    U = jax.random.normal(ku, (L, 2, d_out, r), jnp.float32) / 4
    V = jax.random.normal(kvb, (L, 2, H * hd, r), jnp.float32) / 8
    sig = jax.random.normal(ksig, (L, n, r, r), jnp.float32) / 4
    cluster_of = jnp.stack([jnp.arange(n, dtype=jnp.int32) % 2,
                            (jnp.arange(n, dtype=jnp.int32) + 1) % 2])
    o_k, d_k = ops.fused_jd_decode(q, ks, vs, kv_w, ids, U, V, sig,
                                   cluster_of, **at, use_pallas="interpret")
    o_r, d_r = ops.fused_jd_decode(q, ks, vs, kv_w, ids, U, V, sig,
                                   cluster_of, **at, use_pallas="ref")
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_r),
                               rtol=2e-2, atol=2e-2)
    np.testing.assert_allclose(np.asarray(d_k), np.asarray(d_r), **FUSED_TOL)
    a_k = ops.decode_attention(q, ks, vs, kv_w, **at, use_pallas="interpret")
    a_r = ops.decode_attention(q, ks, vs, kv_w, **at, use_pallas="ref")
    np.testing.assert_allclose(np.asarray(a_k), np.asarray(a_r),
                               rtol=2e-2, atol=2e-2)


@pytest.mark.parametrize("G", [2, 4])
@pytest.mark.parametrize("mode", ["attn", "lora", "lora_q8", "jd_diag_k1",
                                  "jd_diag_k3", "jd_full_k1", "jd_full_k3"])
def test_stacked_cache_bit_exact_with_layer_slice(mode, G):
    """A call on the layer-stacked (L, B, S, Kv, hd) cache at ``layer``
    over ``window`` < S tokens, with layer-stacked banks read in place, ==
    the (B, S, Kv, hd) call on ``k[layer, :, :window]`` with that layer's
    banks: out (and l, m, delta) bit for bit."""
    L, B, Kv, hd, S, window, n, r, d_out, li = 3, 3, 2, 32, 96, 64, 5, 8, 64, 1
    H = G * Kv
    ks = jax.random.split(jax.random.PRNGKey(50 + G), 8)
    q = jax.random.normal(ks[0], (B, H, hd), jnp.float32)
    k = jax.random.normal(ks[1], (L, B, S, Kv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (L, B, S, Kv, hd), jnp.float32)
    kv_len = jax.random.randint(ks[3], (B,), 1, window + 1)
    ids = jax.random.randint(ks[4], (B,), 0, n)
    k_l, v_l = k[li, :, :window], v[li, :, :window]
    at = dict(layer=li, window=window, block_s=32)

    def layer_of(banks):
        return [None if x is None else x[li] for x in banks]

    if mode == "attn":
        got = flash_decode(q, k, v, kv_len, **at)
        want = flash_decode(q, k_l, v_l, kv_len, block_s=32)
    elif mode.startswith("lora"):
        A = jax.random.normal(ks[5], (L, n, r, H * hd), jnp.float32) / 8
        Bm = jax.random.normal(ks[6], (L, n, d_out, r), jnp.float32) / 4
        banks = [A, Bm, None, None]
        if mode == "lora_q8":
            (aq, a_s), (bq, b_s) = adapter_quantize(A), adapter_quantize(Bm)
            banks = [aq, bq, a_s, b_s]
        got = fused_decode_lora(q, k, v, kv_len, ids, *banks, **at)
        want = fused_decode_lora(q, k_l, v_l, kv_len, ids,
                                 *layer_of(banks), block_s=32)
    else:
        kc = int(mode[-1])
        U = jax.random.normal(ks[5], (L, kc, d_out, r), jnp.float32) / 4
        V = jax.random.normal(ks[6], (L, kc, H * hd, r), jnp.float32) / 8
        sig = (jnp.abs(jax.random.normal(ks[7], (L, n, r))) if "diag" in mode
               else jax.random.normal(ks[7], (L, n, r, r)) / 4)
        cluster_of = (jnp.arange(n, dtype=jnp.int32)[None]
                      + jnp.arange(L, dtype=jnp.int32)[:, None]) % kc
        banks = [U, V, sig, cluster_of]
        got = fused_decode_jd(q, k, v, kv_len, ids, *banks, **at)
        want = fused_decode_jd(q, k_l, v_l, kv_len, ids, *layer_of(banks),
                               block_s=32)
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))
