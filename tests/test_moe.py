"""MoE dispatch/combine correctness + dense-oracle equivalence."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import smoke_config
from repro.models.moe import _combine, _dispatch, _moe_dense, _route, moe_defs
from repro.models.param import init_params


def test_dispatch_combine_roundtrip():
    """dispatch->identity-expert->combine == weighted passthrough."""
    key = jax.random.PRNGKey(0)
    T, d, E, k, C = 32, 16, 4, 2, 24
    x = jax.random.normal(key, (T, d))
    topi = jax.random.randint(key, (T, k), 0, E)
    topw = jnp.ones((T, k)) / k
    buf, eid, slot, valid = _dispatch(x, topi, C, E)
    y = _combine(buf, eid, slot, valid, topw)
    # capacity is ample => every choice kept => y == x (sum_k w_k x = x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x), rtol=1e-5,
                               atol=1e-5)


def test_dispatch_respects_capacity():
    T, d, E, k = 64, 8, 2, 1
    x = jnp.ones((T, d))
    topi = jnp.zeros((T, k), jnp.int32)       # all to expert 0
    cap = 16
    buf, eid, slot, valid = _dispatch(x, topi, cap, E)
    assert int(valid.sum()) == cap
    assert float(buf[0].sum()) == cap * d


def test_dispatch_offset_window():
    """Only experts inside [offset, offset+n_local) are bucketed."""
    T, d, E = 16, 4, 8
    x = jnp.ones((T, d))
    topi = jnp.tile(jnp.arange(8, dtype=jnp.int32)[:, None], (2, 1))
    buf, eid, slot, valid = _dispatch(x, topi, 4, 2, bucket_offset=4)
    assert int(valid.sum()) == 4            # experts 4 and 5, two each
    assert float(buf.sum()) == 4 * d


def test_moe_dense_matches_manual():
    cfg = smoke_config("deepseek-moe-16b")
    defs = moe_defs(cfg)
    p = init_params(defs, jax.random.PRNGKey(0), dtype_override=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (12, cfg.d_model))
    topw, topi, aux = _route(p, x, cfg)
    y = _moe_dense(p, x, topw, topi, cfg)
    # manual: per token loop
    y_ref = np.zeros_like(np.asarray(y))
    for t in range(12):
        acc = np.zeros(cfg.d_model, np.float32)
        for j in range(cfg.moe.top_k):
            e = int(topi[t, j])
            g = np.asarray(x[t] @ p["w_gate"][e])
            u = np.asarray(x[t] @ p["w_up"][e])
            h = g / (1 + np.exp(-g)) * u
            acc += float(topw[t, j]) * (h @ np.asarray(p["w_down"][e]))
        y_ref[t] = acc
    np.testing.assert_allclose(np.asarray(y), y_ref, rtol=2e-4, atol=2e-4)
    assert jnp.isfinite(aux)


EP_EQUIV_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp, numpy as np
from repro.configs import smoke_config
from repro.models.moe import moe_defs, moe_fwd
from repro.models.param import init_params
from repro.distributed.sharding import use_mesh
cfg = smoke_config("deepseek-moe-16b")
# ample capacity: EP must match the (no-drop) dense oracle exactly
cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                       capacity_factor=16.0))
defs = moe_defs(cfg)
p = init_params(defs, jax.random.PRNGKey(0), dtype_override=jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, cfg.d_model))
y_dense, aux_d = moe_fwd(p, x, cfg)              # no mesh -> dense oracle
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
with use_mesh(mesh):
    y_ep, aux_e = jax.jit(lambda p, x: moe_fwd(p, x, cfg))(p, x)
err = float(jnp.max(jnp.abs(y_ep - y_dense)))
rel = err / float(jnp.max(jnp.abs(y_dense)))
assert rel < 1e-4, (err, rel)
print("EP-vs-dense rel err:", rel)
"""


def test_moe_ep_matches_dense_subprocess():
    """shard_map expert-parallel path == dense oracle (8 fake devices)."""
    r = subprocess.run([sys.executable, "-c", EP_EQUIV_SCRIPT],
                       capture_output=True, text=True,
                       env={**__import__("os").environ,
                            "PYTHONPATH": "src"}, cwd=".", timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "EP-vs-dense rel err" in r.stdout
