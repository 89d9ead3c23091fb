"""Logical-axis sharding rules: divisibility fallback + per-cell specs."""
import os
import subprocess
import sys


SPEC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from jax.sharding import PartitionSpec as P
from repro.distributed.sharding import spec_for, use_mesh
from repro.launch import shardings as sh
from repro.configs import get_config
from repro.models import transformer as tf
from repro.models.param import param_specs

from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)

# divisible -> sharded
assert spec_for((16, 64), ("batch", "d_ff"), mesh) == P("data", "model")
# non-divisible head count -> replicate (granite's 24 heads scenario)
assert spec_for((6,), ("heads",), mesh) == P(None)
# one mesh axis never used twice
s = spec_for((8, 8), ("heads", "d_ff"), mesh)
assert s == P("model", None)
# experts take precedence, expert_ff falls back (deepseek vs granite)
assert spec_for((8, 16, 32), ("experts", "d_model", "expert_ff"), mesh) \
    == P("model", None, None)
assert spec_for((6, 16, 32), ("experts", "d_model", "expert_ff"), mesh) \
    == P(None, None, "model")

# param specs: FSDP only in train rules
cfg = get_config("qwen3-1.7b")
defs = tf.model_defs(cfg)
tr = sh.params_shardings(defs, mesh, "train")
se = sh.params_shardings(defs, mesh, "serve")
wq_tr = tr["layers"]["attn"]["wq"].spec
wq_se = se["layers"]["attn"]["wq"].spec
assert wq_tr == P(None, "data", "model", None), wq_tr  # (L,d,H,hd) FSDP+TP
assert wq_se == P(None, None, "model", None), wq_se    # TP only
print("sharding specs ok")
"""


def test_spec_rules_subprocess():
    r = subprocess.run([sys.executable, "-c", SPEC_SCRIPT],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=".", timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "sharding specs ok" in r.stdout


def test_constrain_noop_without_mesh():
    import jax.numpy as jnp
    from repro.distributed.sharding import constrain
    x = jnp.ones((4, 4))
    y = constrain(x, "batch", "d_model")
    assert (y == x).all()
