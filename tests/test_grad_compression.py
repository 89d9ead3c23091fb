"""int8 compressed psum == exact psum within quantization tolerance."""
import os
import subprocess
import sys

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.distributed.grad_compression import compressed_psum
from jax.sharding import AxisType
mesh = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
x = jax.random.normal(jax.random.PRNGKey(0), (8, 4, 37))

def body(xs):
    exact = jax.lax.psum(xs, "data")
    comp = compressed_psum(xs, "data")
    return exact, comp

exact, comp = jax.jit(jax.shard_map(body, mesh=mesh,
                                    in_specs=P("data"),
                                    out_specs=P("data")))(x)
rel = float(jnp.max(jnp.abs(exact - comp)) / jnp.max(jnp.abs(exact)))
assert rel < 0.05, rel
print("compressed psum rel err:", rel)
"""


def test_compressed_psum_subprocess():
    r = subprocess.run([sys.executable, "-c", SCRIPT],
                       capture_output=True, text=True,
                       env={**os.environ, "PYTHONPATH": "src"},
                       cwd=".", timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "compressed psum rel err" in r.stdout
