"""Dry-run pipeline smoke (reduced device count via subprocess) + results
integrity of the full 512-device sweep if present."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

RESULTS = Path(__file__).resolve().parents[1] / "results" / "dryrun"


def test_dryrun_cell_subprocess():
    env = {**os.environ, "PYTHONPATH": "src", "REPRO_DRYRUN_DEVICES": "256"}
    r = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-1.7b",
         "--shape", "decode_32k", "--force", "--out", "/tmp/dryrun_test"],
        capture_output=True, text=True, env=env, cwd=".", timeout=580)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    rec = json.loads(Path(
        "/tmp/dryrun_test/qwen3-1.7b__decode_32k__pod16x16.json").read_text())
    assert rec["ok"]
    assert rec["roofline"]["flops_per_dev"] > 0
    assert rec["roofline"]["bottleneck"] in ("compute", "memory", "collective")


@pytest.mark.skipif(not RESULTS.exists(), reason="full sweep not run")
def test_full_sweep_complete_and_ok():
    recs = [json.loads(p.read_text()) for p in RESULTS.glob("*.json")]
    assert len(recs) >= 80
    bad = [r for r in recs if not r.get("ok")]
    assert not bad, [(r["arch"], r["shape"], r.get("error")) for r in bad]
    skips = [r for r in recs if r.get("skipped")]
    # exactly the documented long_500k skips (8 archs x 2 meshes)
    assert all(r["shape"] == "long_500k" for r in skips)
    assert len(skips) == 16


def test_hlo_cost_parser_on_reference():
    """Loop-aware parser exactly recovers flops of a known scanned matmul."""
    env = {**os.environ, "PYTHONPATH": "src"}
    script = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_cost import analyze_hlo
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
L, M, K, N = 8, 64, 128, 256
def f(x, w):
    def body(c, wl):
        return jnp.tanh(c @ wl), None
    y, _ = jax.lax.scan(body, x, w)
    return y.sum()
co = jax.jit(f, in_shardings=(NamedSharding(mesh, P("data", None)),
                              NamedSharding(mesh, P(None, None, "model")))
             ).lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                     jax.ShapeDtypeStruct((L, K, K), jnp.float32)).compile()
res = analyze_hlo(co.as_text())
expected = 2 * L * M * K * (K / 4) / 2   # per-device
assert abs(res["dot_flops_per_dev"] - expected) / expected < 0.05, res
print("parser ok", res["dot_flops_per_dev"], expected)
"""
    r = subprocess.run([sys.executable, "-c", script], capture_output=True,
                       text=True, env=env, cwd=".", timeout=300)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "parser ok" in r.stdout
