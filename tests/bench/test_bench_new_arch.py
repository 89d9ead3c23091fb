"""A configuration of another architecture joins the benchmark with new
files and new ``BENCHMARK.json`` entries only.

In a copy of the harness, the repo's MoE family at the program's smoke size
(``smoke_config("deepseek-moe-16b")``) brings its architecture module, its
configuration, traffic, limits, plain reference and a reader of a kernel's
roofline (``tests/bench/data/moe_arch``).  The copy's own files stay as
they are; from there the configuration goes through ``spec.model_config``,
``weights.make_params`` / ``make_adapters`` and the program's prefill, the
reference, the cost object and the new reader on a hand-made record."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).parent / "data" / "moe_arch"
CELL = "deepseek-moe-16b-smoke.jd16.smoke"

SCRIPT = r'''
import json
import jax
import jax.numpy as jnp
import numpy as np
from bench import spec, trace, weights
from repro.configs.registry import smoke_config
from repro.models import transformer as tf
from repro.models.lora import LoRAContext

cell = spec.cell("%s")
conf, tr = cell.config, cell.traffic
ad = tr["adapters"]
mod = spec.arch(conf["reference"])
cfg = spec.model_config(conf, tr)
smoke = smoke_config("deepseek-moe-16b")
same = {k: getattr(cfg, k) == getattr(smoke, k) for k in (
    "family", "num_layers", "d_model", "num_heads", "num_kv_heads",
    "resolved_head_dim", "d_ff", "vocab_size", "rope_theta", "norm_eps")}
same["moe"] = cfg.moe == smoke.moe
init = conf["initializer_range"]
params = weights.make_params(cfg, 7, init)
dims = mod.adapter_dims(conf)
bundles = weights.make_adapters(cfg, dims, ad, 7, init)

# the program's prefill, on every adapter stack it takes
n, P = 4, tr["prompt_len"]
tokens = np.random.default_rng(0).integers(0, conf["vocab_size"], (n, P))
ids = jnp.asarray([0, 5, 10, 15], jnp.int32)
cache = tf.init_cache(cfg, n, tr["s_max"])
proto = LoRAContext(mode="jd", params=None, ids=ids, scaling=1.0)
prog, _ = tf.prefill(params, {"tokens": jnp.asarray(tokens)}, cfg, cache,
                     lora_params=bundles, lora_ctx_proto=proto)
prog = np.asarray(prog[:, -1, :conf["vocab_size"]], np.float32)
ref = spec.reference(conf["reference"])
gaps = {}
for norm in (True, False):
    c = dict(conf, norm_topk_prob=norm)
    r = np.asarray(ref.logits(params, bundles, c, "jd", tokens, ids, P - 1)
                   [:, 0], np.float32)
    gaps[str(norm)] = float(np.abs(r - prog).max() / np.abs(r).max())

# the cost object and a reader of a kernel no harness file names
a = mod.arch(conf)
ev = {"device": {"/device:TPU:0": [
        ["jit__fused_decode_fn/moe_experts.3", 1000, 4000, ""],
        ["jit__fused_decode_fn/fusion.2", 6000, 1000, ""]]},
      "modules": {"/device:TPU:0": [["jit__fused_decode_fn", 1000, 6000]]},
      "host": [["bench.window", 0, 10000]]}
rec = trace.Record(reduced=trace.reduce(ev), adapters=ad,
                   spans=[("decode", 0.0, 1e-5, {"kv_lens": [17, 17, 17, 17],
                                                 "ids": [0, 5, 10, 15]})],
                   arch=a, peak={"flops_per_s": 197e12,
                                 "hbm_bytes_per_s": 819e9})
calls = a.kernel_calls("moe_experts", ad, [17] * 4, [0, 5, 10, 15])
print(json.dumps({
    "same": same, "stacks": {k: sorted(v) for k, v in bundles.items()},
    "q_sigma": list(bundles["layers"]["q"]["sigma"].shape),
    "o_V": list(bundles["dense_layers"]["o"]["V"].shape),
    "dims": dims, "gaps": gaps,
    "roofline": spec.metric_reader("moe_experts_roofline").read(rec),
    "mfu": spec.metric_reader("step.mfu").read(rec),
    "least": calls, "prefill_flops": a.prefill_flops(ad, P)}))
''' % CELL


@pytest.fixture(scope="module")
def copied(tmp_path_factory):
    """The harness with the MoE configuration's files added, and what the
    configuration read there."""
    root = tmp_path_factory.mktemp("bench_copy")
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    added = json.loads((DATA / "entries.json").read_text())
    for key, rows in added.items():
        bench[key] += rows
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    new = []
    for f in sorted(DATA.rglob("*")):
        rel = f.relative_to(DATA)
        if f.is_file() and f.name != "entries.json":
            assert not (root / "bench" / rel).exists(), rel
            (root / "bench" / rel).write_bytes(f.read_bytes())
            new.append(str(rel))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    return root, new, json.loads(out.stdout.strip().splitlines()[-1])


def test_only_files_are_added(copied):
    root, new, _ = copied
    assert sorted(new) == ["archs/moe_gqa.py",
                           "configs/deepseek-moe-16b-smoke.json",
                           "limits/deepseek-moe-16b-smoke.jd16.smoke.json",
                           "metrics/moe_experts_roofline.py",
                           "references/moe_gqa.py", "traffic/jd16.smoke.json"]
    for f in (ROOT / "bench").rglob("*.py"):
        if "__pycache__" not in f.parts:
            rel = f.relative_to(ROOT / "bench")
            assert (root / "bench" / rel).read_bytes() == f.read_bytes(), rel


def test_the_config_is_the_program_smoke_moe(copied):
    _, _, got = copied
    assert all(got["same"].values()), got["same"]
    assert got["dims"] == {"q": [128, 128], "k": [128, 128],
                           "v": [128, 128], "o": [128, 128]}


def test_adapters_come_in_the_program_stacks(copied):
    _, _, got = copied
    # one dense layer, then three expert layers
    assert got["stacks"] == {"dense_layers": ["k", "o", "q", "v"],
                             "layers": ["k", "o", "q", "v"]}
    assert got["q_sigma"] == [3, 16, 8, 8]
    assert got["o_V"] == [1, 2, 128, 8]


def test_the_reference_agrees_with_the_program_where_it_routes_alike(copied):
    """The program renormalises the routed weights (``moe._route``) where
    the published configuration does not (``norm_topk_prob`` false), so its
    prefill agrees with the reference only where the reference does so
    too."""
    _, _, got = copied
    # bf16 rounding reads 0.008 where both renormalise, 0.076 where not
    assert got["gaps"]["True"] < 0.02, got["gaps"]
    assert got["gaps"]["False"] > 4 * got["gaps"]["True"], got["gaps"]


def test_costs_and_a_new_kernel_reader(copied):
    _, _, got = copied
    (flops, nbytes, calls), = got["least"]
    assert calls == 3
    assert flops == 4 * 2 * 2 * 3 * 128 * 64
    assert nbytes == (8 * 3 * 128 * 64 + 2 * 4 * 128) * 2
    least = 3 * max(flops / 197e12, nbytes / 819e9)
    assert got["roofline"] == pytest.approx(100 * least / 4000e-9)
    assert got["mfu"] is not None and got["mfu"] > 0
    assert got["prefill_flops"] > 0
