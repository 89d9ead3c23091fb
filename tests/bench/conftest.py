"""The benchmark's tests import it as the package ``bench`` from the root
of the checkout, as ``python3 -m bench.run`` does."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


import json  # noqa: E402

import pytest  # noqa: E402


def _tiny_cell(mode: str, **traffic):
    """A cell of the real configuration and mix files at a width and depth
    the CPU runs in seconds: qwen3-1.7b's layer (qk-norm, tied embeddings)
    and the jd1000 decode mix (with raw LoRA banks for ``lora``), with fewer
    clients and tokens."""
    from bench import spec

    conf = json.loads((ROOT / "bench/configs/qwen3-1.7b.json").read_text())
    conf.update(hidden_size=256, intermediate_size=512, num_attention_heads=4,
                num_key_value_heads=2, head_dim=64, num_hidden_layers=4,
                vocab_size=4096)
    tr = json.loads((ROOT / "bench/traffic/jd1000.decode.json").read_text())
    tr.update(clients=8, max_batch=8, prompt_len=32, output_len=64,
              s_max=128, check_requests=8)
    tr["adapters"]["count"] = 16
    if mode == "lora":
        for k in ("clusters", "sigma"):
            tr["adapters"].pop(k)
    tr["adapters"]["mode"] = mode
    tr.update(traffic)
    b = spec.benchmark()
    return spec.Cell(name=f"tiny.{mode}", config=conf, traffic=tr,
                     limits={"widest_gap": {"limit": TINY_LIMIT},
                             "compared_tokens": {"limit": 8 * 65}},
                     chips=1, end_to_end=b["end_to_end"],
                     per_layer=b["per_layer"])


# widest gap allowed at the tiny size: sound runs read at most 0.0084 and
# the float8 control at least 0.0378 over seeds 1-6 (CPU)
TINY_LIMIT = 0.02


@pytest.fixture
def tiny_cell():
    return _tiny_cell
