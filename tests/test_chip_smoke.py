"""chip_smoke.py off the chip: it refuses a platform other than the TPU
before printing anything, and each of its phases serves, checks and
reports end to end at a tiny width with the Pallas kernels interpreted."""
import importlib.util
from pathlib import Path

import pytest

from repro.configs import smoke_config
from repro.kernels import ops
from repro.launch.serve import build_real_executor

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_a_platform_other_than_tpu(smoke, capsys):
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "platform 'cpu'" in err


def test_kernel_check_fails_without_the_compiled_kernel(smoke):
    ex = build_real_executor(smoke_config("qwen3-1.7b"), 4, "lora",
                             max_batch=smoke.MAX_BATCH, s_max=smoke.S_MAX,
                             decode_path="fused")
    assert "tpu_custom_call" not in smoke.fused_step_hlo(ex)


@pytest.mark.parametrize("mode", ["lora", "jd"])
def test_phase_serves_and_checks_at_tiny_width(smoke, monkeypatch, mode):
    resolve = ops.resolve_impl
    monkeypatch.setattr(ops, "resolve_impl",
                        lambda u: "interpret" if u == "auto" else resolve(u))
    # interpreted kernels leave no custom call to find
    monkeypatch.setattr(smoke, "fused_step_hlo",
                        lambda ex: "tpu_custom_call")
    reading = smoke.run_phase(smoke_config("qwen3-1.7b"), mode, 0,
                              smoke.CompileClock())
    assert reading["phase"] == mode
    assert reading["logit_rel_err"]["rms"] <= smoke.LOGIT_RTOL
    assert 0 < reading["decode_step_ms_p50"] <= reading["decode_step_ms_p99"]
