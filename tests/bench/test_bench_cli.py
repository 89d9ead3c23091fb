"""The benchmark's entry points refuse a platform other than the TPU before
printing any result, and every cell named in BENCHMARK.json resolves to its
files."""
import json

import pytest

from bench import calibrate, run, spec


@pytest.mark.parametrize("entry", [run.main, calibrate.main])
def test_refuses_the_cpu(entry, capsys):
    argv = ["--workload", "qwen3-1.7b.jd1000.decode", "--seed", "1"]
    if entry is run.main:
        argv += ["--seconds", "1", "--trace", "0"]
    else:
        argv = ["--workload", "qwen3-1.7b.jd1000.decode", "--seeds", "1"]
    with pytest.raises(SystemExit) as exc:
        entry(argv)
    assert exc.value.code == 1
    out, err = capsys.readouterr()
    assert out == ""
    assert "platform 'cpu'" in err


def test_every_cell_resolves_to_its_files():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.cell(w["name"], bench)
        assert cell.limits["widest_gap"]["limit"] > 0
        assert cell.end_to_end and cell.per_layer
        for m in cell.per_layer:
            assert callable(spec.metric_reader(m["name"]).read)
        spec.reference(cell.config["reference"])
        arch = spec.arch(cell.config["reference"])
        assert set(arch.adapter_dims(cell.config)) >= set(
            cell.traffic["adapters"]["targets"])
        assert spec.model_config(cell.config, cell.traffic).num_layers == \
            cell.config["num_hidden_layers"]
        for m in cell.per_layer:
            if m["name"].endswith("_roofline"):
                kernel = m["name"][:-len("_roofline")]
                assert arch.arch(cell.config).kernel_calls(
                    kernel, cell.traffic["adapters"], [1], [0]), kernel
    for c in bench["configs"]:
        conf = json.loads((spec.ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["reduced"] == c["reduced"]


def test_an_unknown_workload_is_an_error():
    with pytest.raises(KeyError, match="no workload"):
        spec.cell("no-such-cell")
