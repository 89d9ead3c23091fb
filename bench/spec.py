"""What a cell is, read from data: ``BENCHMARK.json`` names each cell's
configuration and traffic mix, and everything else is found by name.

* configuration ``<c>``: ``bench/configs/<c>.json`` (the published
  ``config.json`` keys as run, plus ``program`` and ``reference``);
* traffic mix ``<t>``: ``bench/traffic/<t>.json``;
* the limits that decide ``correct`` for cell ``<w>``: ``bench/limits/<w>.json``;
* per-layer metric ``<m>``: ``bench/metrics/<m>.py``, whose ``read(rec)``
  returns a number or None;
* plain reference ``<r>``: ``bench/references/<r>.py``;
* architecture ``<r>``, the same name as the configuration's reference:
  ``bench/archs/<r>.py``, whose ``model_config(conf, traffic)`` gives the
  program's `ModelConfig`, ``adapter_dims(conf)`` the adapter targets'
  ``{target: (d_in, d_out)}`` and ``arch(conf)`` the cost object that the
  per-layer metrics read (``rec.arch``).

A later change adds a cell, a mix, a metric or an architecture as new files
and entries, without editing any file that is already here.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Dict

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def _load_module(path: Path, name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    # registered first, as an import would: a dataclass in the module
    # looks its module up by name
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    config: Dict
    traffic: Dict
    limits: Dict
    chips: int
    end_to_end: list
    per_layer: list


def benchmark() -> Dict:
    return _load_json(ROOT / "BENCHMARK.json")


def cell(name: str, bench: Dict | None = None) -> Cell:
    bench = bench or benchmark()
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        names = ", ".join(w["name"] for w in bench["workloads"])
        raise KeyError(f"no workload {name!r} in BENCHMARK.json ({names})")
    w = found[0]

    def applies(m):
        return "workloads" not in m or name in m["workloads"]

    return Cell(
        name=name,
        config=_load_json(BENCH_DIR / "configs" / f"{w['config']}.json"),
        traffic=_load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json"),
        limits=_load_json(BENCH_DIR / "limits" / f"{name}.json"),
        chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if applies(m)],
        per_layer=[m for m in bench["per_layer"] if applies(m)])


def metric_reader(name: str) -> ModuleType:
    return _load_module(BENCH_DIR / "metrics" / f"{name}.py",
                        "bench_metric_" + name.replace(".", "_"))


def reference(name: str) -> ModuleType:
    return _load_module(BENCH_DIR / "references" / f"{name}.py",
                        "bench_reference_" + name)


def arch(name: str) -> ModuleType:
    return _load_module(BENCH_DIR / "archs" / f"{name}.py",
                        "bench_arch_" + name)


def model_config(conf: Dict, traffic: Dict):
    """The program's `ModelConfig` for a configuration file, by its
    architecture's module."""
    return arch(conf["reference"]).model_config(conf, traffic)
