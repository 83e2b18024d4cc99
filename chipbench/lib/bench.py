"""Finds a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration is
``chipbench/configs/<config>.json`` (its plain reference is
``chipbench/refs/<reference>.py``), the traffic mix is
``chipbench/traffic/<traffic>.json`` and names the driver that runs it,
``chipbench/drivers/<driver>.py``; the limits its comparison with the
reference is held to are ``chipbench/limits/<cell>.json``; each
per-layer metric is read by ``chipbench/metrics/<metric>.py``; and the
configuration's ``program.family`` names ``chipbench/families/<family>.py``,
which counts its operations and bytes (``lib/counts.py``) and may add
rules for its weights (``lib/weights.py``).  Adding any of these is
adding files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib
import sys

from .common import BenchError

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    path = root / "BENCHMARK.json"
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def load_module(path: pathlib.Path, name: str | None = None):
    """Import a file by path (metric and config names hold dots)."""
    if not path.is_file():
        raise BenchError(f"no {path}")
    mod_name = name or "chipbench_" + path.stem.replace(".", "_").replace(
        "-", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict
    bench_dir: pathlib.Path = BENCH_DIR     # where its family file is

    @property
    def driver(self):
        return load_module(BENCH_DIR / "drivers" / f"{self.traffic['driver']}.py")

    @property
    def reference(self):
        return load_module(BENCH_DIR / "refs" / f"{self.config['reference']}.py")


def read_json(path: pathlib.Path) -> dict:
    if not path.is_file():
        raise BenchError(f"no {path}")
    return json.loads(path.read_text())


def cell(bench: dict, name: str, bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    found = [w for w in bench["workloads"] if w["name"] == name]
    if not found:
        raise BenchError(f"no workload {name!r} in BENCHMARK.json; known: "
                         f"{[w['name'] for w in bench['workloads']]}")
    w = found[0]
    conf = [c for c in bench["configs"] if c["name"] == w["config"]]
    if not conf:
        raise BenchError(f"workload {name!r} names no known config")
    config = read_json(ROOT / conf[0]["file"])
    # an unknown family is refused here, before any weights or compiles
    family(config["program"]["family"], bench_dir)
    traffic = read_json(bench_dir / "traffic" / f"{w['traffic']}.json")
    limits = read_json(bench_dir / "limits" / f"{name}.json")
    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        limits=limits, bench_dir=bench_dir,
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    return load_module(bench_dir / "metrics" / f"{name}.py")


def family(family: str, bench_dir: pathlib.Path = BENCH_DIR):
    """The module of an architecture family, found by its name."""
    return load_module(bench_dir / "families" / f"{family}.py",
                       "chipbench_family_" + family)


def program_config(config: dict):
    """The program's registry config for this file, checked field by
    field against the file's published sizes."""
    from repro.configs import get_arch
    cfg = get_arch(config["program"]["arch"])
    for key, field in config["program_fields"].items():
        have = getattr(cfg, field)
        if have != config[key]:
            raise BenchError(f"{config['name']}: the program's {field} is "
                             f"{have!r}, the configuration says "
                             f"{key}={config[key]!r}")
    if cfg.param_dtype != config["param_dtype"]:
        raise BenchError(f"{config['name']}: the program stores "
                         f"{cfg.param_dtype}, the configuration "
                         f"{config['param_dtype']}")
    return cfg
