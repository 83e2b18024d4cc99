"""Tiny copies of the benchmark's cells for CPU tests.

A cell's configuration, traffic mix and end-to-end metrics come from its
entry in ``BENCHMARK.json``, through ``lib/bench.py`` as a chip run finds
them.  The program runs its smoke variant of the configuration, whose
sizes are written into the configuration; ``tiny/<cell>.json`` holds the
traffic sizes that replace the mix's and the limits for those sizes.  A
new cell's CPU tests add that file and edit none.
"""
from __future__ import annotations

import dataclasses
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
BENCH = HERE.parent
ROOT = BENCH.parent
for p in (str(ROOT / "src"), str(BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

from lib import bench as B  # noqa: E402


def tiny_cell(name: str, param_dtype: str = "float32", **traffic):
    """(cell, program config) at smoke size, built from the cell's files
    and ``tiny/<cell>.json``."""
    from repro.configs import smoke_variant
    cell = B.cell(B.load_benchmark(), name)
    tiny = B.read_json(HERE / "tiny" / f"{name}.json")
    config = cell.config
    cfg = dataclasses.replace(smoke_variant(B.program_config(config)),
                              param_dtype=param_dtype)
    for key, field in config["program_fields"].items():
        config[key] = getattr(cfg, field)
    config["param_dtype"] = cfg.param_dtype
    config["padded_vocab_size"] = cfg.padded_vocab()
    if "head_dim" in config:
        config["head_dim"] = cfg.head_dim
    cell.traffic = {**cell.traffic, **tiny["traffic"], **traffic}
    cell.limits = tiny["limits"]
    cell.per_layer = []     # a CPU has no published peaks to read against
    return cell, cfg


def run_tiny(name: str, seed: int = 7, seconds: float = 0.2,
             trace: bool = False, param_dtype: str = "float32", **traffic):
    import time

    import jax

    import run as R
    cell, cfg = tiny_cell(name, param_dtype, **traffic)
    return R.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                      time.perf_counter(), cfg=cfg)
