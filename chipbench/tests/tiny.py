"""Tiny copies of the benchmark's cells for CPU tests: the program's smoke
variant of each configuration, the configuration file's sizes rewritten
to match it, and the traffic mix shrunk."""
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

TRAFFIC = {
    "danube-session-return": {
        "sessions": [{"history": 8, "slots": 28}, {"history": 12, "slots": 32},
                     {"history": 20, "slots": 32}, {"history": 32, "slots": 32}],
        "order": [3, 0, 2, 1], "user_tokens": 4, "answer_tokens": 6,
        "rate_per_s": 40.0, "check_turns": 2, "check_pad": 64},
}


# limits for these sizes: float32 or bfloat16 at smoke size reads gaps
# of 1e-3 or less; the planted faults read far above these
LIMITS = {
    "danube-session-return": {"answer_gap_max": 0.5},
}

CELLS = {   # cell -> (configuration file, traffic file, end-to-end metrics)
    "danube-session-return": ("h2o-danube-1.8b", "session_return",
                              ["return_ttft_p50_ms", "tpot_p95_ms"]),
}


def tiny_cell(name: str, param_dtype: str = "float32", **traffic):
    """(cell, program config) at smoke size, built from the cell's
    configuration and traffic files."""
    from repro.configs import smoke_variant
    conf_name, traffic_name, e2e = CELLS[name]
    config = B.read_json(BENCH / "configs" / f"{conf_name}.json")
    cfg = dataclasses.replace(smoke_variant(B.program_config(config)),
                              param_dtype=param_dtype)
    for key, field in config["program_fields"].items():
        config[key] = getattr(cfg, field)
    config["param_dtype"] = cfg.param_dtype
    config["padded_vocab_size"] = cfg.padded_vocab()
    if "head_dim" in config:
        config["head_dim"] = cfg.head_dim
    tr = {**B.read_json(BENCH / "traffic" / f"{traffic_name}.json"),
          **TRAFFIC[name], **traffic}
    metrics = [{"name": m, "unit": "-"} for m in e2e + ["setup_s"]]
    cell = B.Cell(name=name, chips=1, config=config, traffic=tr,
                  end_to_end=metrics, per_layer=[], limits=LIMITS[name])
    return cell, cfg


def run_tiny(name: str, seed: int = 7, seconds: float = 0.2,
             trace: bool = False, param_dtype: str = "float32", **traffic):
    import time

    import jax

    import run as R
    cell, cfg = tiny_cell(name, param_dtype, **traffic)
    return R.run_cell(cell, seed, seconds, trace, jax.devices()[:1],
                      time.perf_counter(), cfg=cfg)
