#!/usr/bin/env python3
"""Reads, on the chip, the numbers each limit is set from.

    python chipbench/tools/control.py --workload <name> --seconds <s>
        --seeds <n> [<n> ...]

For each seed, in one process: one run of the cell as ``run.py`` makes it
(its checks: the lower readings), and the float8 control put in the
program's place over the same inputs (the upper readings), each held to
the cell's limits by the same checks; the control has to come out not
correct.  One JSON line per seed.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import run as R                       # noqa: E402
from lib import bench as B            # noqa: E402
from lib.common import accelerator   # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    cell = B.cell(B.load_benchmark(), args.workload)
    devices = accelerator(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for seed in args.seeds:
        out = {}
        result, checks = R.run_cell(cell, seed, args.seconds, False, devices,
                                    time.perf_counter(), out=out)
        control = out["control"]
        print(json.dumps({
            "seed": seed,
            "program": {"correct": result["correct"],
                        "checks": {c.name: c.as_json() for c in checks}},
            "control": {"correct": all(c.ok for c in control),
                        "checks": {c.name: c.as_json() for c in control}},
            "metrics": result["metrics"],
            "memory_peak_bytes": result["device"]["memory_peak_bytes"]}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
