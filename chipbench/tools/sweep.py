#!/usr/bin/env python3
"""Finds, on the chip, the highest return rate a session cell sustains.

    python chipbench/tools/sweep.py --workload <name> --seed <n>
        --returns <k> --rates <r> [<r> ...]

For each rate, one run of the cell with ``k`` returns due at that rate;
prints each return's lateness (its start after its due time) and time to
first token.  A rate is sustained when the lateness does not grow from
the first returns to the last.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import pathlib
import statistics
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
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--returns", type=int, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args()
    import jax
    base = B.cell(B.load_benchmark(), args.workload)
    devices = accelerator(base.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    for rate in args.rates:
        cell = dataclasses.replace(
            base, traffic={**base.traffic, "rate_per_s": rate})
        seconds = (args.returns - 0.5) / rate
        out = {}
        R.run_cell(cell, args.seed, seconds, False, devices,
                   time.perf_counter(), out=out)
        tl = out["extras"]["returns"]
        late = [start - due for due, start, _ in tl]
        half = len(late) // 2
        print(json.dumps({
            "rate_per_s": rate, "returns": len(tl),
            "ttft_p50_ms": 1e3 * statistics.median(t for _, _, t in tl),
            "late_first_half_s": statistics.mean(late[:half]),
            "late_second_half_s": statistics.mean(late[half:]),
            "late_s": late, "ttft_s": [t for _, _, t in tl]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
