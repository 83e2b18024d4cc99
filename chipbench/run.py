#!/usr/bin/env python3
"""Runs one cell of the chip benchmark once.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its metrics are found by
name from ``BENCHMARK.json``.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` a ``breakdown``, and last the ``checks``: each number compared
with its limit.  Exits non-zero and prints no result where JAX finds no
TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                   # noqa: E402
import contextlib                 # noqa: E402
import pathlib                    # noqa: E402
import shutil                     # noqa: E402
import sys                        # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
for _p in (str(ROOT / "src"), str(HERE)):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from lib import bench as B        # noqa: E402
from lib import counts, peaks     # noqa: E402
from lib import trace as T        # noqa: E402
from lib.common import BenchError, Recorder, accelerator, emit  # noqa: E402

TRACE_DIR = ROOT / ".chipbench_trace"


class Ctx:
    """What a driver is given: the cell's files, the run's arguments, the
    span recorder, and the traced window."""

    def __init__(self, cell, seed, seconds, trace, devices, t_start,
                 cfg=None, control=False):
        self.cell = cell
        self.config, self.traffic = cell.config, cell.traffic
        self.limits = cell.limits
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.devices, self.t_start = devices, t_start
        self.rec = Recorder(trace)
        self.cfg = cfg if cfg is not None else B.program_config(cell.config)
        self.reference = cell.reference
        self.summary = None
        self.control = control      # also read the float8 control

    @contextlib.contextmanager
    def window(self):
        """The measured window; in a traced run, the profiler records it
        and its reduction is left in ``self.summary``."""
        if not self.trace:
            with self.rec.span(T.WINDOW):
                yield
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        # no Python tracer: over the store's host code it fills the
        # host's memory, and the reduction reads device ops and spans
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
        try:
            with self.rec.span(T.WINDOW):
                yield
        finally:
            jax.profiler.stop_trace()
        raw = T.read(T.find_xplane(str(TRACE_DIR)))
        self.summary = T.reduce(raw, n_devices=len(self.devices))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)


class LayerData:
    """What a per-layer metric reader reads."""

    def __init__(self, ctx, out, kind):
        self.rec, self.summary = ctx.rec, ctx.summary
        self.extras = out.get("extras", {})
        self.counts = counts.counts_for(ctx.config, ctx.cell.bench_dir)
        self.peaks = peaks.peaks(kind)
        self.cell = ctx.cell


def run_cell(cell, seed, seconds, trace, devices, t_start, cfg=None,
             out=None):
    """Run one cell; returns (result dict, checks).  ``out``, a dict,
    receives the driver's whole output."""
    ctx = Ctx(cell, seed, seconds, trace, devices, t_start, cfg,
              control=out is not None)
    driven = cell.driver.run(ctx)
    if out is not None:
        out.update(driven)
    out = driven
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    metrics = {}
    result = {"correct": all(c.ok for c in out["checks"]),
              "attempted": out["attempted"], "failed": out["failed"]}
    if trace:
        data = LayerData(ctx, out, dev.device_kind)
        for m in cell.per_layer:
            value = B.metric_reader(m["name"]).read(data)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.summary["busy_s"]
        device["window_s"] = ctx.summary["window_s"]
        result["breakdown"] = {"device_ops": ctx.summary["device_ops"],
                               "idle_gaps": ctx.summary["idle_gaps"]}
    else:
        for m in cell.end_to_end:
            if m["name"] in out["metrics"]:
                metrics[m["name"]] = {"value": out["metrics"][m["name"]],
                                      "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    return result, out["checks"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = B.cell(B.load_benchmark(), args.workload)
        import jax
        devices = accelerator(cell.chips)
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        # cache every program, the decode step's sub-second compile too
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        result, checks = run_cell(cell, args.seed, args.seconds,
                                  bool(args.trace), devices, T_START)
    except BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 3
    emit(result, checks)
    return 0


if __name__ == "__main__":
    sys.exit(main())
