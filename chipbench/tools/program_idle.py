#!/usr/bin/env python3
"""Device idle time by the program's own spans, from one traced run.

    python chipbench/tools/program_idle.py --workload <name> --seed <n>
        [--seconds <s>] [--keep <dir>]

Runs the cell once as ``run.py --trace 1`` does and prints its result
line, keeping the run's ``.xplane.pb`` in ``--keep``.  From that trace
it prints the device's idle seconds by the innermost ``repro:`` span
open over them (the program's spans, ``repro.core.obs``), or where none
is open by the innermost ``cb:`` span (the benchmark's own), and, for
each ``repro:`` span name inside the window, how its durations in the
trace agree with the same spans as the program recorded them.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import time
from collections import defaultdict

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(HERE.parent / "src"), str(HERE)]

import run as R                       # noqa: E402
from lib import bench as B            # noqa: E402
from lib import trace as T            # noqa: E402
from lib.common import SPAN_PREFIX, Recorder, accelerator, emit  # noqa: E402

PROGRAM_PREFIX = "repro:"


def host_spans(path: str) -> list:
    """(name, start_ns, end_ns) of every ``repro:`` and ``cb:`` span on
    the trace's host planes, prefixes kept."""
    from jax.profiler import ProfileData
    return [(e.name, e.start_ns, e.end_ns)
            for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith((PROGRAM_PREFIX, SPAN_PREFIX))]


def idle_by_span(raw: dict, spans: list, top: int = 40) -> dict:
    """The trace reduction with idle time charged to the shortest span
    open over it: a ``repro:`` span wherever one is open, since each lies
    inside the ``cb:`` span of the benchmark's call that opened it."""
    window = SPAN_PREFIX + T.WINDOW
    named = [(T.WINDOW if n == window else n, a, b) for n, a, b in spans]
    return T.reduce({"spans": named, "devices": raw["devices"]}, top=top)


def agreement(spans: list, events, trace_window, window) -> dict:
    """Per ``repro:`` name: how many spans lie inside the window in the
    trace and in the program's own events, and the largest difference
    between the i-th one's duration in each (microseconds)."""
    (tlo, thi), (lo, hi) = trace_window, window
    prof = defaultdict(list)
    for n, a, b in spans:
        if n.startswith(PROGRAM_PREFIX) and tlo <= a and b <= thi:
            prof[n[len(PROGRAM_PREFIX):]].append((a, (b - a) / 1e3))
    mem = defaultdict(list)
    for e in events:
        if e.kind == "span" and lo <= e.start and e.end <= hi:
            mem[e.name].append((e.start, 1e6 * (e.end - e.start)))
    out = {}
    for name in sorted(set(prof) | set(mem)):
        got, had = sorted(prof[name]), sorted(mem[name])
        diffs = [abs(p - m) for (_, p), (_, m) in zip(got, had)]
        out[name] = {"trace": len(got), "program": len(had),
                     "trace_s": sum(d for _, d in got) / 1e6,
                     "program_s": sum(d for _, d in had) / 1e6,
                     "max_diff_us": max(diffs) if diffs else None}
    return out


class _KeptRecorder(Recorder):
    """The run's span recorder, kept for the window's host times."""
    last = None

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        _KeptRecorder.last = self


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--keep", default=str(HERE.parent / ".chipbench_keep"))
    args = ap.parse_args()
    t_start = time.perf_counter()
    bench = B.load_benchmark()
    cell = B.cell(bench, args.workload)
    seconds = args.seconds or bench["run_seconds"]
    import jax
    devices = accelerator(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    keep = pathlib.Path(args.keep)
    keep.mkdir(parents=True, exist_ok=True)
    kept = keep / f"{args.workload}.{args.seed}.xplane.pb"
    find = T.find_xplane

    def find_and_keep(trace_dir):
        path = find(trace_dir)
        shutil.copy(path, kept)
        return path

    T.find_xplane, R.Recorder = find_and_keep, _KeptRecorder
    try:
        result, checks = R.run_cell(cell, args.seed, seconds, True, devices,
                                    t_start)
    finally:
        T.find_xplane, R.Recorder = find, Recorder
    emit(result, checks)

    from repro.core import obs
    raw = T.read(str(kept))
    spans = host_spans(str(kept))
    s = idle_by_span(raw, spans)
    trace_window, = [(a, b) for n, a, b in raw["spans"] if n == T.WINDOW]
    window, = [(a, b) for n, a, b in _KeptRecorder.last.spans
               if n == T.WINDOW]
    print(json.dumps({
        "trace": str(kept), "trace_bytes": kept.stat().st_size,
        "window_s": s["window_s"], "busy_s": s["busy_s"],
        "idle_by_span": s["idle_gaps"], "dropped": obs.dropped(),
        "agreement": agreement(spans, obs.events(), trace_window,
                               window)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
