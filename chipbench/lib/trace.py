"""Reduction of a JAX profiler trace to device busy time, step times and
idle gaps by host span.

The trace is the ``.xplane.pb`` that ``jax.profiler.start_trace`` writes.
Device planes are ``/device:TPU:<n>``; on each, the ``XLA Ops`` line holds
one event per executed operation and the ``XLA Modules`` line one per
executed program (a jitted step).  Host spans are the benchmark's own
``cb:<name>`` annotations on the host plane; the one named
``cb:window`` bounds the measured window.
"""
from __future__ import annotations

import glob
import os
from collections import defaultdict

from .common import SPAN_PREFIX, BenchError

WINDOW = "window"
# control flow whose events contain the operations they run
CONTAINERS = ("while", "conditional", "call")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise BenchError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def op_name(text: str) -> str:
    """``%fusion.74 = bf16[...] fusion(...)`` -> ``fusion.74``."""
    head = text.split(" = ", 1)[0].strip()
    return head[1:] if head.startswith("%") else head


def _is_device_plane(name: str) -> bool:
    head = "/device:TPU:"
    return name.startswith(head) and name[len(head):].isdigit()


def _innermost(spans, lo, hi):
    """Disjoint (name, start, end) segments covering [lo, hi), each named
    after the shortest host span open over it ("outside spans" where
    none is)."""
    cuts = sorted({lo, hi, *(t for _, a, b in spans for t in (a, b)
                             if lo < t < hi)})
    starts = sorted(spans, key=lambda s: s[1])
    segs, k, open_ = [], 0, []
    for a, b in zip(cuts, cuts[1:]):
        while k < len(starts) and starts[k][1] <= a:
            open_.append(starts[k])
            k += 1
        open_ = [s for s in open_ if s[2] > a]
        name = min(open_, key=lambda s: s[2] - s[1])[0] if open_ \
            else "outside spans"
        segs.append((name, a, b))
    return segs


def read(path: str) -> dict:
    """Raw events of one trace file: host spans and, per device plane,
    its operations and programs, as (name, start_ns, end_ns)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    spans, devices = [], {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns, e.end_ns))
        elif _is_device_plane(plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = [(e.name, e.start_ns, e.end_ns)
                           for e in line.events]
                elif line.name == MODULES_LINE:
                    mods = [(e.name, e.start_ns, e.end_ns)
                            for e in line.events]
            devices[plane.name] = {"ops": ops, "modules": mods}
    return {"spans": spans, "devices": devices}


def reduce(raw: dict, n_devices: int = 1, top: int = 10) -> dict:
    """Busy and idle time over the ``cb:window`` span, device time per
    program name, the operations that took most time, and idle time by
    the innermost host span it falls in."""
    windows = [(a, b) for n, a, b in raw["spans"] if n == WINDOW]
    if not windows:
        raise BenchError("the trace holds no cb:window span")
    lo, hi = windows[0]
    planes = sorted(raw["devices"])[:n_devices]
    if not planes or not any(raw["devices"][p]["ops"] for p in planes):
        raise BenchError("the trace holds no device operations")
    busy_ns = 0.0
    modules = defaultdict(lambda: [0.0, 0])
    ops = defaultdict(float)
    gaps = []
    for p in planes:
        dev = raw["devices"][p]
        busy = _union(_clip([(a, b) for _, a, b in dev["ops"]], lo, hi))
        busy_ns += sum(b - a for a, b in busy)
        for name, a, b in dev["modules"]:
            if a >= lo and b <= hi:
                modules[name][0] += (b - a) / 1e9
                modules[name][1] += 1
        for name, a, b in dev["ops"]:
            short = op_name(name)
            if a >= lo and b <= hi and not short.startswith(CONTAINERS):
                ops[short] += (b - a) / 1e9
        prev = lo
        for a, b in busy + [(hi, hi)]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
    by_span = defaultdict(float)
    segs = _innermost([(n, a, b) for n, a, b in raw["spans"]
                       if n != WINDOW], lo, hi)
    i = 0
    for a, b in sorted(gaps):
        while i < len(segs) and segs[i][2] <= a:
            i += 1
        j = i
        while j < len(segs) and segs[j][1] < b:
            name, sa, sb = segs[j]
            by_span[name] += (min(b, sb) - max(a, sa)) / 1e9 / len(planes)
            j += 1
    window_s = (hi - lo) / 1e9
    return {
        "window_s": window_s,
        "busy_s": busy_ns / 1e9 / len(planes),
        "modules": {k: {"seconds": v[0], "count": v[1]}
                    for k, v in modules.items()},
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in by_span.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def module_time(summary: dict, part: str) -> tuple[float, int]:
    """Device seconds and executions of the programs whose name holds
    ``part`` (a jitted function's name, e.g. ``decode_step``)."""
    secs, count = 0.0, 0
    for name, m in summary["modules"].items():
        if part in name:
            secs += m["seconds"]
            count += m["count"]
    return secs, count
