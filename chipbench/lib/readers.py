"""Helpers the per-layer metric readers share (``chipbench/metrics``).

A reader returns ``None`` when its run holds nothing for it to read; it
never returns 0 for a share of a peak.
"""
from __future__ import annotations

from .trace import module_time


def mean_span_s(data, name: str):
    d = data.rec.durations(name)
    return sum(d) / len(d) if d else None


def idle_share_pct(data):
    s = data.summary
    if not s or s["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - s["busy_s"] / s["window_s"])


def device_step_s(data, program: str):
    """Device seconds per execution of a jitted program, by its name."""
    if not data.summary:
        return None
    secs, n = module_time(data.summary, program)
    return secs / n if n else None
