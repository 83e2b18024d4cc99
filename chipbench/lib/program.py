"""Reads the program's own spans and counters (``repro.core.obs``) over
the measured window, for the per-layer metrics of the session store.

The window is the benchmark's ``window`` span; both clocks are
``time.perf_counter``.  A top span (``kv.offload``, ``kv.restore``)
counts where it lies inside the window.  Each span under it is charged
its self time, its duration less the union of its children's intervals,
and that goes to the bucket of its name (``bucket``); the top span's
own self time and that of any unbucketed span is ``untraced``, so the
buckets and ``untraced`` sum to the top spans' time.

Every reading is ``None`` where the checkout's program has no tracer,
where the ring dropped events that may lie inside the window, or where
no top span lies inside it: never a number from part of the window.
"""
from __future__ import annotations

from collections import defaultdict

from .trace import WINDOW

COMPILE = "jax.compile"
CHECKSUM = "integrity.checksum"
BUCKETS = ("fetch", "checksum", "store")


def bucket(name: str) -> str:
    """The bucket that takes a span's self time."""
    if name == "ser.to_host":
        return "fetch"
    if name == CHECKSUM:
        return "checksum"
    if name.startswith(("obj.", "tx.")):
        return "store"
    return "untraced"


def _covered(intervals, lo, hi) -> float:
    """Length of the union of ``intervals`` clipped to [lo, hi]."""
    total, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            total += b - a
            end = b
    return total


def _whole(events, dropped: int, lo: float) -> bool:
    """True unless the ring dropped events that may have ended inside the
    window (a dropped event ended no later than the oldest one kept)."""
    return not dropped or (bool(events) and events[0].end < lo)


def breakdown(events, dropped: int, lo: float, hi: float,
              tops) -> dict | None:
    """Seconds per bucket over the top spans named in ``tops`` that lie
    inside [lo, hi], their count and time, their ``nbytes`` and the bytes
    checksummed under them; ``None`` as the module says."""
    if not _whole(events, dropped, lo):
        return None
    spans = [e for e in events if e.kind == "span"]
    top = [e for e in spans if e.name in tops and lo <= e.start
           and e.end <= hi]
    if not top:
        return None
    children = defaultdict(list)
    for e in spans:
        if e.parent:
            children[e.parent].append(e)
    out = {"n": len(top), "total_s": 0.0, "nbytes": 0, "checksum_bytes": 0,
           "untraced": 0.0, **{b: 0.0 for b in BUCKETS}}
    for t in top:
        out["total_s"] += t.end - t.start
        out["nbytes"] += int(t.attrs.get("nbytes", 0))
        todo = [t]
        while todo:
            e = todo.pop()
            kids = children.get(e.id, [])
            todo.extend(kids)
            own = (e.end - e.start) - _covered(
                [(k.start, k.end) for k in kids], e.start, e.end)
            out["untraced" if e is t else bucket(e.name)] += own
            if e.name == CHECKSUM:
                out["checksum_bytes"] += int(e.attrs.get("nbytes", 0))
    return out


def window_compiles(events, dropped: int, lo: float, hi: float):
    """Programs compiled or loaded inside [lo, hi]; ``None`` unless the
    compile counter was already listening when the window opened."""
    if not _whole(events, dropped, lo):
        return None
    ticks = [e for e in events if e.kind == "counter" and e.name == COMPILE]
    if not any(e.start <= lo for e in ticks):
        return None
    return int(sum(e.attrs["value"] for e in ticks if lo <= e.start <= hi))


def _read(data):
    """The program's events, its dropped count and the window, or
    ``None`` where the checkout's program has no tracer."""
    try:
        from repro.core import obs
    except ImportError:
        return None
    win = [(a, b) for n, a, b in data.rec.spans if n == WINDOW]
    if not win:
        return None
    return obs.events(), obs.dropped(), *win[0]


def split(data, *tops) -> dict | None:
    got = _read(data)
    return None if got is None else breakdown(*got, tops)


def compiles(data):
    got = _read(data)
    return None if got is None else window_compiles(*got)


def mean_ms(data, top: str, part: str):
    """Milliseconds of ``part`` (a bucket) per ``top`` span."""
    b = split(data, top)
    return None if b is None else 1e3 * b[part] / b["n"]
