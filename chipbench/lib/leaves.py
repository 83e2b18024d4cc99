"""Reads the program's ``kv.leaf`` spans (one per cache leaf, inside
``kv.offload`` and ``kv.restore``; attributes ``path`` and ``nbytes``)
over the measured window, for the per-layer metrics of a cache's
recurrent leaves.

A reading is ``None`` on the terms of ``lib/program.py`` (no tracer, a
ring that dropped events that may lie in the window, no top span in it)
and where the top spans hold no ``kv.leaf`` span: a program without
them.
"""
from __future__ import annotations

from . import bench as B
from .program import _read, breakdown


def recurrent(data) -> tuple:
    """The configuration's recurrent cache leaves, by name, as its family
    file lists them (none where it lists none)."""
    fam = B.family(data.cell.config["program"]["family"],
                   data.cell.bench_dir)
    return tuple(getattr(fam, "RECURRENT_LEAVES", ()))


def leaf_s(events, dropped: int, lo: float, hi: float, top: str,
           names) -> dict | None:
    """Seconds under the ``kv.leaf`` spans whose path ends in one of
    ``names``, directly under the ``top`` spans inside [lo, hi], and the
    number of those top spans."""
    b = breakdown(events, dropped, lo, hi, (top,))
    if b is None:
        return None
    tops = {e.id for e in events if e.kind == "span" and e.name == top
            and lo <= e.start and e.end <= hi}
    leaves = [e for e in events if e.kind == "span" and e.name == "kv.leaf"
              and e.parent in tops]
    if not leaves:
        return None
    mine = [e for e in leaves
            if str(e.attrs.get("path", "")).rsplit("/", 1)[-1] in names]
    return {"n": b["n"], "s": sum(e.end - e.start for e in mine)}


def recurrent_ms(data, top: str):
    """Milliseconds under the recurrent leaves' ``kv.leaf`` spans per
    ``top`` span (``kv.offload`` or ``kv.restore``)."""
    got = _read(data)
    names = recurrent(data)
    if got is None or not names:
        return None
    r = leaf_s(*got, top, names)
    return None if r is None else 1e3 * r["s"] / r["n"]
