"""In-process spans and counters for the store path.

``span(name, **attrs)`` times one piece of work: its name, start and end
on ``time.perf_counter`` (the clock the chip benchmark's own host spans
use), the span it runs inside, and its attributes.  Parents come from a
per-thread stack, so a span opened on an ``EventQueue`` worker thread is
a root.  Where ``jax`` is already imported, each span is also a
``jax.profiler.TraceAnnotation`` named ``repro:<name>``: in a profiler
trace it sits on the host plane, on the device trace's clock.  This
module never imports ``jax`` itself, so ``import repro.core`` stays
free of it.

The one counter is ``jax.compile``: one event per program that JAX
compiled or loaded from its persistent cache, counted by a
``jax.monitoring`` listener registered the first time a span opens with
``jax`` loaded.  That registration leaves a ``jax.compile`` event of
value 0, the point from which compiles are counted.

Events go into a ring of ``RING`` events, oldest first, in the order
they ended.  Once it is full each new event drops the oldest, and
``dropped()`` counts them: a dropped event ended no later than the
oldest event kept, so a reader whose window starts after that event's
end has lost nothing.  ``events()`` and ``dropped()`` are the read API.
"""
from __future__ import annotations

import contextlib
import itertools
import sys
import threading
import time
from collections import deque
from typing import Any, NamedTuple

PREFIX = "repro:"
RING = 65536
COMPILE = "jax.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Event(NamedTuple):
    kind: str            # "span" or "counter"
    name: str
    start: float         # time.perf_counter seconds
    end: float           # == start for a counter
    id: int              # 0 for a counter
    parent: int          # 0 for a root span or a counter
    attrs: dict          # a counter's increment is attrs["value"]


_ring: deque = deque(maxlen=RING)
_dropped = 0
_lock = threading.Lock()
_ids = itertools.count(1)
_local = threading.local()
_listening = False


def _append(make) -> None:
    """Append ``make(now)``; the clock is read under the lock, so the
    ring holds events in the order they ended, across threads too."""
    global _dropped
    with _lock:
        if len(_ring) == _ring.maxlen:
            _dropped += 1
        _ring.append(make(time.perf_counter()))


def _count(name: str, value: float, **attrs: Any) -> None:
    """One counter event, stamped now."""
    attrs["value"] = value
    _append(lambda t: Event("counter", name, t, t, 0, 0, attrs))


def _on_duration(event: str, duration: float, **kw: Any) -> None:
    if event == _COMPILE_EVENT:
        _count(COMPILE, 1, program=str(kw.get("fun_name", "?")))


def _listen() -> None:
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    _count(COMPILE, 0)


@contextlib.contextmanager
def span(name: str, **attrs: Any):
    """Time the block as one span; yields its attributes, which the block
    may add to (a size known only at the end).  As a decorator, each call
    of the function is one span."""
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    parent = stack[-1] if stack else 0
    sid = next(_ids)
    ann = contextlib.nullcontext()
    if "jax" in sys.modules:
        if not _listening:
            _listen()
        ann = sys.modules["jax"].profiler.TraceAnnotation(PREFIX + name)
    stack.append(sid)
    with ann:
        t0 = time.perf_counter()
        try:
            yield attrs
        finally:
            stack.pop()
            _append(lambda t1: Event("span", name, t0, t1, sid, parent,
                                     attrs))


def events() -> list[Event]:
    """Every event the ring holds, oldest first."""
    with _lock:
        return list(_ring)


def dropped() -> int:
    """How many events the full ring has dropped since the process began."""
    return _dropped
