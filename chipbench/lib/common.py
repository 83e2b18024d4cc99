"""What every cell shares: seeds, host spans, device facts, the result line.

Host spans are timed with ``time.perf_counter`` and, in a traced run, are
also written into the profiler's trace as ``cb:<name>`` annotations, so
the trace reduction can say what the host was doing in each device gap.
"""
from __future__ import annotations

import contextlib
import json
import math
import sys
import time

SPAN_PREFIX = "cb:"
# what a check reads when there is nothing to compare: above any limit,
# and a number JSON can carry (inf and nan it cannot)
NO_READING = 1e9


class BenchError(RuntimeError):
    """A cell cannot run as its files describe it."""


def split_seed(seed: int) -> tuple[int, int]:
    """A seed of any size as two 32-bit words (JAX keys take 32 bits)."""
    seed = int(seed)
    if seed < 0:
        raise BenchError(f"--seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def jax_key(seed: int, stream: int = 0):
    import jax
    lo, hi = split_seed(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    return jax.random.fold_in(key, stream)


def np_rng(seed: int, stream: int = 0):
    import numpy as np
    return np.random.default_rng([int(seed), int(stream)])


class Recorder:
    """Host spans and values of one run, kept in memory."""

    def __init__(self, trace: bool = False) -> None:
        self.trace = trace
        self.spans: list[tuple[str, float, float]] = []
        self.values: dict[str, list[float]] = {}

    @contextlib.contextmanager
    def span(self, name: str):
        ann = contextlib.nullcontext()
        if self.trace:
            import jax
            ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        t0 = time.perf_counter()
        with ann:
            yield
        self.spans.append((name, t0, time.perf_counter()))

    def durations(self, name: str) -> list[float]:
        return [b - a for n, a, b in self.spans if n == name]

    def add(self, name: str, value: float) -> None:
        self.values.setdefault(name, []).append(float(value))


def quantile(values, q: float) -> float:
    """The q-quantile (0 < q < 1) by linear interpolation between order
    statistics, as ``statistics.quantiles(method="inclusive")``."""
    xs = sorted(values)
    if not xs:
        raise BenchError("quantile of no values")
    if len(xs) == 1:
        return xs[0]
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def accelerator(chips: int):
    """The devices a cell runs on; raises unless JAX sees enough TPUs."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX's first device is "
                         f"{devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell asks for {chips} chips; JAX sees "
                         f"{len(devs)}")
    return devs[:chips]


def memory_peak_bytes(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else 0


def free_device_memory() -> None:
    """Drop every live device array: the reference runs after the
    program's state is gone, so it cannot set the memory peak."""
    import gc

    import jax
    gc.collect()
    for a in jax.live_arrays():
        a.delete()
    gc.collect()


class Check:
    """One number compared with its limit; ``ok`` when value <= limit."""

    def __init__(self, name: str, value: float, limit: float) -> None:
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return (not math.isnan(self.value)) and self.value <= self.limit

    def as_json(self) -> dict:
        return {"value": self.value, "limit": self.limit}


def emit(result: dict, checks: list[Check]) -> None:
    """Print the checks as the last lines of stderr and the result as the
    last line of stdout, the checks under the key that comes last."""
    for c in checks:
        print(f"check {c.name} = {c.value!r} limit {c.limit!r} "
              f"{'ok' if c.ok else 'FAIL'}", file=sys.stderr, flush=True)
    line = dict(result)
    line["checks"] = {c.name: c.as_json() for c in checks}
    print(json.dumps(line), flush=True)
