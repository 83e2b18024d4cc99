#!/usr/bin/env python3
"""Records a small profiler trace on the chip for the reduction's tests.

    python chipbench/tools/record_trace.py <out_dir>

A jitted ``decode_step`` runs between host spans named as the drivers
name theirs (``cb:window``, ``cb:restore``, ``cb:decode``, ``cb:idle``);
the ``.xplane.pb`` is copied to ``<out_dir>/sample.xplane.pb`` and the
trace's planes and lines are printed.
"""
from __future__ import annotations

import pathlib
import shutil
import sys
import time

HERE = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

from lib import trace as T           # noqa: E402
from lib.common import Recorder, accelerator  # noqa: E402


def main() -> int:
    out = pathlib.Path(sys.argv[1])
    import jax
    import jax.numpy as jnp
    accelerator(1)

    def decode_step(w, x):
        return jnp.tanh(x @ w) @ w.T

    step = jax.jit(decode_step)
    w = jnp.ones((2048, 2048), jnp.bfloat16) * 0.01
    x = jnp.ones((8, 2048), jnp.bfloat16)
    jax.block_until_ready(step(w, x))
    tmp = out / "raw"
    shutil.rmtree(tmp, ignore_errors=True)
    rec = Recorder(trace=True)
    jax.profiler.start_trace(str(tmp))
    with rec.span(T.WINDOW):
        for _ in range(3):
            with rec.span("restore"):
                time.sleep(0.02)
            with rec.span("decode"):
                for _ in range(10):
                    x = step(w, x)
                jax.block_until_ready(x)
            with rec.span("idle"):
                time.sleep(0.01)
    jax.profiler.stop_trace()
    src = T.find_xplane(str(tmp))
    shutil.copy(src, out / "sample.xplane.pb")
    shutil.rmtree(tmp, ignore_errors=True)
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(out / "sample.xplane.pb"))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("   line", repr(line.name), len(evs),
                  [e.name for e in evs[:4]])
    print(T.reduce(T.read(str(out / "sample.xplane.pb"))))
    return 0


if __name__ == "__main__":
    sys.exit(main())
