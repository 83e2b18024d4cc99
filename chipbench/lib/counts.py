"""Operations and bytes each configuration's steps need, from its sizes.

These are what a roofline share or an MFU divides by: the work the
algorithm requires, computed from the configuration file alone, never
from the program's compiled graph (a program that does extra work shows
a lower share, which is the point).

A configuration's ``program.family`` names the file
``chipbench/families/<family>.py`` that counts for it.  Its class
``Counts(config)`` gives what the per-layer readers call:

- ``n_params`` and ``matmul_params``: all weights, and those that enter
  a matrix product (the head among them);
- ``cache_bytes(slots, batch=1)``: one session's cache at ``slots``
  positions;
- ``decode_flops(slots, batch=1)`` and ``decode_bytes(slots, batch=1)``:
  the operations and the HBM bytes of one decode step against ``slots``
  cached positions.
"""
from __future__ import annotations

import pathlib

from . import bench as B

BF16 = 2


def vocab(c: dict) -> int:
    return int(c.get("padded_vocab_size", c["vocab_size"]))


def counts_for(config: dict, bench_dir: pathlib.Path = B.BENCH_DIR):
    return B.family(config["program"]["family"], bench_dir).Counts(config)
