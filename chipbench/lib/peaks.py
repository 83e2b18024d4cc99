"""Published peaks per chip, keyed by JAX's ``device_kind``.

An unknown device is an error: a roofline share against a guessed peak
would be a number with no meaning.
"""
from __future__ import annotations

import json
import pathlib

from .common import BenchError

TABLE = pathlib.Path(__file__).with_name("peaks.json")


def peaks(device_kind: str) -> dict:
    table = json.loads(TABLE.read_text())
    if device_kind not in table:
        raise BenchError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(table)}")
    return table[device_kind]
