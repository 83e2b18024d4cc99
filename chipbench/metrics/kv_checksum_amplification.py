"""Bytes checksummed under the program's ``kv.offload`` and ``kv.restore``
spans over the cache bytes those spans moved (x): 1 would check each byte
once a direction."""
from lib.program import split


def read(data):
    b = split(data, "kv.offload", "kv.restore")
    if b is None or not b["nbytes"]:
        return None
    return b["checksum_bytes"] / b["nbytes"]
