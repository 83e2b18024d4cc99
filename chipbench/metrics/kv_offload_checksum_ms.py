"""Self time of ``integrity.checksum`` under the program's ``kv.offload``
span, per offload (ms)."""
from lib.program import mean_ms


def read(data):
    return mean_ms(data, "kv.offload", "checksum")
