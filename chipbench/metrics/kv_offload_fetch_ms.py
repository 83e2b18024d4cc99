"""Self time of the device-to-host copy (``ser.to_host``) under the
program's ``kv.offload`` span, per offload (ms)."""
from lib.program import mean_ms


def read(data):
    return mean_ms(data, "kv.offload", "fetch")
