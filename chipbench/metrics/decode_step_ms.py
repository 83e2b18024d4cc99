"""Device time of one execution of the jitted decode step (ms)."""
from lib.readers import device_step_s


def read(data):
    s = device_step_s(data, "decode_step")
    return None if s is None else 1e3 * s
