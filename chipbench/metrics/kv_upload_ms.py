"""Host time of ``jax.device_put`` of a restored cache, to the device (ms)."""
from lib.readers import mean_span_s


def read(data):
    s = mean_span_s(data, "upload")
    return None if s is None else 1e3 * s
