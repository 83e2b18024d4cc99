"""Host time of ``KVCacheStore.restore`` per return (ms)."""
from lib.readers import mean_span_s


def read(data):
    s = mean_span_s(data, "restore")
    return None if s is None else 1e3 * s
