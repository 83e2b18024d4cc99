"""Self time of the object and transaction layers (``obj.*``, ``tx.*``)
under the program's ``kv.restore`` span, per restore (ms)."""
from lib.program import mean_ms


def read(data):
    return mean_ms(data, "kv.restore", "store")
