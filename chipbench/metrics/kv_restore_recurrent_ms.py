"""Time under the program's ``kv.leaf`` spans of the cache's recurrent
leaves (the family's ``RECURRENT_LEAVES``: the SSD state and conv tail)
per ``kv.restore`` span: their read, checksum and rebuild (ms)."""
from lib.leaves import recurrent_ms


def read(data):
    return recurrent_ms(data, "kv.restore")
