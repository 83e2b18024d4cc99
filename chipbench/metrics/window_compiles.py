"""Programs JAX compiled or loaded from its cache inside the measured
window (count), from the program's compile counter."""
from lib.program import compiles


def read(data):
    return compiles(data)
