"""window_compiles (device): backend compiles inside the measured window (JAX monitoring events); expected 0."""
from harness.readers import window_compiles


def read(v):
    return window_compiles(v)
