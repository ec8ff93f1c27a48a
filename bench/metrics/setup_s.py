"""setup_s (end to end): process start to window start, s."""
from harness.readers import setup_s


def read(v):
    return setup_s(v)
