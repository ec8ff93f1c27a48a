"""idle_share.steady (device): 1 - device-busy union / traced window, %, in steady cells."""
from harness.readers import idle_share


def read(v):
    return idle_share(v)
