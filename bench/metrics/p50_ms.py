"""p50_ms (end to end): median latency from due time over every request due in the window, ms."""
from harness.readers import latency_ms


def read(v):
    return latency_ms(v, 50)
