"""tail_p99_ms (post-filter index): 99th percentile latency from due time over every request due in the window, ms."""
from harness.readers import tail_p99_ms


def read(v):
    return tail_p99_ms(v)
