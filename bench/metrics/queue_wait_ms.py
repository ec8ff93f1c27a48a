"""queue_wait_ms (batcher): median wait of a window request from its due time to the start of its batch, ms."""
from harness.readers import queue_wait_ms


def read(v):
    return queue_wait_ms(v)
