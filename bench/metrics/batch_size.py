"""batch_size (batcher): mean rows per batch_query call in the window."""
from harness.readers import batch_size


def read(v):
    return batch_size(v)
