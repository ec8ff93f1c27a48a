"""post_group_ms (execute groups): mean wall of the program's post-filter group spans, ms."""
from harness.readers import group_ms


def read(v):
    return group_ms(v, ("post",))
