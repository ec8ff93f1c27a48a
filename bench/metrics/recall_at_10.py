"""recall_at_10 (end to end): mean recall@10 of the sampled served answers against the reference."""
from harness.readers import recall_at_10


def read(v):
    return recall_at_10(v)
