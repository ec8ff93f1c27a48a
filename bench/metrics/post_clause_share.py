"""post_clause_share (planning): share of the window's served clauses planned post-filter, %."""
from harness.readers import post_clause_share


def read(v):
    return post_clause_share(v)
