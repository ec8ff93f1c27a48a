"""plan_ms (planning): mean PlannedResult.plan_overhead of one batch_query call, ms."""
from harness.readers import plan_ms


def read(v):
    return plan_ms(v)
