"""Queries per fused batch in the window: the scheduler's counters
``sssp_scheduler_queries_done_total`` over ``..._batches_total``."""


def read(rec):
    c = rec["counters"]
    if not c or not c["batches"]:
        return None
    return c["queries_done"] / c["batches"]
