"""Demand pool fills (a cold block copied into the arena because a
round needs it now) per 1,000 events ingested in the window."""


def read(rec):
    return rec["counters"]["demand_pool_fills"] * 1e3 \
        / rec["window"]["events"]
