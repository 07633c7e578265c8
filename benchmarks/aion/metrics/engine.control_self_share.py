"""Share of the window's wall time spent inside ``advance_watermark``
and ``poll`` outside the executions they run (``exec_seconds``):
expiry, re-execution planning, staging requests, cleanup, policy, in %."""


def read(rec):
    h = rec["host"]
    return 100.0 * (h["control_s"] - h["round_s"]) / rec["window"]["seconds"]
