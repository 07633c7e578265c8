"""95th percentile of the staleness of late events: for every late event
sent in the window, its due time to the wall time of the first answer
of its window given at or after its step. Late events that no answer
covers by the window's end count at their age then, so a stall can only
raise the tail."""

from harness import weighted_quantile


def read(rec):
    s = rec["staleness"]
    return weighted_quantile(s["ages"], s["weights"], 0.95)
