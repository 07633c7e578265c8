"""Median staleness of late events in the open-loop cell: for every
late event sent in the window, its due time to the wall time of the
first answer of its window given at or after its step; unanswered ones
count at their age at the window's end. The trigger's planned wait
sets most of it. Its spread between runs (13-17% on a TPU v5e) is too
wide for an end-to-end bound; the 95th percentile carries one."""

from harness import weighted_quantile


def read(rec):
    s = rec["staleness"]
    return weighted_quantile(s["ages"], s["weights"], 0.50)
