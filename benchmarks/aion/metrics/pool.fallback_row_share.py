"""Share of the rows of the window's fold rounds that missed the device
block pool and were stacked for the fallback fold, in %."""


def read(rec):
    c = rec["counters"]
    rows = c["pooled_rows"] + c["fallback_rows"]
    if rows == 0:
        return None
    return 100.0 * c["fallback_rows"] / rows
