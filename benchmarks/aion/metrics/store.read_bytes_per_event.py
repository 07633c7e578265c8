"""Bytes the log store read in the window, per event ingested."""


def read(rec):
    return rec["counters"]["store_bytes_read"] / rec["window"]["events"]
