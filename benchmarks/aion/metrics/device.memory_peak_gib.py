"""The device memory high-water mark (``peak_bytes_in_use``) of the
fullest chip at the window's end, set-up and window together, in GiB.
The largest fold round's transient sets it, and which round that is
depends on I/O timing: its spread between runs (24-49% on a TPU v5e) is
too wide for an end-to-end bound."""


def read(rec):
    return rec["peak_bytes"] / float(1 << 30)
