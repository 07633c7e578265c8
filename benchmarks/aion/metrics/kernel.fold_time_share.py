"""Share of the traced window taken by the Pallas fold kernels on the
device (their summed device time over the window), in %."""


def read(rec):
    t = rec["trace"]
    if t is None or t["kernel_s"] <= 0:
        return None
    return 100.0 * t["kernel_s"] / t["window_s"]
