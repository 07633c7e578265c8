"""Share of the window's wall time spent copying blocks into the device
arena, in %: the pool's ``fill_seconds`` over the window, host seconds
summed over the threads that fill (so it can pass 100 where several
threads fill at once)."""


def read(rec):
    t = rec["counters"].get("pool.fill_seconds")
    if t is None:
        return None
    return 100.0 * t / rec["window"]["seconds"]
