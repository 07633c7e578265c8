"""Share of the window's wall time spent reading the log store's files,
in %: the store's ``read_seconds`` over the window, host seconds summed
over the threads that read (so it can pass 100 where several threads
read at once)."""


def read(rec):
    t = rec["counters"].get("store.read_seconds")
    if t is None:
        return None
    return 100.0 * t / rec["window"]["seconds"]
