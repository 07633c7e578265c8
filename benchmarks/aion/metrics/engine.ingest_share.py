"""Share of the window's wall time spent inside ``StreamEngine.ingest``
(assign, append, late writes, re-execution planning), in %."""


def read(rec):
    return 100.0 * rec["host"]["ingest_s"] / rec["window"]["seconds"]
