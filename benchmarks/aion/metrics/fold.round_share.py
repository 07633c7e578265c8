"""Share of the window's wall time inside window executions, batched
fold rounds and single-window folds alike (the engine's
``exec_seconds`` counter over the window), in %."""


def read(rec):
    return 100.0 * rec["counters"]["exec_seconds"] / rec["window"]["seconds"]
