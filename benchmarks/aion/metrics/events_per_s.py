"""Events ingested and processed per second of the whole window, on the
host clock: every event of every step sent in the window, over the time
from the window's start to the return of its last step."""


def read(rec):
    w = rec["window"]
    return w["events"] / w["seconds"]
