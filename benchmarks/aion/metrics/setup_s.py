"""Set-up time: process start to the window's start, on the host clock.

Loading, building the engine and its device arena, drawing and streaming
the history, and every compilation or load from the persistent cache
that the history's calls make."""


def read(rec):
    return rec["setup_s"]
