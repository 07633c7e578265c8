"""Programs made ready inside the window: every lowering to a backend
program, compiled or loaded from the persistent cache. Set-up should
leave none."""


def read(rec):
    return rec["compiles"]["lowered"]
