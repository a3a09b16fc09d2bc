"""Tokens of every step completed in the window over the window's wall
time (host clock, loop boundary to loop boundary), for a model that
trains on tokens."""


def read(r):
    if not r.intervals or not r.tokens:
        return None
    return len(r.intervals) * r.tokens / r.window_s
