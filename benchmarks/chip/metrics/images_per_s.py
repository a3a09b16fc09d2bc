"""Images of every step completed in the window over the window's wall
time (host clock, loop boundary to loop boundary)."""


def read(r):
    if not r.intervals:
        return None
    return len(r.intervals) * r.batch / r.window_s
