"""rows_per_s: every row of the calls in the window, over the window
(from its start to the last call's return), on the host clock."""


def read(r):
    if r.trace is not None or r.window_s <= 0:
        return None
    return sum(c.rows for c in r.calls) / r.window_s
