"""Lineitem rows of every query completed in the window, over the seconds
from the window's start to the last completion (host clock)."""


def read(ctx):
    done = [q for q in ctx.queries if q.error is None]
    if not done:
        return None
    return len(done) * ctx.rows_per_query / (ctx.window_end - ctx.window_start)
