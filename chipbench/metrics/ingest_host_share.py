"""Self time of the ``consume_async`` spans (key combining, the planner's
per-chunk sample, morselizing, dispatch) over the window, in percent."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans:
        return None
    return 100.0 * T.self_us(ctx.spans, "consume_async") / ctx.window_us
