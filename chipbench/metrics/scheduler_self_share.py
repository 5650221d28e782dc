"""Self time of the server's ``quantum`` spans (the scheduler's own work,
the streams' pumps excluded) over the window, in percent."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans or not any(e["name"] == "quantum" for e in ctx.spans):
        return None
    return 100.0 * T.self_us(ctx.spans, "quantum") / ctx.window_us
