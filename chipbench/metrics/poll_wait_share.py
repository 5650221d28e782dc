"""Self time of the ``poll`` spans (the host blocked on a chunk's control
read, growth excluded) over the window, in percent."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans:
        return None
    return 100.0 * T.self_us(ctx.spans, "poll") / ctx.window_us
