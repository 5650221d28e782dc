"""Time in the planner's per-chunk sample, the ``plan_sample`` spans (the
head slice and its key combine, the blocking read of the sample, the
Misra-Gries update, resolving or re-planning), over the window, in
percent.  A program without the span reads nothing."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans or not any(e["name"] == "plan_sample" for e in ctx.spans):
        return None
    return 100.0 * T.total_us(ctx.spans, "plan_sample") / ctx.window_us
