"""Device idle time while the host is in the planner's per-chunk sample
(innermost annotation ``repro.plan_sample``), over the window, in percent;
see :mod:`chipbench.span_idle`."""
from chipbench import span_idle


def read(ctx):
    return span_idle.share(ctx, ("plan_sample",))
