"""Device idle time while the host dispatches the jitted scan (innermost
annotation ``repro.dispatch``: argument handling, allocation, enqueue and
any recompile), over the window, in percent; see
:mod:`chipbench.span_idle`."""
from chipbench import span_idle


def read(ctx):
    return span_idle.share(ctx, ("dispatch",))
