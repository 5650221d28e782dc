"""Time in the ``pause_migrate_resume`` spans (section 4.4 growth) over the
window, in percent.  A window with spans but no growth reads 0."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans:
        return None
    return 100.0 * T.total_us(ctx.spans, "pause_migrate_resume") / ctx.window_us
