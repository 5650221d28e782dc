"""Self time of the ``consume_async`` spans over the window, in percent:
what a chunk's ingest leaves once its four child spans (``plan_sample``,
``combine_keys``, ``morselize``, ``dispatch``) are taken out: the
executors' calls between those stages and, where the planner's sample is
launched behind the chunk's scan (a carry larger than the chunk), that
launch."""
from chipbench import trace_reduce as T


def read(ctx):
    if not ctx.spans:
        return None
    return 100.0 * T.self_us(ctx.spans, "consume_async") / ctx.window_us
