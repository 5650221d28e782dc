"""The queries' share of the cell's bandwidth roofline over device busy
time, in percent: (queries completed x bytes each query must move, from
the configuration) / (chips x one chip's peak HBM bytes/s), over the busy
seconds of the trace, a mean per chip.  The cell's chips share each
query's bytes, so on four chips each moves a quarter of them; with one
chip the divisor is one chip's peak.  It counts the query's work, not one
implementation's."""


def read(ctx):
    s = ctx.summary
    done = [q for q in ctx.queries if q.error is None]
    if s is None or s.busy_s <= 0 or not done:
        return None
    least_s = len(done) * ctx.bytes_per_query / (ctx.chips * ctx.peaks["hbm_bytes_per_s"])
    return 100.0 * least_s / s.busy_s
