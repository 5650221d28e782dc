"""Device idle time while the host stages a chunk (innermost annotation
``repro.combine_keys`` or ``repro.morselize``), over the window, in
percent; see :mod:`chipbench.span_idle`."""
from chipbench import span_idle


def read(ctx):
    return span_idle.share(ctx, ("combine_keys", "morselize"))
