"""95th percentile of every query's latency in the window, submission to
result on the host (host clock); a failed query counts as never done."""
import math

import numpy as np


def read(ctx):
    if not ctx.queries:
        return None
    lat = [q.end - q.start if q.error is None else math.inf for q in ctx.queries]
    return float(np.percentile(lat, 95))
