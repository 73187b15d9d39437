"""95th percentile of latency from the due time, in ms, over every request
of the window (host clock, untraced window); a failed or unanswered request
counts as the wait horizon."""

import numpy as np


def read(ctx):
    lat = ctx.latencies_ms
    return float(np.percentile(lat, 95)) if len(lat) else None
