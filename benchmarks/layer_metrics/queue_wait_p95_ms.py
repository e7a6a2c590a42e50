"""Queue wait from the program's own enqueue and dispatch events."""

import numpy as np

from zkbench import spans


def read(ctx):
    params = ctx["spec"]["params"]
    enq = spans.events_by_rid(ctx["spans"], params["enqueue"])
    dis = spans.events_by_rid(ctx["spans"], params["dispatch"])
    waits = [(dis[r] - enq[r]) / 1e6 for r in dis if r in enq]
    if len(waits) < int(params.get("min_samples", 1)):
        return None
    return float(np.percentile(waits, 95))
