"""95th percentile of the gaps between consecutive tokens of one request,
from the program's own ``token_delivered`` events (one per token, by
request id, stamped by the thread that delivers it), both tokens of a gap
delivered inside the traced window. The client's ``itl_p95_ms`` times the
same gaps from outside, by polling."""

import numpy as np


def read(ctx):
    params = ctx["spec"]["params"]
    by_rid = {}
    for r in ctx["spans"]:
        if r["name"] == params["event"] and r["rid"] is not None:
            by_rid.setdefault(r["rid"], []).append(r["ts_ns"])
    gaps = [
        gap / 1e6 for stamps in by_rid.values()
        for gap in np.diff(sorted(stamps)).tolist()
    ]
    if len(gaps) < int(params.get("min_samples", 1)):
        return None
    return float(np.percentile(gaps, 95))
