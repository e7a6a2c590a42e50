"""Busiest expert's rows over the mean expert's, a layer, averaged over the
layers: from the per-dispatch counts the device summed."""

import numpy as np


def read(ctx):
    name = ctx["spec"]["params"]["event"]
    total = None
    for r in ctx["spans"]:
        if r["name"] != name:
            continue
        counts = np.asarray((r.get("attrs") or {}).get("counts", ()), np.float64)
        if counts.ndim != 2:
            continue
        total = counts if total is None else total + counts
    if total is None or not np.all(total.sum(axis=1) > 0):
        return None
    return float(np.mean(total.max(axis=1) / total.mean(axis=1)))
