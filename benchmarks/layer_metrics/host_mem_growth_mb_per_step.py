"""Megabytes (1e6 bytes) of machine memory the training loop leaves behind
per step: the least-squares slope of the program's ``host_memory`` events
(``MemTotal - MemAvailable`` at each sync point) over their ``step``, in
the run's untraced slice. Memory outside the process's RSS is in it."""

import numpy as np


def read(ctx):
    lo, hi = ctx["window_host_ns"]
    params = ctx["spec"]["params"]
    points = [
        (r["step"], r["attrs"][params["attr"]])
        for r in ctx["spans"]
        if r["name"] == params["event"] and lo <= r["ts_ns"] < hi
        and r["step"] is not None and params["attr"] in (r["attrs"] or {})
    ]
    steps = np.asarray([p[0] for p in points], np.float64)
    if len(points) < int(params.get("min_samples", 3)) or np.ptp(steps) == 0:
        return None
    used = np.asarray([p[1] for p in points], np.float64)
    slope = np.polyfit(steps - steps.mean(), used - used.mean(), 1)[0]
    return float(slope) / 1e6
