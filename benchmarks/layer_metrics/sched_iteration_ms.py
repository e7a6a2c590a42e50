"""Host milliseconds per decode-scheduler iteration inside a set of the
program's spans, over the iterations of the traced window.

The scheduler gives every record of one iteration the iteration's number as
``step`` (thread and step name an iteration) and closes it with one
``sched_iteration_end`` event (``params.closing_event``). The spans counted
are those named in ``params.names`` or starting with one of
``params.prefixes``.

``params.stat`` ``mean``: the milliseconds of those spans that start in the
window, over the iterations that have a record in it. An iteration longer
than the window (a closed loop's first admits every slot in one) counts as
one, with the part of it that lies inside.

``params.stat`` ``p95``: per iteration the spans' sum, the 95th percentile
over the iterations that lie whole in the window (opening leaf and closing
event both in it)."""

import numpy as np


def read(ctx):
    params = ctx["spec"]["params"]
    names = set(params.get("names", ()))
    prefixes = tuple(params.get("prefixes", ()))
    closed, opened, per_iteration = set(), set(), {}
    for r in ctx["spans"]:
        if r["step"] is None:
            continue
        key = (r["thread_id"], r["step"])
        if r["name"] == params["closing_event"]:
            closed.add(key)
        elif r["phase"] == "X":
            if r["name"] == params["opening_span"]:
                opened.add(key)
            if r["name"] in names or (prefixes and r["name"].startswith(prefixes)):
                per_iteration[key] = per_iteration.get(key, 0) + r["dur_ns"]
    mean = params["stat"] == "mean"
    over = (set(per_iteration) | closed) if mean else (closed & opened)
    if not over or len(over) < int(params.get("min_samples", 1)):
        return None
    values = [per_iteration.get(key, 0) / 1e6 for key in over]
    if mean:
        return float(np.mean(values))
    return float(np.percentile(values, float(params["stat"].lstrip("p"))))
