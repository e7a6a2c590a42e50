"""Of the first chip's idle seconds in the traced window (the gaps between
its busy intervals, as ``DeviceTrace.idle_gaps`` takes them, short ones
included), the share that a set of the program's spans overlaps, BY OVERLAP
and not by majority: a gap half under the spans counts half.

The spans are the ``X`` records named in ``params.names``, or with
``params.thread_of`` every ``X`` record of the thread(s) that recorded
that name (the scheduler's thread: the one that closes iterations). They
are moved onto the profiler's clock by ``DeviceTrace.host_offset_ns``.
``params.share`` ``covered`` gives the share they overlap, ``uncovered``
what is left: idle time in which that thread was inside no span at all.
A trace without the offset, without idle time or without such a span
gives ``None``."""

from zkbench import tracereduce


def read(ctx):
    params, trace = ctx["spec"]["params"], ctx["trace"]
    if trace is None or trace.host_offset_ns is None:
        return None
    records = [r for r in ctx["spans"] if r["phase"] == "X"]
    if "names" in params:
        names = set(params["names"])
        records = [r for r in records if r["name"] in names]
    else:
        threads = {
            r["thread_id"] for r in ctx["spans"]
            if r["name"] == params["thread_of"]
        }
        records = [r for r in records if r["thread_id"] in threads]
    if not records:
        return None
    idle = tracereduce.gaps(
        trace.busy_intervals(next(iter(trace.planes))), trace.lo, trace.hi
    )
    total = sum(end - start for start, end in idle)
    if total <= 0:
        return None
    offset = trace.host_offset_ns
    cover = tracereduce.union(
        (r["ts_ns"] + offset, r["ts_ns"] + r["dur_ns"] + offset)
        for r in records
    )
    overlap, i = 0.0, 0
    for start, end in idle:  # both sorted and disjoint
        while i < len(cover) and cover[i][1] <= start:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < end:
            overlap += min(end, cover[j][1]) - max(start, cover[j][0])
            j += 1
    share = overlap / total
    if params["share"] == "uncovered":
        share = 1.0 - share
    return 100.0 * share
