"""Checks on a list of trace records (``Tracer.snapshot()``), shared by
the tests that hold the program's spans to the leaf rule of PR 24: on one
thread no ``X`` record encloses or overlaps another, and the leaves of one
iteration share its ``step`` and end in one closing event."""

from collections import defaultdict


def overlapping_spans(records):
    """``(thread, earlier name, later name)`` for every pair of ``X``
    records of one thread that share more than an instant. On one thread
    two spans can only overlap by one enclosing the other."""
    by_thread = defaultdict(list)
    for r in records:
        if r["phase"] == "X":
            by_thread[r["thread_name"], r["thread_id"]].append(r)
    found = []
    for (thread, _), spans in by_thread.items():
        spans.sort(key=lambda r: (r["ts_ns"], -r["dur_ns"]))
        latest = None  # the span that ends last among those seen
        for r in spans:
            if latest is not None and r["ts_ns"] < latest["ts_ns"] + latest["dur_ns"]:
                found.append((thread, latest["name"], r["name"]))
            if latest is None or (
                r["ts_ns"] + r["dur_ns"] > latest["ts_ns"] + latest["dur_ns"]
            ):
                latest = r
    return found


def iterations(records, closing="sched_iteration_end"):
    """Records grouped by ``(thread_id, step)`` for every step that some
    ``closing`` event carries; ``{key: [records in ring order]}``."""
    closed = {
        (r["thread_id"], r["step"])
        for r in records if r["name"] == closing
    }
    out = defaultdict(list)
    for r in records:
        key = (r["thread_id"], r["step"])
        if r["step"] is not None and key in closed:
            out[key].append(r)
    return out
