"""The program's own host spans and events (``observability/trace.py``),
taken as they are: the benchmark turns the tracer on for the traced run
and reads the ring when the traced window has closed."""

from typing import Dict, List, Sequence, Tuple


def enable(capacity: int = 1_000_000) -> None:
    from zookeeper_tpu.observability import trace

    trace.disable()
    trace.enable(capacity)


def drain() -> List[dict]:
    from zookeeper_tpu.observability import trace

    tracer = trace.get_tracer()
    records = tracer.snapshot() if tracer is not None else []
    trace.disable()
    return records


def within(records: Sequence[dict], lo_ns: int, hi_ns: int) -> List[dict]:
    """Records that start inside ``[lo, hi)`` on ``perf_counter_ns``."""
    return [r for r in records if lo_ns <= r["ts_ns"] < hi_ns]


def span_seconds(records: Sequence[dict], name: str, lo_ns: int, hi_ns: int) -> Tuple[float, int]:
    """Seconds (clipped to the window) and count of the spans ``name``."""
    total, count = 0, 0
    for r in records:
        if r["name"] != name or r["phase"] != "X":
            continue
        start, end = max(r["ts_ns"], lo_ns), min(r["ts_ns"] + r["dur_ns"], hi_ns)
        if end > start:
            total += end - start
            count += 1
    return total / 1e9, count


def as_host_spans(records: Sequence[dict]) -> List[Tuple[str, int, int]]:
    return [
        (r["name"], r["ts_ns"], r["dur_ns"])
        for r in records if r["phase"] == "X"
    ]


def events_by_rid(records: Sequence[dict], name: str) -> Dict[int, int]:
    return {
        r["rid"]: r["ts_ns"]
        for r in records if r["name"] == name and r["rid"] is not None
    }
