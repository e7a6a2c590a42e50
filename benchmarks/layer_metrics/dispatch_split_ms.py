"""One part of the program's dispatch span, in mean milliseconds over the
spans that start in the traced window (``params.span``, the decode step's
``decode_dispatch``).

Inside the span the engine records one ``dispatch_enqueued`` event
(``params.event``) when the compiled call has returned and the cache is
swapped; on the device the same step is one ``XLA Modules`` event whose
name holds ``params.module_contains``. ``params.part``:

- ``enqueue``: the event less the span's start, the host's time to launch
  a step (program records alone).
- ``launch_lag``: the module's start on the first chip less the span's
  start moved onto the profiler's clock (``DeviceTrace.host_offset_ns``).
- ``readback_lag``: the span's end less that module's end: the readback,
  the thread's wake-up and the host's return.

Spans and modules are paired in order, a module to the span that holds its
midpoint, so ``launch_lag`` + the module's time + ``readback_lag`` is the
span whatever the offset's error: the error only moves time between the
two lags. A trace without the event, without a module or without the
offset gives ``None``."""


def read(ctx):
    params = ctx["spec"]["params"]
    if params["part"] == "enqueue":
        values, last = [], {}
        # in the order the ring holds them: a span closes after its events
        for r in sorted(ctx["spans"], key=lambda r: r["ts_ns"] + r["dur_ns"]):
            if r["name"] == params["event"]:
                last[r["thread_id"]] = r["ts_ns"]
            elif r["phase"] == "X" and r["name"] == params["span"]:
                at = last.pop(r["thread_id"], None)
                if at is not None and at >= r["ts_ns"]:
                    values.append(at - r["ts_ns"])
    else:
        spans = [
            r for r in ctx["spans"]
            if r["phase"] == "X" and r["name"] == params["span"]
        ]
        values = _lags(ctx["trace"], spans, params)
    if not values:
        return None
    return sum(values) / len(values) / 1e6


def _lags(trace, spans, params):
    if trace is None or trace.host_offset_ns is None:
        return []
    plane = trace.planes[next(iter(trace.planes))]
    modules = sorted(
        (start, start + dur) for name, start, dur, _ in plane["modules"]
        if params["module_contains"] in name
    )
    launch = params["part"] == "launch_lag"
    values, i = [], 0
    for r in sorted(spans, key=lambda r: r["ts_ns"]):
        s0 = r["ts_ns"] + trace.host_offset_ns
        s1 = s0 + r["dur_ns"]
        while i < len(modules) and sum(modules[i]) / 2 < s0:
            i += 1
        if i < len(modules) and sum(modules[i]) / 2 < s1:
            m0, m1 = modules[i]
            values.append(m0 - s0 if launch else s1 - m1)
            i += 1
    return values
