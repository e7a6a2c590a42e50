"""Mean milliseconds per batch inside one of the loader's producer-thread
spans, over the run's untraced slice (``window_host_ns``, profiler off).

A batch is one ``loader_stage`` span (``params.per``): the producer's last
pull of a pass finds the iterator exhausted and makes no batch, so the
seconds inside ``params.span`` are divided by the batches staged, not by
the span's own count."""

from zkbench import spans


def read(ctx):
    lo, hi = ctx["window_host_ns"]
    params = ctx["spec"]["params"]
    seconds, count = spans.span_seconds(ctx["spans"], params["span"], lo, hi)
    _, batches = spans.span_seconds(ctx["spans"], params["per"], lo, hi)
    if count == 0 or batches == 0:
        return None
    return 1e3 * seconds / batches
