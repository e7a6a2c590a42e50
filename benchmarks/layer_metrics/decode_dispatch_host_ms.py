"""Mean host milliseconds inside the program's ``decode_dispatch`` span."""

from zkbench import spans


def read(ctx):
    lo, hi = ctx["window_host_ns"]
    seconds, count = spans.span_seconds(
        ctx["spans"], ctx["spec"]["params"]["span"], lo, hi
    )
    if count == 0:
        return None
    return 1e3 * seconds / count
