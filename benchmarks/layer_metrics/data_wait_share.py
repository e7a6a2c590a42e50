"""Share of the traced window inside the program's ``data_wait`` span."""

from zkbench import spans


def read(ctx):
    lo, hi = ctx["window_host_ns"]
    name = ctx["spec"]["params"]["span"]
    seconds, count = spans.span_seconds(ctx["spans"], name, lo, hi)
    if count == 0 or hi <= lo:
        return None
    return 100.0 * seconds / ((hi - lo) / 1e9)
