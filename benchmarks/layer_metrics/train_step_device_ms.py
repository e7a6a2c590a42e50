"""Device-busy milliseconds per training step of the traced window."""


def read(ctx):
    steps = ctx["work"].get("steps", 0)
    busy = ctx["trace"].busy_s()
    if steps <= 0 or busy <= 0:
        return None
    return 1e3 * busy / steps
