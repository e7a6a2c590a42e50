"""Prefill device milliseconds per thousand prompt tokens computed."""


def read(ctx):
    seconds = calls = 0
    for needle in ctx["spec"]["params"]["module_contains"]:
        s, c = ctx["trace"].module_seconds(needle)
        seconds, calls = seconds + s, calls + c
    tokens = sum(n - cached for n, cached in ctx["work"]["prefills"])
    if calls == 0 or tokens <= 0:
        return None
    return 1e3 * seconds / (tokens / 1e3)
