"""Device milliseconds per call of the decode-step program."""


def read(ctx):
    seconds, calls = ctx["trace"].module_seconds(
        ctx["spec"]["params"]["module_contains"]
    )
    if calls == 0:
        return None
    return 1e3 * seconds / calls
