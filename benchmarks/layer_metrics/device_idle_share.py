"""1 - union of the device's op intervals over the traced window."""


def read(ctx):
    share = ctx["trace"].idle_share()
    return None if share is None else 100.0 * share
