"""Share of the batches the loader's producer thread assembled into a
recycled host buffer (``data/pipeline.py``'s ``HostBatchRing``), over the
run's untraced slice (``window_host_ns``): the ``recycled`` attribute
(``params.attr``) of its ``loader_assemble`` spans (``params.span``).

A span without the attribute made no batch (the pass's last pull, which
found the iterator exhausted) or comes from a program without the ring:
none with it reads nothing."""


def read(ctx):
    lo, hi = ctx["window_host_ns"]
    params = ctx["spec"]["params"]
    flags = [
        r["attrs"][params["attr"]]
        for r in ctx["spans"]
        if r["name"] == params["span"] and r["phase"] == "X"
        and lo <= r["ts_ns"] < hi
        and (r["attrs"] or {}).get(params["attr"]) is not None
    ]
    if not flags:
        return None
    return 100.0 * sum(flags) / len(flags)
